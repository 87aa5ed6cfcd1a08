"""Marker lint: every ``pytest.mark.X`` in tests/ must be declared.

Tier-1 excludes ``-m 'not slow'`` work to stay under its time budget —
but a typo'd marker (``@pytest.mark.slw``) silently keeps an expensive
test IN tier-1, and an undeclared one only warns. This AST scan turns
both into a hard failure: the set of markers used across the test tree
must be a subset of pyproject's declared markers plus pytest builtins.
"""

import ast
import pathlib

_TESTS = pathlib.Path(__file__).parent
_PYPROJECT = _TESTS.parent / "pyproject.toml"

# markers pytest itself defines; always legal
_BUILTIN = {
    "parametrize",
    "skip",
    "skipif",
    "xfail",
    "usefixtures",
    "filterwarnings",
}


def declared_markers():
    try:
        import tomllib
    except ImportError:  # py<3.11
        import tomli as tomllib  # type: ignore[no-redef]
    with open(_PYPROJECT, "rb") as f:
        data = tomllib.load(f)
    lines = data["tool"]["pytest"]["ini_options"].get("markers", [])
    return {line.split(":", 1)[0].strip() for line in lines}


def used_markers():
    """(marker, file, lineno) for every pytest.mark.<name> attribute."""
    used = []
    for path in sorted(_TESTS.glob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Attribute):
                continue
            val = node.value
            if (
                isinstance(val, ast.Attribute)
                and val.attr == "mark"
                and isinstance(val.value, ast.Name)
                and val.value.id == "pytest"
            ):
                used.append((node.attr, path.name, node.lineno))
    return used


def test_all_markers_declared():
    legal = declared_markers() | _BUILTIN
    rogue = [
        f"{fn}:{ln}: pytest.mark.{m}"
        for m, fn, ln in used_markers()
        if m not in legal
    ]
    assert not rogue, (
        "undeclared pytest markers (declare in pyproject.toml "
        "[tool.pytest.ini_options] markers, or fix the typo):\n"
        + "\n".join(rogue)
    )


def test_slow_marker_still_declared():
    """Tier-1's ``-m 'not slow'`` filter depends on this declaration."""
    assert "slow" in declared_markers()


def _module_slow_marked(tree) -> bool:
    """True when the module sets a top-level ``pytestmark`` that
    includes ``pytest.mark.slow`` (whole file excluded from tier-1)."""
    for node in tree.body:
        if not isinstance(node, ast.Assign):
            continue
        if not any(
            isinstance(t, ast.Name) and t.id == "pytestmark"
            for t in node.targets
        ):
            continue
        for sub in ast.walk(node.value):
            if isinstance(sub, ast.Attribute) and sub.attr == "slow":
                return True
    return False


def _test_functions(tree):
    """Top-level (incl. class-nested) test functions with their decorator
    lists."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name.startswith("test_"):
                out.append(node)
    return out


def _fn_slow_marked(fn) -> bool:
    for dec in fn.decorator_list:
        for sub in ast.walk(dec):
            if isinstance(sub, ast.Attribute) and sub.attr == "slow":
                return True
    return False


_MESH_AXES = ("dp", "fsdp", "tp", "pp", "ep", "sp")


def _multi_axis_mesh_devices(fn) -> int:
    """Largest statically-known device count among MULTI-AXIS
    ``MeshConfig(...)`` calls in a function; 0 when there is none.
    ``-1`` (fill the remaining devices) counts as reaching the suite's
    8 virtual devices."""
    best = 0
    for node in ast.walk(fn):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "MeshConfig"
        ):
            continue
        sizes = [
            kw.value.value
            for kw in node.keywords
            if kw.arg in _MESH_AXES
            and isinstance(kw.value, ast.Constant)
            and isinstance(kw.value.value, int)
        ]
        explicit = [s for s in sizes if s > 1]
        fills = any(s == -1 for s in sizes)
        if len(explicit) + (1 if fills else 0) < 2:
            continue
        total = 1
        for s in explicit:
            total *= s
        if fills:
            total = max(total, 8)
        best = max(best, total)
    return best


def _compiles_train_step(fn) -> bool:
    return any(
        isinstance(node, ast.Name) and node.id == "TrainStepBuilder"
        for node in ast.walk(fn)
    )


def test_mesh_zoo_step_compiles_are_slow():
    """A test that builds a multi-axis mesh over all 8 virtual devices
    AND compiles a train step through it is a mesh-zoo matrix entry —
    each one costs multiple multi-device SPMD compiles (~10s each on
    this backend), and the update-sharding matrix keeps growing. Those
    tests must carry ``slow`` (per-function mark or module
    ``pytestmark``) so tier-1 stays inside its 870s budget. Cheap
    multi-axis uses — plan resolution, eval_shape, checkpoint layout
    math — stay fast; the lint keys on the mesh build AND the
    ``TrainStepBuilder`` reference together."""
    rogue = []
    for path in sorted(_TESTS.glob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        if _module_slow_marked(tree):
            continue
        for fn in _test_functions(tree):
            if _fn_slow_marked(fn):
                continue
            if _multi_axis_mesh_devices(fn) >= 8 and _compiles_train_step(fn):
                rogue.append(f"{path.name}:{fn.lineno}: {fn.name}")
    assert not rogue, (
        "multi-axis mesh (≥8 devices) train-step compiles must be "
        "marked slow (add @pytest.mark.slow or a module pytestmark):\n"
        + "\n".join(rogue)
    )


def test_process_spawning_fault_tests_are_slow():
    """Files importing ``elastic_harness`` at module level spawn real
    master/agent/worker PROCESSES — the fault-injection drills. Every
    test in such a file must carry ``slow`` (module ``pytestmark`` or a
    per-function mark): a process-spawning eviction/kill drill that
    slips into tier-1 blows its time budget and flakes under load.
    In-process injectors (elastic/faults.py used directly) stay fast
    and belong in tier-1.
    """
    rogue = []
    for path in sorted(_TESTS.glob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        imports_harness = False
        for node in tree.body:  # module level only, by design
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            else:
                continue
            if any(
                n == "elastic_harness" or n.startswith("elastic_harness.")
                for n in names
            ):
                imports_harness = True
                break
        if not imports_harness or _module_slow_marked(tree):
            continue
        for fn in _test_functions(tree):
            if not _fn_slow_marked(fn):
                rogue.append(f"{path.name}:{fn.lineno}: {fn.name}")
    assert not rogue, (
        "process-spawning fault-injection tests not marked slow (add "
        "@pytest.mark.slow, or a module-level pytestmark):\n"
        + "\n".join(rogue)
    )


def _imports_pallas_paged(tree) -> bool:
    """Module-level import of the paged-attention kernel module."""
    mod_name = "dlrover_tpu.ops.pallas_paged"
    for node in tree.body:  # module level only, by design
        if isinstance(node, ast.Import):
            if any(
                a.name == mod_name or a.name.startswith(mod_name + ".")
                for a in node.names
            ):
                return True
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod == mod_name or mod.startswith(mod_name + "."):
                return True
            if mod == "dlrover_tpu.ops" and any(
                a.name == "pallas_paged" for a in node.names
            ):
                return True
    return False


def test_pallas_paged_importers_are_interpret_units_or_slow():
    """Direct ``ops.pallas_paged`` consumers outside the interpret-mode
    kernel unit files (``test_pallas*``) are serving integration tests:
    they drive jitted decode loops over page pools, which belongs in
    the slow tier. The interpret-mode unit files stay in tier-1 — they
    are the cheap CPU-executable coverage of the kernel bodies — and so
    does ``test_tpu_compile.py``, which compiles the kernels for a
    described chip and runs nothing."""
    rogue = []
    for path in sorted(_TESTS.glob("*.py")):
        if path.name.startswith("test_pallas"):
            continue  # interpret-mode kernel unit files
        if path.name == "test_tpu_compile.py":
            continue  # compile-only kernel unit file
        tree = ast.parse(path.read_text(), filename=str(path))
        if not _imports_pallas_paged(tree) or _module_slow_marked(tree):
            continue
        for fn in _test_functions(tree):
            if not _fn_slow_marked(fn):
                rogue.append(f"{path.name}:{fn.lineno}: {fn.name}")
    assert not rogue, (
        "ops.pallas_paged importers outside interpret-mode unit files "
        "must be slow-marked (add @pytest.mark.slow or a module "
        "pytestmark):\n" + "\n".join(rogue)
    )


# ---------------------------------------------------------------------------
# tier-1 duration ledger
# ---------------------------------------------------------------------------
# Tests measured >= ~9s on the tier-1 backend whose property is already
# covered by a faster sibling were moved to the slow tier to keep the
# suite inside its 870s budget (measured: the pre-rebalance fast tier
# ran ~1077s). This ledger pins that decision: each entry must exist
# AND must not collect under ``-m 'not slow'``. Removing a mark without
# updating the ledger is a hard failure; deleting/renaming the test
# fails the existence check so the ledger can't rot silently.
_SLOW_LEDGER = [
    "test_fused_block.py::test_blockwise_cadences_match_stepwise[5]",
    "test_fused_block.py::test_blockwise_cadences_match_stepwise[8]",
    "test_fused_block.py::test_blockwise_cadences_match_stepwise[13]",
    "test_fused_block.py::test_blockwise_cadences_match_stepwise[64]",
    "test_fused_block.py::test_blockwise_eval_cadence_and_final_partial_block",
    "test_fused_block.py::test_blockwise_data_exhaustion_runs_partial_block",
    "test_estimator.py::test_train_and_evaluate_exports_best",
    "test_estimator.py::test_estimator_resume_from_latest",
    "test_estimator.py::test_estimator_incremental_restore",
    "test_estimator.py::test_evaluator_role_watches_checkpoints",
    "test_estimator.py::test_estimator_executor_env_cluster_and_resume",
    "test_sentinels.py::test_sentinels_add_no_device_to_host_transfers",
    "test_watchdog.py::test_nan_drill_end_to_end",
    "test_trainer.py::test_elastic_remesh_resume",
    "test_trainer.py::test_prefetch_to_device_preserves_stream",
    "test_model.py::test_sharded_init_and_step",
    "test_moe.py::test_train_step_threads_jitter_rng",
    "test_moe.py::test_ragged_no_truncation_under_imbalance",
    "test_elastic.py::test_restart_hits_persistent_compile_cache",
    "test_rl.py::test_dpo_trainer_shifts_preference",
    "test_sparse_serving.py::test_server_crash_failover_without_migration",
    # serving migration drills: two replica servers (four jit compiles)
    # plus a mid-stream kill each — far past the tier-1 budget
    "test_serving_migration.py::test_migration_drill_zero_reprefill_bitwise",
    "test_serving_migration.py::"
    "test_faulted_migration_degrades_to_reprefill[torn]",
    "test_serving_migration.py::"
    "test_faulted_migration_degrades_to_reprefill[stall]",
    "test_serving_migration.py::test_wait_all_backoff_with_slow_straggler",
    # serving observability drills: replica pairs with tracing on and
    # an injected stall — same two-compiles-plus-kill cost profile
    "test_serving_observability.py::"
    "test_tracing_drill_merged_trace_has_rid_span_chain",
    "test_serving_observability.py::"
    "test_slo_breach_drill_capture_and_healthcheck_naming",
    # prefix-sharing migration drill: a replica pair with two slots
    # sharing refcounted pages, killed mid-decode — same cost profile
    "test_serving_prefix.py::test_migration_drill_with_shared_pages_in_flight",
    # prefix-sharing engine drills: each stands up one-or-two engines
    # (a jit compile apiece) and streams a donor to completion. The
    # hit-path property they share is pinned fast by
    # test_prefix_hit_fast_pin (one compile, bf16/paged/spec-off);
    # the full {mode} x {kernel} x {spec} parity matrix, byte-identity
    # under sharer eviction, COW isolation, and lookahead admission run
    # on the slow tier.
    "test_serving_prefix.py::"
    "test_prefix_hit_stream_bitwise_equals_cold[0-True-bf16]",
    "test_serving_prefix.py::"
    "test_prefix_hit_stream_bitwise_equals_cold[0-True-int8]",
    "test_serving_prefix.py::"
    "test_prefix_hit_stream_bitwise_equals_cold[0-False-bf16]",
    "test_serving_prefix.py::"
    "test_prefix_hit_stream_bitwise_equals_cold[0-False-int8]",
    "test_serving_prefix.py::"
    "test_prefix_hit_stream_bitwise_equals_cold[3-True-bf16]",
    "test_serving_prefix.py::"
    "test_prefix_hit_stream_bitwise_equals_cold[3-True-int8]",
    "test_serving_prefix.py::"
    "test_prefix_hit_stream_bitwise_equals_cold[3-False-bf16]",
    "test_serving_prefix.py::"
    "test_prefix_hit_stream_bitwise_equals_cold[3-False-int8]",
    "test_serving_prefix.py::test_int8_hit_equals_int8_cold_stream[True]",
    "test_serving_prefix.py::test_int8_hit_equals_int8_cold_stream[False]",
    "test_serving_prefix.py::test_sharer_eviction_never_perturbs_sharee",
    "test_serving_prefix.py::test_cow_tail_page_isolates_writes",
    "test_serving_prefix.py::"
    "test_hit_aware_lookahead_admits_past_blocked_cold_head",
    "test_serving_prefix.py::test_lookahead_zero_preserves_head_of_line",
    "test_serving_prefix.py::"
    "test_sharing_off_engine_reports_inert_prefix_stats",
    # second budget rebalance (PR 16): the fast tier had crept back to
    # ~1220s wall on the 1-cpu box (870s budget) as PRs 13-15 grew the
    # suite. Coarse e2e drills whose core properties keep a faster
    # tier-1 sibling (or a cheaper representative parametrization)
    # moved to the slow tier; every one still runs under -m slow.
    "test_observability.py::test_runtime_timer_samples_real_op_breakdown",
    "test_fused_ce.py::test_loss_fn_fused_matches_unfused",
    "test_fused_ce.py::test_fused_ce_under_tp_mesh_falls_back",
    "test_sentinels.py::test_replicated_sentinels_detect_injected_nan",
    "test_trainer.py::test_trainer_resumes_from_checkpoint",
    "test_trainer.py::test_trainer_drives_auto_accelerate_plan",
    "test_trainer.py::test_trainer_early_stopping_and_control_flags",
    "test_trainer.py::test_trainer_callbacks_fire_and_log_lr",
    "test_trainer.py::test_trainer_data_exhaustion_stops_cleanly",
    "test_pallas_norm.py::test_decoder_fused_norm_matches_unfused",
    "test_rl.py::test_model_engine_roles_and_update",
    "test_rl.py::test_prompt_lens_bound_the_bidirectional_prefix",
    "test_rl.py::test_prefix_lm_cached_matches_full",
    "test_rl.py::test_decode_step_logits_match_forward",
    "test_rl.py::test_cached_generation_matches_uncached_greedy",
    "test_rl.py::test_cached_rollout_speedup",
    "test_rl.py::test_rollout_reads_training_actor_buffers",
    "test_elastic.py::test_prewarm_produces_the_exact_step_executable",
    "test_model_families.py::test_window_forward_on_sequence_parallel_mesh",
    "test_model_families.py::test_glm_forward_on_sequence_parallel_mesh",
    "test_model_families.py::test_glm_sample_runs_uncached",
    "test_model_families.py::test_parallel_residual_forward_and_grads",
    "test_estimator.py::test_estimator_trains_checkpoints_and_prunes",
    "test_serving_spec.py::test_greedy_spec_on_bitwise_equal_greedy[False]",
    "test_serving_spec.py::test_int8_spec_on_equals_spec_off[True]",
    "test_serving_spec.py::test_int8_spec_on_equals_spec_off[False]",
    "test_serving_spec.py::test_oracle_draft_accepts_everything",
    "test_serving_spec.py::test_wrong_draft_rejects_everything_same_output",
    "test_serving_spec.py::test_rejected_draft_rows_never_reach_pools",
    "test_serving_spec.py::test_spec_counters_flow_to_serving_record",
    "test_serving_sampling.py::"
    "test_sampled_engine_matches_offline_bitwise[True-0]",
    "test_serving_sampling.py::"
    "test_sampled_engine_matches_offline_bitwise[False-3]",
    "test_serving_sampling.py::"
    "test_sampled_engine_matches_offline_bitwise[True-3]",
    "test_serving_sampling.py::test_seed_stable_across_slot_reordering",
    "test_serving_sampling.py::"
    "test_poisoned_request_fails_future_and_loop_survives",
    "test_moe.py::test_alltoall_matches_dense_dispatch",
    "test_moe.py::test_ragged_sharded_matches_local",
    "test_model.py::test_streamed_offload_serializes_leaf_transfers",
    "test_generate_cache.py::test_external_cache_rollout_bitwise_identical",
    "test_mup.py::test_zip_infshapes_on_decoder_params",
    "test_fused_block.py::test_mid_block_stop_flag_stops_at_boundary",
    "test_fused_block.py::test_mid_block_save_flag_honored_at_next_boundary",
    "test_kube_http.py::test_pod_watcher_survives_410_by_relisting",
    "test_kube_http.py::test_reconcile_loop_over_real_http_client",
    "test_operator.py::test_operator_entrypoint_main_loop_over_http",
    # third budget rebalance (PR 17): the new fast additions are tiny,
    # but the full fast tier measured 915s wall against the 870s budget
    # on the 1-cpu box. The four heaviest remaining fast tests (58s +
    # 35s + 23s + 22s, each a coarse double-compile or full-Trainer
    # composition with a faster tier-1 sibling) moved to the slow tier.
    "test_model.py::test_logical_axes_match_params",
    "test_model.py::test_remat_matches_no_remat",
    "test_observability.py::test_runtime_timer_in_trainer",
    "test_model.py::test_moe_forward",
    "test_model_families.py::test_glm_loss_and_grads_with_prefix_batch",
    "test_model_families.py::test_flash_kernel_window_matches_reference",
    "test_trainer.py::test_trainer_loss_decreases",
    "test_sentinels.py::test_fused_block_sentinels_are_stacked",
    "test_estimator.py::test_estimator_survives_master_outage",
    # disaggregated prefill/decode drills (PR 17): every entry stands up
    # a role-typed fleet (two-plus jit compiles) against a unified
    # reference replica; the affinity-gate property keeps fast units in
    # the same file.
    "test_serving_disagg.py::"
    "test_disagg_bitwise_parity_matrix[0-True-bf16]",
    "test_serving_disagg.py::"
    "test_disagg_bitwise_parity_matrix[0-True-int8]",
    "test_serving_disagg.py::"
    "test_disagg_bitwise_parity_matrix[0-False-bf16]",
    "test_serving_disagg.py::"
    "test_disagg_bitwise_parity_matrix[0-False-int8]",
    "test_serving_disagg.py::"
    "test_disagg_bitwise_parity_matrix[3-True-bf16]",
    "test_serving_disagg.py::"
    "test_disagg_bitwise_parity_matrix[3-True-int8]",
    "test_serving_disagg.py::"
    "test_disagg_bitwise_parity_matrix[3-False-bf16]",
    "test_serving_disagg.py::"
    "test_disagg_bitwise_parity_matrix[3-False-int8]",
    "test_serving_disagg.py::test_one_shot_handoff_parity",
    "test_serving_disagg.py::test_torn_fragment_retries_and_stays_bitwise",
    "test_serving_disagg.py::test_torn_beyond_retries_degrades_to_reprefill",
    "test_serving_disagg.py::"
    "test_mid_stream_prefill_kill_cancels_or_repoints_exactly_once",
    "test_serving_disagg.py::test_mid_stream_decode_kill_collapses_to_unified",
    "test_serving_disagg.py::"
    "test_prefix_affinity_skips_prefill_and_stale_plan_bounces",
    # SLO-driven autoscaling drills (PR 18): live fleets (two-plus jit
    # compiles apiece) driven through scale-out, live-drain scale-in,
    # and oscillating load; the decision logic keeps fast pure units in
    # the same file (synthetic signals + fake clock, no replicas).
    "test_serving_autoscale.py::test_burst_scale_out_restores_p99_bitwise",
    "test_serving_autoscale.py::"
    "test_scale_in_drains_live_zero_loss_and_detached_is_not_dead",
    "test_serving_autoscale.py::"
    "test_live_oscillating_load_one_decision_per_cooldown",
    # brain auto-tuner drills (PR 19): each compiles real jitted steps
    # (a TrainStepBuilder rebuild, or an engine pair for retune parity)
    # and drives versioned revisions through them; the planner math and
    # ladder units (synthetic records, injected clock, no jit) stay
    # tier-1 in the same file.
    "test_brain_tuner.py::test_tuning_replan_drill_loss_continuity",
    "test_brain_tuner.py::test_serving_retune_bitwise_parity",
    # tiered sparse-serving drills (PR 20): each stands up the
    # recommendation serving loop (serving.sparse_engine) and, for the
    # reshard drill, three KvServer processes; the tiered-table,
    # prefetcher, cold-store and partition-property units in the same
    # files stay tier-1.
    "test_sparse_serving.py::test_ps_reshard_drill_mid_traffic",
]


def _collected_ids(extra_args):
    import subprocess
    import sys

    out = subprocess.run(
        [
            sys.executable, "-m", "pytest", str(_TESTS), "-q",
            "--collect-only", "-p", "no:cacheprovider",
            "--continue-on-collection-errors", *extra_args,
        ],
        capture_output=True, text=True, cwd=str(_TESTS.parent),
        timeout=300,
    )
    return {
        line.strip().split("::", 1)[0].rsplit("/", 1)[-1]
        + "::" + line.strip().split("::", 1)[1]
        for line in out.stdout.splitlines()
        if "::" in line and not line.startswith(" ")
    }


def test_slow_ledger_entries_exist_and_stay_out_of_tier1():
    everything = _collected_ids([])
    fast = _collected_ids(["-m", "not slow"])
    missing = [t for t in _SLOW_LEDGER if t not in everything]
    assert not missing, (
        "slow-ledger entries no longer exist (renamed/deleted test? "
        "update _SLOW_LEDGER):\n" + "\n".join(missing)
    )
    leaked = [t for t in _SLOW_LEDGER if t in fast]
    assert not leaked, (
        "tier-1 budget regression: these heavyweight tests lost their "
        "slow mark and collect into the fast tier again:\n"
        + "\n".join(leaked)
    )


def _imports_serving_migration(tree) -> bool:
    """Module-level import of the live KV-page migration layer."""
    mod_name = "dlrover_tpu.serving.migration"
    for node in tree.body:  # module level only, by design
        if isinstance(node, ast.Import):
            if any(
                a.name == mod_name or a.name.startswith(mod_name + ".")
                for a in node.names
            ):
                return True
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod == mod_name or mod.startswith(mod_name + "."):
                return True
            if mod == "dlrover_tpu.serving" and any(
                a.name == "migration" for a in node.names
            ):
                return True
    return False


def test_serving_migration_importers_are_unit_file_or_slow():
    """``serving.migration`` consumers outside its own unit-test file
    (``test_serving_migration.py``) are failover drills: they stand up
    replica pairs, kill one mid-stream, and migrate live pages — slow
    tier by construction. The unit file keeps the cheap wire-format
    coverage in tier-1; everyone else must be slow-marked so a new
    drill can't silently blow the 870s budget."""
    rogue = []
    for path in sorted(_TESTS.glob("*.py")):
        if path.name == "test_serving_migration.py":
            continue  # the unit-test file: fast wire coverage lives here
        tree = ast.parse(path.read_text(), filename=str(path))
        if not _imports_serving_migration(tree) or _module_slow_marked(tree):
            continue
        for fn in _test_functions(tree):
            if not _fn_slow_marked(fn):
                rogue.append(f"{path.name}:{fn.lineno}: {fn.name}")
    assert not rogue, (
        "serving.migration importers outside its unit-test file must be "
        "slow-marked (add @pytest.mark.slow or a module pytestmark):\n"
        + "\n".join(rogue)
    )


def _imports_serving_e2e(tree) -> bool:
    """Module-level import of the serving SERVER or REPLICA layer —
    both spin background serve threads and jit-compile the decode
    engine. ``sparse_engine`` counts too: its server runs the same
    background loop and its drills add multiprocess KvServers on top.
    Engine/scheduler/kv_cache unit imports stay fast."""
    e2e = (
        "dlrover_tpu.serving.server",
        "dlrover_tpu.serving.replica",
        "dlrover_tpu.serving.disagg",
        "dlrover_tpu.serving.sparse_engine",
    )
    for node in tree.body:  # module level only, by design
        if isinstance(node, ast.Import):
            if any(
                a.name == m or a.name.startswith(m + ".")
                for a in node.names
                for m in e2e
            ):
                return True
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if any(mod == m or mod.startswith(m + ".") for m in e2e):
                return True
            if mod == "dlrover_tpu.serving" and any(
                a.name in ("server", "replica", "disagg", "sparse_engine")
                for a in node.names
            ):
                return True
    return False


def _fn_imports_serving_e2e(fn) -> bool:
    """Function-BODY import of serving.server/replica/sparse_engine
    (the drill idiom: import inside the test so tier-1 collection stays
    light)."""
    e2e = (
        "dlrover_tpu.serving.server",
        "dlrover_tpu.serving.replica",
        "dlrover_tpu.serving.disagg",
        "dlrover_tpu.serving.sparse_engine",
    )
    for node in ast.walk(fn):
        if isinstance(node, ast.Import):
            if any(
                a.name == m or a.name.startswith(m + ".")
                for a in node.names
                for m in e2e
            ):
                return True
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if any(mod == m or mod.startswith(m + ".") for m in e2e):
                return True
            if mod == "dlrover_tpu.serving" and any(
                a.name in ("server", "replica", "disagg", "sparse_engine")
                for a in node.names
            ):
                return True
    return False


def test_serving_e2e_function_imports_are_slow():
    """A test that imports serving.server/replica INSIDE its body is
    still an e2e serving drill — the function-level import dodges the
    module-level rule below but pays the same background-thread +
    two-jit-compiles cost at run time. Such tests must carry ``slow``
    themselves (helpers shared by several drills are exempt; the drills
    calling them are what collect)."""
    rogue = []
    for path in sorted(_TESTS.glob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        if _module_slow_marked(tree):
            continue
        for fn in _test_functions(tree):
            if _fn_slow_marked(fn):
                continue
            if _fn_imports_serving_e2e(fn):
                rogue.append(f"{path.name}:{fn.lineno}: {fn.name}")
    assert not rogue, (
        "function-level serving server/replica imports in non-slow "
        "tests (add @pytest.mark.slow, or a module-level pytestmark):\n"
        + "\n".join(rogue)
    )


def _fn_references(fn, names):
    """Subset of ``names`` referenced anywhere in a function body —
    bare names and attribute accesses both count."""
    found = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and node.id in names:
            found.add(node.id)
        elif isinstance(node, ast.Attribute) and node.attr in names:
            found.add(node.attr)
    return found


def test_autoscaler_fleet_drills_are_slow():
    """A test referencing BOTH ``ServingAutoScaler`` and
    ``ServingReplica`` is an autoscaling FLEET drill: it stands up live
    replicas (a jit compile plus a background loop apiece) and drives
    the scale loop against them — slow tier by construction. The scale
    loop's pure decision units (synthetic signal dicts + a fake clock,
    ``evaluate()`` only) reference no replica class and stay in tier-1,
    which is the whole point of keeping ``evaluate`` pure."""
    targets = {"ServingAutoScaler", "ServingReplica"}
    rogue = []
    for path in sorted(_TESTS.glob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        if _module_slow_marked(tree):
            continue
        for fn in _test_functions(tree):
            if _fn_slow_marked(fn):
                continue
            if _fn_references(fn, targets) == targets:
                rogue.append(f"{path.name}:{fn.lineno}: {fn.name}")
    assert not rogue, (
        "autoscaler fleet drills (ServingAutoScaler + ServingReplica) "
        "must be slow-marked (add @pytest.mark.slow or a module "
        "pytestmark):\n" + "\n".join(rogue)
    )


def test_brain_tuner_e2e_drills_are_slow():
    """A test referencing ``BrainTuner`` together with a step-building
    layer (``TrainStepBuilder``) or a live engine (``ServingEngine``)
    is a telemetry→config loop drill: it compiles real jitted steps
    and drives versioned revisions through them — slow tier by
    construction. The tuner's pure ladder units (synthetic records +
    an injected clock, no jit anywhere) reference neither class and
    stay in tier-1, which is the whole point of keeping the ladders
    pure."""
    engines = {"TrainStepBuilder", "ServingEngine"}
    rogue = []
    for path in sorted(_TESTS.glob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        if _module_slow_marked(tree):
            continue
        for fn in _test_functions(tree):
            if _fn_slow_marked(fn):
                continue
            refs = _fn_references(fn, engines | {"BrainTuner"})
            if "BrainTuner" in refs and refs & engines:
                rogue.append(f"{path.name}:{fn.lineno}: {fn.name}")
    assert not rogue, (
        "brain tuner e2e drills (BrainTuner + TrainStepBuilder/"
        "ServingEngine) must be slow-marked (add @pytest.mark.slow or "
        "a module pytestmark):\n" + "\n".join(rogue)
    )


def test_serving_e2e_tests_are_slow():
    """Files importing the serving server/replica layer at module level
    run end-to-end serving loops: background threads driving jitted
    prefill+decode over the paged KV cache, and (replica) failover
    drills. Every test in such a file must carry ``slow`` — an e2e
    serving run that slips into tier-1 pays two jit compiles per config
    and flakes under load. Allocator/scheduler/engine-math unit tests
    import those modules directly and stay in tier-1.
    """
    rogue = []
    for path in sorted(_TESTS.glob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        if not _imports_serving_e2e(tree) or _module_slow_marked(tree):
            continue
        for fn in _test_functions(tree):
            if not _fn_slow_marked(fn):
                rogue.append(f"{path.name}:{fn.lineno}: {fn.name}")
    assert not rogue, (
        "serving e2e tests not marked slow (add @pytest.mark.slow, or "
        "a module-level pytestmark):\n" + "\n".join(rogue)
    )
