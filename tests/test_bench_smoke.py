"""bench.py bit-rot guard: the driver runs bench.py on real hardware at
round end, where an import error or schema regression would surface too
late to fix. Run the cheap pieces here on the CPU mesh."""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ENV = dict(
    os.environ,
    JAX_PLATFORMS="cpu",
    XLA_FLAGS="--xla_force_host_platform_device_count=1",
    # the sentinel cost probe compiles a SECOND train step per --single
    # run — too expensive for the CPU smoke tier; the schema test turns
    # it back on for exactly one run
    DLROVER_TPU_SENTINEL_PROBE="0",
)


def _run(args, timeout, env_extra=None):
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"), *args],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=dict(_ENV, **(env_extra or {})),
        cwd=_REPO,
    )


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """bench's measured paths refuse to run off the chip. The schema of
    their record is still worth pinning on the CPU, so the test — not an
    option of the program — tells the one probe it sees a v5e and lets a
    step without Pallas kernels through."""
    sys.path.insert(0, _REPO)
    import bench

    from dlrover_tpu.common import device

    monkeypatch.setattr(
        device, "require_tpu",
        lambda: device.DeviceInfo("tpu", "TPU v5 lite", 1),
    )
    monkeypatch.setattr(device, "require_kernels", lambda compiled, what: 0)
    monkeypatch.setenv("DLROVER_TPU_SENTINEL_PROBE", "0")
    return bench


@pytest.mark.slow  # tier-1 budget: full bench run; schema readers stay fast
def test_bench_single_tiny_emits_schema(as_on_the_chip, monkeypatch):
    monkeypatch.setenv("DLROVER_TPU_SENTINEL_PROBE", "1")
    rec = as_on_the_chip.run_config("tiny", 2, 64, "none")
    for key in ("metric", "value", "unit", "vs_baseline", "device",
                "tokens_per_sec", "flop_expansion_est", "tpu_custom_calls"):
        assert key in rec, key
    assert rec["unit"] == "fraction_of_peak"
    assert rec["value"] > 0
    # the sentinel cost probe ran and recorded a real on-vs-off delta
    # (the <1% acceptance number is a TPU claim; on CPU just require
    # the probe to have produced a measurement, not fallen to None)
    assert rec["sentinel_overhead_frac"] is not None


@pytest.mark.slow  # tier-1 budget: full bench run; schema readers stay fast
def test_bench_single_block_k_mode(as_on_the_chip):
    """Fused-block bench (block_k>1): same schema as block_k=1, plus the
    block fields, so the k=8-vs-k=1 host-overhead comparison stays
    runnable on real hardware."""
    rec = as_on_the_chip.run_config("tiny", 2, 64, "none", block_k=4)
    assert rec["block_k"] == 4
    assert ",k4," in rec["metric"]
    assert rec["value"] > 0
    assert rec["host_dispatch_us_per_step"] >= 0


def test_bench_measured_paths_refuse_the_cpu():
    """No chip, no number: the kernel check, the matmul ceiling and a
    train attempt all fail on the CPU instead of reporting a pass, an
    empty record or an MFU against a made-up peak."""
    for args in (["--check"], ["--ceiling"],
                 ["--single", "tiny", "2", "64", "none"]):
        out = _run(args, timeout=120)
        assert out.returncode != 0, args
        assert "needs a TPU" in out.stderr, args
        assert not out.stdout.strip(), args


@pytest.mark.slow  # tier-1 budget: full bench run; schema readers stay fast
def test_bench_single_save_qkv_offload_recipe(as_on_the_chip):
    """The promoted gpt2 remat policy runs end-to-end on CPU (offload
    residency is a no-op there; the policy/plumbing is what's smoked)."""
    rec = as_on_the_chip.run_config("tiny", 2, 64, "save_qkv_offload")
    assert rec["value"] > 0
    assert rec["flop_expansion_est"] == pytest.approx(1.233, abs=1e-3)


def test_attempt_budgets_fit_deadline():
    """The documented `timeout 900 python bench.py` must always reach
    the tiny config: per-attempt budgets may not exceed the deadline."""
    sys.path.insert(0, _REPO)
    try:
        import bench
    finally:
        sys.path.remove(_REPO)
    assert sum(a[4] for a in bench._ATTEMPTS) <= bench._DEADLINE_S
    # the seq-matched companion must stay locked to the ladder
    assert bench._BASELINE_SEQ_COMPANION == bench._ATTEMPTS[1][:4]


def test_gpt2_attempt_promoted_off_full_remat():
    """ISSUE 3 acceptance: the gpt2-1.5b attempt (and thus the fallback
    block, which derives from it) runs an offload remat policy, not
    full; the on-device kernel gate covers the narrow d=64 head shape."""
    sys.path.insert(0, _REPO)
    try:
        import bench
    finally:
        sys.path.remove(_REPO)
    assert bench._GPT2_FALLBACK[0] == "gpt2-1.5b"
    assert bench._GPT2_FALLBACK[3] == "save_qkv_offload"
    assert "save_qkv_offload" in bench._FLOP_EXPANSION
    assert any(d == 64 for _h, d in bench._KERNEL_CHECK_SHAPES)
    # the narrow shape must exercise auto head-packing incl. odd heads
    assert (25, 64) in bench._KERNEL_CHECK_SHAPES


def test_failure_classifier_buckets():
    """Failed attempts now emit a machine-readable `failure` field so
    the round-end driver can tell an OOM (retry smaller batch) from a
    compile error (fix the kernel) from a deadline kill."""
    sys.path.insert(0, _REPO)
    try:
        import bench
    finally:
        sys.path.remove(_REPO)
    cf = bench._classify_failure
    assert cf(1, "RESOURCE_EXHAUSTED: out of HBM") == "oom"
    assert cf(1, "jaxlib ... ResourceExhausted while allocating") == "oom"
    assert cf(1, "Allocation failure on device") == "oom"
    assert cf(1, "Mosaic lowering failed for fused kernel") == \
        "compile_error"
    assert cf(1, "XlaCompile: Compilation failure in backend") == \
        "compile_error"
    assert cf(None, "") == "timeout"
    assert cf(None, "anything at all") == "timeout"
    assert cf(2, "Traceback (most recent call last): ValueError") == \
        "error"
    # OOM wins over compile wording when both appear (an OOM during
    # compilation is still actionable as an OOM)
    assert cf(1, "Compilation failure: RESOURCE_EXHAUSTED") == "oom"


def test_overlap_and_bucket_models_scale_with_zero2():
    """zero2 pays the gradient exchange once per microbatch: the
    overlap estimate scales the reduce-scatter wire by grad_accum, and
    the suggested bucket grows so the recurring launch cost stays
    amortized. zero1 (one deferred exchange) passes through unscaled."""
    sys.path.insert(0, _REPO)
    try:
        import bench
    finally:
        sys.path.remove(_REPO)
    stats = {"bytes_by_op": {"reduce-scatter": 1e8, "all-gather": 1e8}}
    base = bench.overlap_report(stats, step_us=10_000.0)
    z1 = bench.overlap_report(
        stats, step_us=10_000.0, grad_accum=4, update_mode="zero1"
    )
    z2 = bench.overlap_report(
        stats, step_us=10_000.0, grad_accum=4, update_mode="zero2"
    )
    rs = lambda r: r["per_op"]["reduce-scatter"]["wire_us"]  # noqa: E731
    assert rs(z1) == rs(base)
    assert rs(z2) == pytest.approx(4 * rs(base))
    # the all-gather param return happens once per step either way
    assert z2["per_op"]["all-gather"]["wire_us"] == \
        pytest.approx(base["per_op"]["all-gather"]["wire_us"])

    grad_bytes = 4e9
    mb1 = bench.suggest_bucket_mb(grad_bytes, launch_us=100.0)
    mb2 = bench.suggest_bucket_mb(
        grad_bytes, launch_us=100.0, grad_accum=4, update_mode="zero2"
    )
    assert mb2 >= mb1
    # zero1 with accum is a single exchange: same answer as accum=1
    assert bench.suggest_bucket_mb(
        grad_bytes, launch_us=100.0, grad_accum=4, update_mode="zero1"
    ) == mb1


def test_drill_recovery_metric_reads_artifact(tmp_path, monkeypatch):
    """The bench record embeds the eviction drill's recovery_s so the
    BENCH and DRILL artifacts share one comparable trajectory number."""
    sys.path.insert(0, _REPO)
    try:
        import bench
    finally:
        sys.path.remove(_REPO)
    p = tmp_path / "DRILL_test.json"
    p.write_text(json.dumps({
        "recovery_budget_s": 30,
        "failures": [
            {"kind": "slice_loss", "recovery_s": 4.2},
            {
                "kind": "host_eviction_live_reshard",
                "recovery_s": 1.7,
                "restore_tier": "live",
            },
        ],
    }))
    got = bench.drill_recovery_metric(str(p))
    assert got["recovery_s"] == pytest.approx(4.2)
    assert got["kind"] == "slice_loss"
    assert got["live_reshard_recovery_s"] == pytest.approx(1.7)
    assert got["budget_s"] == 30
    assert got["n_failures"] == 2
    # env override wins; missing/corrupt artifacts degrade to None
    monkeypatch.setenv("DLROVER_TPU_DRILL_ARTIFACT", str(p))
    assert bench.drill_recovery_metric()["recovery_s"] == \
        pytest.approx(4.2)
    assert bench.drill_recovery_metric(str(tmp_path / "nope.json")) is None
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert bench.drill_recovery_metric(str(bad)) is None
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"failures": []}))
    assert bench.drill_recovery_metric(str(empty)) is None


def test_nonmatmul_residue_derivation():
    """`nonmatmul_us_per_step` = step time minus the matmuls-only
    counterfactual (executed flops at the shape's measured chained-
    matmul rate), clamped at 0, absent without a measured ceiling."""
    sys.path.insert(0, _REPO)
    try:
        import bench
    finally:
        sys.path.remove(_REPO)
    rec = {
        "tokens_per_sec": 100_000.0,
        "mxu_tflops": 150.0,
        "mxu_ceiling_frac": 0.75,
        "model_tflops_per_sec": 100.0,
    }
    # step = 8192/1e5 s = 81920us; shape_rate = (150/0.75)*0.75 = 150;
    # residue = 81920 * (1 - 100/150)
    got = bench._nonmatmul_us_per_step(rec, "llama-1.4b", 1, 8192, "none")
    assert got == pytest.approx(81920 * (1 - 100 / 150), abs=0.1)
    # faster-than-ceiling (long-seq flash) clamps to 0, never negative
    fast = dict(rec, model_tflops_per_sec=200.0)
    assert bench._nonmatmul_us_per_step(
        fast, "llama-1.4b", 1, 8192, "none"
    ) == 0.0
    # CPU smoke runs carry no ceiling -> no field
    assert bench._nonmatmul_us_per_step(
        {"tokens_per_sec": 1.0}, "llama-1.4b", 1, 8192, "none"
    ) is None
    # gpt2 family is judged against its own shape-set ceiling
    g = dict(rec, mxu_ceiling_frac_gpt2_shapes=0.5)
    got_g = bench._nonmatmul_us_per_step(g, "gpt2-1.5b", 1, 8192, "none")
    # shape_rate = (150/0.75)*0.5 = 100 -> executed == rate -> 0 residue
    assert got_g == 0.0
    # remat expansion raises executed flops and shrinks the residue
    assert bench._nonmatmul_us_per_step(
        rec, "llama-1.4b", 1, 8192, "full"
    ) < bench._nonmatmul_us_per_step(rec, "llama-1.4b", 1, 8192, "none")


@pytest.mark.slow  # a full threaded serve run (two jit compiles) in a
# subprocess — the one bench smoke too heavy for the tier-1 budget
def test_bench_serve_mode_emits_schema():
    """`bench.py serve` is the serving half of the trajectory: decode
    tokens/sec at a fixed p99 target plus the paged-KV memory story.
    The headline fields must be present AND measured (non-None), and
    the int8 geometry must beat bf16 residency by 2d/(d+4) — 1.6x at
    the toy's 16-wide heads (real head widths: test_serving_kv_cache)."""
    out = _run(["serve", "int8", "4"], timeout=540)
    assert out.returncode == 0, out.stderr[-800:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["unit"] == "new_tokens_per_sec"
    assert rec["serve_tokens_per_s"] is not None
    assert rec["serve_tokens_per_s"] > 0
    assert rec["serve_p99_ms"] is not None
    assert rec["serve_p99_ms"] >= rec["serve_p50_ms"] > 0
    assert rec["p99_target_ms"] > 0
    # per-phase latency axes, measured from the scheduler's log-bucketed
    # histograms: TTFT/TPOT resolve the interactive SLO story that the
    # e2e percentile alone can't
    assert rec["ttft_p99_ms"] >= rec["ttft_p50_ms"] > 0
    assert rec["tpot_p99_ms"] >= rec["tpot_p50_ms"] > 0
    assert rec["queue_wait_p99_ms"] >= 0
    assert rec["ttft_p99_ms"] <= rec["serve_p99_ms"]
    assert rec["kv_cache"]["mode"] == "int8"
    assert rec["kv_cache"]["reduction_vs_bf16"] >= 1.6
    assert (
        rec["kv_cache"]["resident_bytes_int8"]
        < rec["kv_cache"]["resident_bytes_bf16"]
    )
    # the speculative arm rode along: spec-on throughput at the same
    # p99 target plus the measured acceptance rate (reported honestly —
    # no assertion that spec wins on the CPU test backend)
    spec = rec["speculative"]
    assert spec["spec_k"] > 0
    assert spec["tokens_per_s"] > 0
    assert spec["draft_tokens"] > 0
    assert 0.0 <= spec["accept_rate"] <= 1.0
    assert spec["accepted_tokens"] <= spec["draft_tokens"]
    assert spec["speedup_vs_specoff"] > 0
    # the migration drill rode along: kill → first post-migration token
    # on the survivor via the live page-migration path, with the token
    # savings over the re-prefill failover it replaced
    migr = rec["migration"]
    assert migr is not None, "migration drill never reached mid-stream"
    assert migr["path"] == "live"
    assert migr["migrated"] == 2 and migr["re_prefilled"] == 0
    assert migr["bytes_moved"] > 0
    assert migr["tokens_saved_vs_reprefill"] > 0
    assert rec["migration_recovery_s"] is not None
    assert rec["migration_recovery_s"] > 0


@pytest.mark.slow  # tier-1 budget: full subprocess bench run; schema readers stay fast
def test_bench_sparse_serve_mode_emits_schema():
    """`bench.py sparse_serve` is the recommender half of the serving
    trajectory: request QPS at a fixed p99 over the tiered embedding
    stack, prefetch-on vs prefetch-off at the same seed. The acceptance
    bar rides in the artifact: the lookahead prefetcher must be worth
    >= 2x QPS at the calibrated cold-tier profile, and the f32 served
    outputs must be exactly equal between the arms."""
    out = _run(["sparse_serve", "80", "8"], timeout=540)
    assert out.returncode == 0, out.stderr[-800:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["unit"] == "requests_per_sec"
    assert rec["sparse_qps"] > 0
    assert rec["sparse_qps_prefetch_off"] > 0
    assert rec["sparse_prefetch_speedup"] >= 2.0
    assert rec["sparse_p99_ms"] > 0
    assert rec["sparse_p99_target_ms"] > 0
    assert rec["sparse_p99_met"] is True
    # correctness half: prefetch moves rows between tiers, never values
    assert rec["sparse_outputs_exact_equal"] is True
    tiers = rec["tiers"]
    on, off = tiers["prefetch_on"], tiers["prefetch_off"]
    # calibrated profile: the off arm faulted essentially everything in
    # the request path; the on arm's prefetcher absorbed most of it
    assert off["cold_faults"] > 0 and off["prefetch_coverage"] == 0.0
    assert on["prefetched"] > 0
    assert on["prefetch_coverage"] > 0.5
    assert on["hot_hit_rate"] > off["hot_hit_rate"]
    assert 0.0 <= on["hot_hit_rate"] <= 1.0
    assert on["promote_latency_avg_ms"] >= 0
    # both arms served the whole trace out of the same row population
    assert rec["demoted_rows"] > 0
    assert on["hot_rows"] == off["hot_rows"]


def test_sparse_serving_trajectory_metric_reads_artifact(
    tmp_path, monkeypatch
):
    """The train record embeds the last sparse-serving bench's
    QPS-at-p99 + tier gauges from its own SPARSE_SERVE_*.json artifact
    family — old SERVE_*.json artifacts replay byte-for-byte unchanged
    (pinned in test_serving_trajectory_metric_reads_artifact)."""
    sys.path.insert(0, _REPO)
    try:
        import bench
    finally:
        sys.path.remove(_REPO)
    p = tmp_path / "SPARSE_SERVE_test.json"
    p.write_text(json.dumps({
        "sparse_qps": 310.5,
        "sparse_p99_ms": 240.0,
        "sparse_p99_target_ms": 10000.0,
        "sparse_p99_met": True,
        "sparse_prefetch_speedup": 7.1,
        "sparse_outputs_exact_equal": True,
        "tiers": {"prefetch_on": {
            "hot_hit_rate": 0.97, "prefetch_coverage": 0.98,
            "promote_latency_avg_ms": 9.2,
        }},
    }))
    got = bench.sparse_serving_trajectory_metric(str(p))
    assert got == {
        "sparse_qps": 310.5,
        "sparse_p99_ms": 240.0,
        "sparse_p99_target_ms": 10000.0,
        "sparse_p99_met": True,
        "sparse_prefetch_speedup": 7.1,
        "sparse_outputs_exact_equal": True,
        "sparse_hot_hit_rate": 0.97,
        "sparse_prefetch_coverage": 0.98,
        "sparse_promote_latency_avg_ms": 9.2,
    }
    monkeypatch.setenv("DLROVER_TPU_SPARSE_SERVE_ARTIFACT", str(p))
    assert bench.sparse_serving_trajectory_metric()["sparse_qps"] == \
        pytest.approx(310.5)
    # a tiers-less artifact projects only the headline block
    bare = tmp_path / "SPARSE_SERVE_bare.json"
    bare.write_text(json.dumps({"sparse_qps": 100.0}))
    got_bare = bench.sparse_serving_trajectory_metric(str(bare))
    assert got_bare["sparse_qps"] == pytest.approx(100.0)
    assert "sparse_hot_hit_rate" not in got_bare
    # missing/corrupt/unmeasured artifacts degrade to None
    assert bench.sparse_serving_trajectory_metric(
        str(tmp_path / "nope.json")
    ) is None
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert bench.sparse_serving_trajectory_metric(str(bad)) is None
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"sparse_qps": None}))
    assert bench.sparse_serving_trajectory_metric(str(empty)) is None
    # an old SERVE artifact is NOT a sparse artifact: the reader wants
    # the sparse headline and degrades to None rather than projecting
    old_serve = tmp_path / "SERVE_old.json"
    old_serve.write_text(json.dumps({
        "serve_tokens_per_s": 123.4, "serve_p99_ms": 80.5,
    }))
    assert bench.sparse_serving_trajectory_metric(str(old_serve)) is None


def test_serving_trajectory_metric_reads_artifact(tmp_path, monkeypatch):
    """The train bench record embeds the last serving bench's
    tokens/s-at-p99 (same cross-artifact pattern as the drill metric)."""
    sys.path.insert(0, _REPO)
    try:
        import bench
    finally:
        sys.path.remove(_REPO)
    p = tmp_path / "SERVE_test.json"
    p.write_text(json.dumps({
        "serve_tokens_per_s": 123.4,
        "serve_p99_ms": 80.5,
        "p99_target_ms": 200.0,
        "p99_met": True,
    }))
    got = bench.serving_trajectory_metric(str(p))
    assert got == {
        "serve_tokens_per_s": 123.4,
        "serve_p99_ms": 80.5,
        "p99_target_ms": 200.0,
        "p99_met": True,
    }
    monkeypatch.setenv("DLROVER_TPU_SERVE_ARTIFACT", str(p))
    assert bench.serving_trajectory_metric()["serve_tokens_per_s"] == \
        pytest.approx(123.4)
    # a spec-bearing artifact projects the speculative headline too
    pspec = tmp_path / "SERVE_spec.json"
    pspec.write_text(json.dumps({
        "serve_tokens_per_s": 123.4,
        "serve_p99_ms": 80.5,
        "p99_target_ms": 200.0,
        "p99_met": True,
        "speculative": {
            "spec_k": 3, "tokens_per_s": 150.0, "accept_rate": 0.62,
            "speedup_vs_specoff": 1.21, "draft_tokens": 90,
            "accepted_tokens": 56, "p99_ms": 70.0, "p99_met": True,
        },
    }))
    got_spec = bench.serving_trajectory_metric(str(pspec))
    assert got_spec["spec_tokens_per_s"] == pytest.approx(150.0)
    assert got_spec["spec_accept_rate"] == pytest.approx(0.62)
    assert got_spec["spec_speedup_vs_specoff"] == pytest.approx(1.21)
    # a phase-latency-bearing artifact projects the ttft/tpot axes;
    # older artifacts (the minimal one above) simply omit them
    pphase = tmp_path / "SERVE_phase.json"
    pphase.write_text(json.dumps({
        "serve_tokens_per_s": 123.4,
        "serve_p99_ms": 80.5,
        "ttft_p50_ms": 12.0, "ttft_p99_ms": 30.0,
        "tpot_p50_ms": 2.5, "tpot_p99_ms": 4.0,
        "queue_wait_p99_ms": 1.5,
    }))
    got_phase = bench.serving_trajectory_metric(str(pphase))
    assert got_phase["ttft_p99_ms"] == pytest.approx(30.0)
    assert got_phase["tpot_p50_ms"] == pytest.approx(2.5)
    assert got_phase["queue_wait_p99_ms"] == pytest.approx(1.5)
    assert "ttft_p99_ms" not in got  # old artifacts stay exact-shape
    # a migration-bearing artifact projects the recovery headline too
    pmig = tmp_path / "SERVE_mig.json"
    pmig.write_text(json.dumps({
        "serve_tokens_per_s": 99.0,
        "serve_p99_ms": 70.0,
        "migration_recovery_s": 0.42,
        "migration": {
            "path": "live", "migrated": 2, "re_prefilled": 0,
            "bytes_moved": 4096, "tokens_saved_vs_reprefill": 17,
        },
    }))
    got_mig = bench.serving_trajectory_metric(str(pmig))
    assert got_mig["migration_recovery_s"] == pytest.approx(0.42)
    assert got_mig["migration_path"] == "live"
    assert got_mig["migration_tokens_saved"] == 17
    # an autoscale-bearing artifact projects the SLO-goodput headline;
    # pre-autoscaler artifacts simply lack the block and replay with
    # the exact shape pinned above
    pasc = tmp_path / "SERVE_asc.json"
    pasc.write_text(json.dumps({
        "serve_tokens_per_s": 99.0,
        "serve_p99_ms": 70.0,
        "autoscale": {
            "p99_target_ms": 120.0,
            "fleet_tokens_per_s_at_p99": 150.0,
            "autoscale_reaction_s": 0.31,
            "scale_decisions": 1,
            "goodput_win_vs_pinned1": 2.1,
            "bitwise_equal_vs_static2": True,
        },
    }))
    got_asc = bench.serving_trajectory_metric(str(pasc))
    assert got_asc["fleet_tokens_per_s_at_p99"] == pytest.approx(150.0)
    assert got_asc["autoscale_reaction_s"] == pytest.approx(0.31)
    assert got_asc["scale_decisions"] == 1
    assert got_asc["autoscale_goodput_win"] == pytest.approx(2.1)
    assert "fleet_tokens_per_s_at_p99" not in got  # old-artifact replay
    assert "scale_decisions" not in got
    # missing/corrupt/unmeasured artifacts degrade to None
    assert bench.serving_trajectory_metric(
        str(tmp_path / "nope.json")
    ) is None
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert bench.serving_trajectory_metric(str(bad)) is None
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"serve_tokens_per_s": None}))
    assert bench.serving_trajectory_metric(str(empty)) is None
    # the tuned arm lives in the TRAIN record, not the serve artifact:
    # old SERVE_*.json files replay with the exact shapes pinned above
    # and never grow a "tuned" key
    assert "tuned" not in got and "tuned" not in got_asc
    # the sparse arm has its OWN artifact family (SPARSE_SERVE_*.json):
    # old SERVE artifacts replay unchanged and never grow sparse keys
    for g in (got, got_spec, got_phase, got_mig, got_asc):
        assert not any(k.startswith("sparse_") for k in g)


def test_tuned_arm_metric_schema():
    """The ``tuned`` block of the train record: cold-start plan vs the
    hand-tuned row (CPU-modeled MFU fraction) plus the live-refinement
    reaction drill. In-process and cheap — no subprocess bench run."""
    sys.path.insert(0, _REPO)
    try:
        import bench
    finally:
        sys.path.remove(_REPO)
    got = bench.tuned_arm_metric("tiny", 2, 64, "none")
    assert "error" not in got, got
    for key in ("planned", "hand", "match", "cold_start_mfu_frac",
                "modeled_chip", "reaction_s", "reaction_knob",
                "reaction_version"):
        assert key in got, key
    for key in ("batch", "remat", "block_k", "comm_bucket_mb",
                "update_sharding", "comm_wire_dtype"):
        assert key in got["planned"], key
    assert got["hand"] == {"batch": 2, "remat": "none"}
    # acceptance bar: the zero-config plan models >= 95% of the
    # hand-tuned row's MFU
    assert got["cold_start_mfu_frac"] >= 0.95
    # off-TPU the plan is modeled against the reference chip the
    # flagship ladder was hand-tuned for
    assert got["modeled_chip"] == "v5e"
    # the synthetic overlap-drift regression produced a versioned
    # revision, and doing so took real (non-negative) wall time
    assert got["reaction_knob"] == "comm_bucket_mb"
    assert got["reaction_version"] >= 1
    assert got["reaction_s"] >= 0
    # the flagship shape reproduces the hand recipe exactly
    flagship = bench.tuned_arm_metric("llama-1.4b", 1, 8192, "save_qkv")
    assert "error" not in flagship, flagship
    assert flagship["match"] is True
    assert flagship["cold_start_mfu_frac"] == pytest.approx(1.0)
    # a brain regression degrades to an error record, never a raise
    assert "error" in bench.tuned_arm_metric("no-such-model", 1, 64, "none")
