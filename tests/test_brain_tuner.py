"""Brain auto-tuner: the telemetry→config loop (cluster/brain.py
ColdStartPlanner + BrainTuner, the master's plan_tuning directive path,
the ParalConfigTuner poll doc, and step-boundary application).

Tier split: the planner math, the revision ladders (synthetic records,
injected clock), the master plumbing, and the MetricsStore durability
pins are pure and fast; the end-to-end drills (a real TrainStepBuilder
rebuild, a ServingEngine retune parity run) compile jitted steps and
live on the slow tier (see test_marker_lint _SLOW_LEDGER +
test_brain_tuner_e2e_drills_are_slow).
"""

import json
import threading

import pytest

from dlrover_tpu.cluster import brain
from dlrover_tpu.common import messages as msgs
from dlrover_tpu.models.config import get_config
from dlrover_tpu.observability import telemetry


@pytest.fixture(autouse=True)
def _fresh_bus():
    telemetry.reset_hub()
    yield
    telemetry.reset_hub()


def _drift(frac=1.0):
    return telemetry.OverlapDriftRecord(
        planned_exposed_us=100.0,
        measured_collective_us=100.0 * (1 + frac),
        drift_us=100.0 * frac,
        drift_frac=frac,
    )


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# cold-start planner
# ---------------------------------------------------------------------------


def test_cold_start_plan_for_a_long_context_shape():
    """From ONLY the model shape + a 16 GiB chip, the planner lands on
    the recipe the long-context cells run (llama-1.4b, b1 x s8192:
    ``full``; ``none`` does not fit)."""
    cfg = get_config("llama-1.4b", max_seq=8192)
    plan = brain.ColdStartPlanner().plan(
        cfg, n_devices=1, seq=8192, hbm_bytes=16e9
    )
    assert plan.origin == "cold_start"
    assert plan.batch_size == 1
    assert plan.remat == "full"
    assert plan.comm_bucket_mb > 0
    # single chip, no dp: no ZeRO, bitwise-safe f32 wire, no DCN
    assert plan.update_sharding == ""
    assert plan.comm_wire_dtype == "float32"
    assert plan.comm_wire_dtype_dcn == ""


def test_cold_start_plan_small_model_dp_mesh():
    """Small shape on a dp mesh: batch fills the token target, remat
    stays off, dispatch-bound small steps get the fused block, dp>1
    without accumulation picks zero1, and a multi-slice mesh narrows
    the DCN wire only."""
    cfg = get_config("tiny")
    plan = brain.ColdStartPlanner().plan(
        cfg,
        mesh={"dp": 4, "num_slices": 2},
        seq=128,
        hbm_bytes=16e9,
    )
    assert plan.remat == "none"
    assert plan.batch_size >= 8
    assert plan.block_k > 1
    assert plan.update_sharding == "zero1"
    assert plan.comm_wire_dtype == "float32"
    assert plan.comm_wire_dtype_dcn == "int8"


def test_cold_start_plan_nothing_fits_degrades_to_floor():
    """A shape no remat can fit on the budget still yields a plan —
    batch 1 at full remat (the caller sees the warning, never a
    crash)."""
    cfg = get_config("llama-1.4b", max_seq=8192)
    plan = brain.ColdStartPlanner().plan(
        cfg, n_devices=1, seq=8192, hbm_bytes=6e9
    )
    assert plan.batch_size == 1
    assert plan.remat == "full"


def test_estimate_hbm_puts_the_long_shape_between_the_policies():
    """The memory model's load-bearing property: at llama-1.4b b1 x
    s8192 ``full`` fits a 16 GiB chip and ``none`` does not — the
    boundary the planner's choice sits on."""
    cfg = get_config("llama-1.4b", max_seq=8192)
    budget = 16e9 * 0.92
    assert brain.estimate_hbm_bytes(cfg, 1, 8192, "full") <= budget
    assert brain.estimate_hbm_bytes(cfg, 1, 8192, "none") > budget


def test_bucket_suggestion_scales_with_zero2_accumulation():
    """ZeRO-2 pays the gradient exchange once per microbatch, so the
    launch cost recurs ``grad_accum`` times a step and the smallest
    bucket worth its launch grows with it; ZeRO-1 defers to one exchange
    a step and takes the answer of no accumulation. At least four
    buckets stay in flight, inside [1, 64] MB."""
    grad_bytes = 4e9
    mb1 = brain._suggest_bucket_mb(grad_bytes, launch_us=10.0)
    mb2 = brain._suggest_bucket_mb(
        grad_bytes, launch_us=10.0, grad_accum=4, update_mode="zero2"
    )
    assert 1.0 < mb1 and mb2 < 64.0
    assert mb2 == pytest.approx(4 * mb1, rel=1e-3)
    assert brain._suggest_bucket_mb(
        grad_bytes, launch_us=10.0, grad_accum=4, update_mode="zero1"
    ) == mb1
    # a slow launch is clamped at 64 MB, a small gradient at a quarter of
    # itself, a tiny one at 1 MB
    assert brain._suggest_bucket_mb(
        grad_bytes, launch_us=100.0, grad_accum=4, update_mode="zero2"
    ) == 64.0
    assert brain._suggest_bucket_mb(
        16 * 2**20, launch_us=10.0, grad_accum=4, update_mode="zero2"
    ) == 4.0
    assert brain._suggest_bucket_mb(2**20) == 1.0


def test_tuning_plan_round_trips_and_replays_old_lines():
    plan = brain.TuningPlan(
        version=3, origin="revision", knob="spec_k", signal="accept",
        spec_k=4,
    )
    assert telemetry.from_json(plan.to_json()) == plan
    # a pre-tuner recording has no TuningPlan lines; a FUTURE recording
    # missing fields fills from defaults (sentinel = leave alone)
    old = json.dumps({"r": "TuningPlan", "d": {"version": 1}})
    back = telemetry.from_json(old)
    assert back.spec_k == -1 and back.page_bucketing == -1
    assert back.remat == "" and back.batch_size == 0


# ---------------------------------------------------------------------------
# revision ladders (synthetic records, injected clock — pure + fast)
# ---------------------------------------------------------------------------


def test_drift_ladder_doubles_bucket_after_patience():
    tuner = brain.BrainTuner(
        brain.TuningPlan(comm_bucket_mb=4.0), cooldown_s=0.0
    )
    for _ in range(2):
        tuner.on_record(_drift())
    assert not tuner.revisions  # patience not yet met
    tuner.on_record(_drift())
    rev = tuner.revisions[-1]
    assert rev.knob == "comm_bucket_mb" and rev.signal == "overlap_drift"
    assert tuner.plan.comm_bucket_mb == 8.0
    # a healthy sample resets the streak
    tuner.on_record(_drift(frac=0.0))
    tuner.on_record(_drift())
    tuner.on_record(_drift())
    assert len(tuner.revisions) == 1


def test_fp8_saturation_widens_dcn_wire_first():
    tuner = brain.BrainTuner(
        brain.TuningPlan(
            comm_wire_dtype="float32", comm_wire_dtype_dcn="int8"
        ),
        cooldown_s=0.0,
    )
    tuner.on_record(telemetry.AnomalyRecord(kind="fp8_saturation"))
    assert tuner.plan.comm_wire_dtype_dcn == "bfloat16"
    assert tuner.plan.comm_wire_dtype == "float32"  # ICI untouched
    tuner.on_record(telemetry.AnomalyRecord(kind="fp8_saturation"))
    assert tuner.plan.comm_wire_dtype_dcn == "float32"
    # ladder top: no further revision
    n = len(tuner.revisions)
    tuner.on_record(telemetry.AnomalyRecord(kind="fp8_saturation"))
    assert len(tuner.revisions) == n


def test_oom_ladder_descends_remat_then_halves_batch():
    tuner = brain.BrainTuner(
        brain.TuningPlan(remat="none", batch_size=4), cooldown_s=0.0
    )
    assert tuner.on_failure("oom").remat == "full"
    assert tuner.on_failure("oom").batch_size == 2
    assert tuner.on_failure("oom").batch_size == 1
    assert tuner.on_failure("oom") is None  # ladder exhausted, no crash
    assert tuner.on_failure("timeout") is None  # only oom ladders


def test_serving_ladders_spec_k_chunk_slots_bucketing():
    tuner = brain.BrainTuner(
        brain.TuningPlan(
            spec_k=2, prefill_chunk=128, n_slots=4, page_bucketing=0
        ),
        cooldown_s=0.0,
        ttft_target_ms=50.0,
        occupancy_patience=2,
    )
    # high accept EWMA → spec_k up (one step per revision window; the
    # zero cooldown here means one step per record)
    tuner.on_record(
        telemetry.ServingRecord(
            replica="r", draft_tokens=10, spec_accept_rate=0.95,
            active_slots=3, queue_depth=1,  # occupancy-neutral sample
        )
    )
    assert tuner.plan.spec_k == 3
    # TTFT breach → chunk halves (never below the floor)
    tuner.on_record(
        telemetry.ServingRecord(
            replica="r", ttft_p99_ms=120.0, active_slots=3, queue_depth=1
        )
    )
    assert tuner.plan.prefill_chunk == 64
    # saturated slots with queued work → grow
    for _ in range(2):
        tuner.on_record(
            telemetry.ServingRecord(
                replica="r", active_slots=4, queue_depth=3
            )
        )
    assert tuner.plan.n_slots == 5
    # table-ship burst across stats snapshots → enable bucketing
    tuner.observe_serving_stats({"table_ships": 0})
    tuner.observe_serving_stats({"table_ships": 20})
    assert tuner.plan.page_bucketing == 1
    knobs = [r.knob for r in tuner.revisions]
    assert knobs == ["spec_k", "prefill_chunk", "n_slots", "page_bucketing"]


def test_cooldown_suppresses_per_knob_thrash():
    clk = FakeClock()
    tuner = brain.BrainTuner(
        brain.TuningPlan(comm_bucket_mb=4.0), cooldown_s=30.0, clock=clk
    )
    for _ in range(3):
        tuner.on_record(_drift())
    assert tuner.plan.comm_bucket_mb == 8.0
    for _ in range(3):
        tuner.on_record(_drift())  # inside the cooldown: suppressed
    assert tuner.plan.comm_bucket_mb == 8.0
    clk.t = 31.0
    for _ in range(3):
        tuner.on_record(_drift())
    assert tuner.plan.comm_bucket_mb == 16.0


def test_revisions_version_through_report_and_publish_to_hub(tmp_path):
    hub = telemetry.configure_hub()
    seen = []
    hub.subscribe(seen.append, types=("TuningPlan",))
    tuner = brain.BrainTuner(
        brain.TuningPlan(version=7, comm_bucket_mb=4.0),
        report=lambda rev: 41,  # the master's directive counter
        cooldown_s=0.0,
    )
    for _ in range(3):
        tuner.on_record(_drift())
    assert tuner.plan.version == 41
    assert seen and seen[-1].version == 41
    # a failing report falls back to local monotonic versioning
    def boom(rev):
        raise OSError("master unreachable")

    tuner._report = boom
    for _ in range(3):
        tuner.on_record(_drift())
    assert tuner.plan.version == 42


def test_apply_revision_maps_fields_onto_acceleration_plan():
    from dlrover_tpu.accelerate.strategy import AccelerationPlan

    ap = AccelerationPlan(remat="none", comm_bucket_mb=4.0)
    out = brain.apply_revision(
        ap,
        brain.TuningPlan(
            remat="full", comm_bucket_mb=8.0, comm_wire_dtype_dcn="bfloat16",
            update_sharding="zero2", grad_accum_steps=2,
        ),
    )
    assert out.remat == "full" and out.comm_bucket_mb == 8.0
    assert out.comm_wire_dtype_dcn == "bfloat16"
    assert out.update_sharding == "zero2" and out.grad_accum == 2
    assert ap.remat == "none"  # pure: input untouched
    # sentinels leave knobs alone; "off" disables
    out2 = brain.apply_revision(out, brain.TuningPlan(update_sharding="off"))
    assert out2.remat == "full" and out2.update_sharding is False


# ---------------------------------------------------------------------------
# master plumbing: versioned directive → ParallelConfig poll
# ---------------------------------------------------------------------------


def test_job_manager_plan_tuning_is_monotonic():
    from dlrover_tpu.master.node_manager import JobManager

    jm = JobManager(num_workers=1)
    assert jm.get_tuning() == {"version": 0}
    v1 = jm.plan_tuning('{"remat": "full"}', reason="oom")
    v2 = jm.plan_tuning('{"spec_k": 3}', reason="accept")
    assert (v1, v2) == (1, 2)
    got = jm.get_tuning()
    assert got["version"] == 2 and got["plan_json"] == '{"spec_k": 3}'


def test_servicer_folds_tuning_directive_into_parallel_config():
    from dlrover_tpu.master.node_manager import JobManager
    from dlrover_tpu.master.servicer import MasterServicer

    jm = JobManager(num_workers=1)
    jm.register_node(msgs.NodeMeta(node_id=0, node_rank=0))
    servicer = MasterServicer(job_manager=jm)
    # before any plan: plain config, version pair (0, 0)
    cfg = servicer.get(msgs.ParallelConfigRequest(node_id=0))
    assert cfg.tuning_version == 0 and cfg.tuning_json == ""
    plan_json = json.dumps({"version": 0, "remat": "full"})
    assert servicer.report(
        msgs.TuningPlanNotice(node_id=0, plan_json=plan_json, signal="oom")
    )
    cfg = servicer.get(msgs.ParallelConfigRequest(node_id=0))
    assert cfg.tuning_version == 1
    assert json.loads(cfg.tuning_json)["remat"] == "full"
    # the dedicated getter carries the same directive
    d = servicer.get(msgs.TuningPlanRequest(node_id=0))
    assert d.version == 1 and d.plan_json == plan_json


def test_config_tuner_doc_carries_tuning_and_gates_on_version_pair(
    tmp_path,
):
    from dlrover_tpu.agent.config_tuner import ParalConfigTuner

    class FakeClient:
        tuning_json = ""
        tuning_version = 0

        def get_parallel_config(self):
            return msgs.ParallelConfig(
                batch_size=32, version=2,
                tuning_json=self.tuning_json,
                tuning_version=self.tuning_version,
            )

    client = FakeClient()
    path = tmp_path / "cfg.json"
    tuner = ParalConfigTuner(client, config_path=str(path))
    assert tuner.poll_once()
    assert "tuning" not in json.loads(path.read_text())
    # same dataloader version, NEW tuning version → rewrite (the pair
    # gates, not either version alone)
    client.tuning_json = json.dumps({"version": 5, "spec_k": 3})
    client.tuning_version = 5
    assert tuner.poll_once()
    doc = json.loads(path.read_text())
    assert doc["version"] == 2 and doc["tuning_version"] == 5
    assert doc["tuning"]["spec_k"] == 3
    assert not tuner.poll_once()  # both versions unchanged → no rewrite
    # malformed directive: dropped with a warning, doc still written
    client.tuning_json = "{not json"
    client.tuning_version = 6
    assert tuner.poll_once()
    assert "tuning" not in json.loads(path.read_text())


def test_config_tuner_rate_limits_tracebacks_and_backs_off(monkeypatch):
    from dlrover_tpu.agent import config_tuner as ct

    class FlakyClient:
        def __init__(self):
            self.fail_with = OSError("master down")

        def get_parallel_config(self):
            raise self.fail_with

    warned = []
    monkeypatch.setattr(
        ct.logger, "warning", lambda msg, *a, **kw: warned.append(msg % a)
    )
    client = FlakyClient()
    tuner = ct.ParalConfigTuner(client, config_path="/tmp/unused_cfg.json")
    for _ in range(4):
        assert not tuner.poll_once()
    # a DISTINCT failure reason warns again
    client.fail_with = ValueError("bad frame")
    assert not tuner.poll_once()
    assert len(warned) == 2  # once per distinct reason, not per poll
    assert "OSError" in warned[0] and "ValueError" in warned[1]
    assert tuner._fail_streak == 5
    # the loop delay grows with the streak (jittered exponential on top
    # of the base cadence) and a success resets it
    from dlrover_tpu.common.comm import _backoff_delay

    assert _backoff_delay(tuner._fail_streak - 1) > 0
    client.fail_with = None

    class OkClient:
        def get_parallel_config(self):
            return msgs.ParallelConfig(batch_size=8, version=1)

    tuner._client = OkClient()
    assert tuner.poll_once()
    assert tuner._fail_streak == 0


# ---------------------------------------------------------------------------
# MetricsStore durability (the jsonl store behind the brain's history)
# ---------------------------------------------------------------------------


def test_metrics_store_tolerates_torn_and_foreign_lines(tmp_path):
    """A crash mid-append leaves a torn last line; a foreign writer
    leaves junk. Reload must keep every intact row and skip the rest —
    same tolerance contract as healthcheck's flight-recorder replay."""
    path = tmp_path / "metrics.jsonl"
    store = brain.MetricsStore(str(path))
    for i in range(3):
        store.append(
            brain.JobMetrics(
                job_name="j", job_kind="llm", worker_num=i + 1,
                samples_per_sec=10.0 * (i + 1), finished=True,
            )
        )
    with open(path, "a") as f:
        f.write("not json at all\n")
        f.write('{"job_name": "j", "unknown_field": 1}\n')  # foreign schema
        f.write('{"job_name": "j", "worker_num": 9')  # torn mid-write
    reloaded = brain.MetricsStore(str(path))
    rows = reloaded.job_rows("j")
    assert [r.worker_num for r in rows] == [1, 2, 3]
    assert all(r.timestamp > 0 for r in rows)  # stamped at append time


def test_jsonl_store_first_allocation_matches_in_process(tmp_path):
    """Cold-start worker allocation from history must not depend on
    WHERE the history lives: the same rows through an in-process store
    and through a jsonl round-trip (write, reload from disk) produce
    the identical plan."""
    rows = [
        brain.JobMetrics(
            job_name=f"old-{i}", job_kind="llm", worker_num=n,
            samples_per_sec=s, finished=True, timestamp=1000.0 + i,
        )
        for i, (n, s) in enumerate([(2, 40.0), (4, 100.0), (8, 120.0)])
    ]
    mem = brain.BrainService(store=brain.MetricsStore())
    for r in rows:
        mem.persist_metrics(r)
    path = tmp_path / "metrics.jsonl"
    disk = brain.MetricsStore(str(path))
    for r in rows:
        disk.append(
            brain.JobMetrics(**{
                f: getattr(r, f)
                for f in ("job_name", "job_kind", "worker_num",
                          "samples_per_sec", "finished", "timestamp")
            })
        )
    jsonl = brain.BrainService(store=brain.MetricsStore(str(path)))
    mem.bind_job("new", "llm")
    jsonl.bind_job("new", "llm")
    a = mem._first_allocation()
    b = jsonl._first_allocation()
    assert a.worker_num == b.worker_num == 4  # best samples/sec/worker


# ---------------------------------------------------------------------------
# healthcheck replay of the decision trail
# ---------------------------------------------------------------------------


def test_healthcheck_replays_tuning_decision_trail(tmp_path):
    from dlrover_tpu.observability import healthcheck as hc

    path = tmp_path / "flight.jsonl"
    with open(path, "w") as f:
        f.write(
            brain.TuningPlan(
                version=1, origin="cold_start", reason="llama-1.4b b1"
            ).to_json() + "\n"
        )
        f.write(
            brain.TuningPlan(
                version=2, origin="revision", knob="comm_bucket_mb",
                signal="overlap_drift", comm_bucket_mb=16.0,
            ).to_json() + "\n"
        )
        f.write('{"torn')
    diag = hc.diagnose(hc.load_records(str(path)))
    t = diag["tuning"]
    assert t["n_revisions"] == 1
    assert t["knobs_moved"] == {"comm_bucket_mb": 1}
    assert [d["version"] for d in t["decisions"]] == [1, 2]
    report = hc.format_report(diag)
    assert "brain tuning: 1 revision(s)" in report
    assert "v2 comm_bucket_mb: overlap_drift" in report
    # pre-tuner recordings replay with NO tuning section, not an error
    empty = tmp_path / "old.jsonl"
    empty.write_text(
        telemetry.StepRecord(step=1, loss=2.0).to_json() + "\n"
    )
    assert hc.diagnose(hc.load_records(str(empty)))["tuning"] == {}


# ---------------------------------------------------------------------------
# step-boundary application (fast: fake build_step, no jit)
# ---------------------------------------------------------------------------


def test_elastic_trainer_apply_tuning_rebuilds_at_boundary():
    from dlrover_tpu.elastic.trainer import ElasticTrainer

    built = []

    def build_step(ga):
        built.append(ga)
        return lambda state, batch: (state, {"ga": ga})

    hub = telemetry.configure_hub()
    events = []
    hub.subscribe(events.append, types=("ElasticEvent",))
    tr = ElasticTrainer(
        global_batch_size=32, micro_batch_size=4,
        build_step=build_step, data_replicas_fn=lambda: 2,
    )
    assert tr.grad_accum == 4 and built == [4]
    # an unversioned no-op plan does nothing
    assert not tr.apply_tuning(brain.TuningPlan())
    # a versioned batch revision re-derives accumulation + rebuilds
    assert tr.apply_tuning(brain.TuningPlan(version=3, batch_size=8))
    assert tr.micro_batch_size == 8 and tr.grad_accum == 2
    assert built == [4, 2]
    kinds = [e.kind for e in events]
    assert "tuning_replan" in kinds and "mesh_replan" not in kinds[1:]
    # a version bump alone (builder-side knob changed) still rebuilds
    assert tr.apply_tuning({"version": 4})
    assert built == [4, 2, 2]
    _, metrics = tr.step(None, None)
    assert metrics["ga"] == 2


# ---------------------------------------------------------------------------
# end-to-end drills (slow tier: real jit compiles)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_tuning_replan_drill_loss_continuity(tmp_path):
    """Injected mid-run regression → versioned revision through the
    master → step-boundary rebuild, NO restart: the drilled run's loss
    trajectory is bitwise the undisturbed run's (same state object
    carries across the rebuild), the revision event lands on the hub,
    and the changed knob is the one the signal maps to."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from dlrover_tpu.elastic.trainer import ElasticTrainer
    from dlrover_tpu.master.node_manager import JobManager
    from dlrover_tpu.parallel.mesh import single_device_mesh
    from dlrover_tpu.train import (
        TrainStepBuilder,
        init_train_state,
        make_optimizer,
    )

    cfg = get_config("tiny", max_seq=64)
    mesh = single_device_mesh()
    opt = make_optimizer(
        learning_rate=1e-3, warmup_steps=2, decay_steps=100
    )
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, 100)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}

    def build_step(ga):
        return TrainStepBuilder(cfg, mesh, opt, grad_accum=ga).build()

    def run(n_steps, mid=None):
        state = init_train_state(jax.random.key(0), cfg, mesh, opt)
        tr = ElasticTrainer(
            global_batch_size=2, micro_batch_size=2,
            build_step=build_step, data_replicas_fn=lambda: 1,
        )
        losses = []
        for i in range(n_steps):
            if mid is not None and i == n_steps // 2:
                mid(tr)
            state, metrics = tr.step(state, batch)
            losses.append(float(jnp.ravel(metrics["loss"])[-1]))
        return losses

    hub = telemetry.configure_hub()
    events = []
    hub.subscribe(events.append, types=("ElasticEvent", "TuningPlan"))
    jm = JobManager(num_workers=1)
    tuner = brain.BrainTuner(
        brain.TuningPlan(version=1, comm_bucket_mb=4.0),
        report=lambda rev: jm.plan_tuning(
            json.dumps({"knob": rev.knob}), reason=rev.signal
        ),
        cooldown_s=0.0,
    )
    tuner.attach(hub)

    def inject(tr):
        # the regression: sustained overlap drift over the threshold
        for _ in range(3):
            hub.publish(_drift())
        assert tuner.revisions, "drift did not produce a revision"
        assert tr.apply_tuning(tuner.plan)

    baseline = run(6)
    drilled = run(6, mid=inject)
    # loss continuity: bitwise the undisturbed trajectory — the rebuild
    # changed the executable, never the state or the math
    assert drilled == baseline
    rev = tuner.revisions[-1]
    assert rev.knob == "comm_bucket_mb"
    # the master minted the version (its counter starts at 1)
    assert rev.version == jm.get_tuning()["version"] == 1
    kinds = [type(e).__name__ + ":" + getattr(e, "kind", "") for e in events]
    assert "ElasticEvent:tuning_replan" in kinds
    assert any(isinstance(e, brain.TuningPlan) for e in events)


@pytest.mark.slow
def test_serving_retune_bitwise_parity():
    """Retuning spec_k + prefill_chunk on a LIVE engine keeps the
    output stream bitwise equal to the offline reference at the same
    seeds (spec-on == spec-off == offline; chunk-width independence),
    and an idle n_slots retune rebuilds geometry without perturbing a
    subsequent wave."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models import decoder, generate
    from dlrover_tpu.serving.engine import ServingEngine
    from dlrover_tpu.serving.scheduler import Scheduler

    cfg = get_config(
        "tiny", n_layer=2, d_model=32, d_ff=64, n_head=4,
        vocab_size=32, max_seq=64,
    )
    params = decoder.init(jax.random.key(0), cfg)
    prompts = [[1, 2, 3, 1, 2, 3, 1], [5, 6, 5, 6, 5, 6, 5, 6, 5]]
    max_new = [8, 6]
    refs = [
        [
            int(t)
            for t in np.asarray(
                generate.greedy(
                    params, cfg, jnp.asarray([p], jnp.int32), m
                )[0]
            )
        ]
        for p, m in zip(prompts, max_new)
    ]

    sched = Scheduler(replica="retune")
    eng = ServingEngine(
        params, cfg, sched, n_slots=2, max_len=32, page_size=4,
        mode="bf16", prefill_chunk=8, paged=True, spec_k=0,
    )
    reqs = [sched.submit(p, m) for p, m in zip(prompts, max_new)]
    for _ in range(2):
        eng.step()
    # mid-stream retune: spec on, chunk halved (halving keeps every
    # in-flight resume point aligned by construction)
    out = eng.retune(spec_k=2, prefill_chunk=4)
    assert out["applied"] == {"spec_k": 2, "prefill_chunk": 4}
    eng.drain(timeout=600)
    assert [r.future.result(timeout=5) for r in reqs] == refs
    assert eng.stats()["spec_k"] == 2

    # growing n_slots while busy defers; once idle it applies and the
    # next wave still matches the offline reference bitwise
    out = eng.retune(n_slots=3)
    assert out["applied"].get("n_slots") == 3  # drained → idle → applies
    reqs = [sched.submit(p, m) for p, m in zip(prompts, max_new)]
    eng.drain(timeout=600)
    assert [r.future.result(timeout=5) for r in reqs] == refs

    # invalid widths are rejected loudly, not deferred
    with pytest.raises(ValueError):
        eng.retune(prefill_chunk=5)  # 32 % 5 != 0
    with pytest.raises(ValueError):
        eng.retune(spec_k=-2)
