"""Auto-scaler, diagnosis, config tuner, metrics tests."""

import json
import time
import urllib.request

import pytest

from dlrover_tpu.common import messages as msgs
from dlrover_tpu.diagnosis.manager import (
    DiagnosisAction,
    DiagnosisManager,
    classify_failure,
)
from dlrover_tpu.master.auto_scaler import JobAutoScaler
from dlrover_tpu.master.job_metrics import (
    JobMetricCollector,
    MetricsHTTPServer,
)
from dlrover_tpu.master.node_manager import JobManager, NoopScaler
from dlrover_tpu.master.resource_optimizer import LocalHeuristicOptimizer
from dlrover_tpu.master.speed_monitor import SpeedMonitor


def test_classify_failures():
    assert classify_failure("RESOURCE_EXHAUSTED: out of memory")[0] == "oom"
    assert classify_failure("ICI link failure on chip 2")[0] == (
        "hardware_error"
    )
    assert classify_failure("ModuleNotFoundError: no module")[1] == (
        DiagnosisAction.ABORT_JOB
    )
    cls, action = classify_failure("something weird")
    assert action == DiagnosisAction.RESTART_WORKER


def test_diagnosis_actions_queue():
    dm = DiagnosisManager()
    # hang report with the worker still alive → restart is prescribed
    dm.collect_failure(
        msgs.NodeFailureReport(node_id=3, error_data="barrier timeout"),
        worker_alive=True,
    )
    assert dm.take_actions(3) == [DiagnosisAction.RESTART_WORKER]
    assert dm.take_actions(3) == []
    assert dm.failure_summary() == {"hang": 1}

    # dead-worker failure → the agent restarts it itself; no duplicate
    # restart action is queued, but stronger actions still are
    dm.collect_failure(
        msgs.NodeFailureReport(node_id=4, error_data="worker exit code 1")
    )
    assert dm.take_actions(4) == []
    dm.collect_failure(
        msgs.NodeFailureReport(node_id=5, error_data="ImportError: x")
    )
    assert dm.take_actions(5) == [DiagnosisAction.ABORT_JOB]


def test_autoscaler_scale_out_and_in():
    jm = JobManager(num_workers=2)
    sm = SpeedMonitor()
    scaler = NoopScaler()
    opt = LocalHeuristicOptimizer(min_workers=2, max_workers=8, node_unit=2)
    asc = JobAutoScaler(
        jm,
        sm,
        scaler,
        optimizer=opt,
        min_workers=2,
        max_workers=8,
        node_unit=2,
    )
    # both workers running & speed healthy → scale out by node_unit
    for i in range(2):
        jm.register_node(msgs.NodeMeta(node_id=i, node_rank=i))
    # interval math runs on the injectable monotonic arrival clock
    sm.collect_global_step(0, now=90.0)
    sm.collect_global_step(50, now=100.0)
    asc.adjust_once()
    assert jm.worker_num == 4
    assert scaler.plans and scaler.plans[-1].worker_num == 4

    # within the grace window booting nodes don't trigger scale-in
    asc.adjust_once()
    assert jm.worker_num == 4

    # after the grace expires, still-unplaced nodes force scale-in
    asc.pending_grace_s = 0.0
    asc.adjust_once()
    assert jm.worker_num == 2


def test_config_tuner_writes_file(tmp_path):
    class FakeClient:
        def get_parallel_config(self):
            return msgs.ParallelConfig(batch_size=32, version=2)

    from dlrover_tpu.agent.config_tuner import ParalConfigTuner

    path = tmp_path / "cfg.json"
    tuner = ParalConfigTuner(FakeClient(), config_path=str(path))
    assert tuner.poll_once()
    doc = json.loads(path.read_text())
    assert doc["batch_size"] == 32 and doc["version"] == 2
    # same version → no rewrite
    assert not tuner.poll_once()


def test_goodput_tracker():
    from dlrover_tpu.master.job_metrics import GoodputTracker

    t = GoodputTracker(now=100.0)
    # startup counts as stalled until the first step report
    t.mark_productive(now=110.0)          # first step at t+10
    assert t.goodput(now=110.0) == pytest.approx(0.0)
    assert t.goodput(now=210.0) == pytest.approx(1 - 10 / 110)
    # node failure at t+110 (training was at step 50) → a STALE in-flight
    # report at/below the stall step must not close the stall
    t.mark_stalled(now=210.0, at_step=50)
    t.mark_stalled(now=215.0)             # idempotent while stalled
    t.mark_productive(now=212.0, step=50)  # stale step — ignored
    # racing in-flight report: step ABOVE the stall point but taken
    # before the stall opened — must not close it
    t.mark_productive(now=213.0, step=51, report_ts=209.0)
    # real post-restart progress (taken after the stall opened)
    t.mark_productive(now=240.0, step=51, report_ts=239.5)
    assert t.lost_seconds(now=240.0) == pytest.approx(40.0)
    # 300s wall, 40s lost → 86.7% goodput
    assert t.goodput(now=400.0) == pytest.approx(1 - 40 / 300)
    # productive while not stalled is a no-op
    t.mark_productive(now=500.0)
    assert t.lost_seconds(now=500.0) == pytest.approx(40.0)

    # hang backdating: detection at t+500 backdates accounting to t+420,
    # clamped to the last close (t+240 in this history is older, so the
    # full backdate stands); the in-flight guard keys on DETECTION time
    t.mark_stalled(now=500.0, at_step=80, accounted_from=420.0)
    t.mark_productive(now=505.0, step=81, report_ts=460.0)  # in-window
    assert t.lost_seconds(now=505.0) == pytest.approx(40.0 + 85.0)
    t.mark_productive(now=520.0, step=81, report_ts=510.0)
    assert t.lost_seconds(now=520.0) == pytest.approx(40.0 + 100.0)
    # a backdate reaching before the last close is clamped — the span
    # [520, 530] is charged once even though accounted_from says 400
    t.mark_stalled(now=530.0, at_step=90, accounted_from=400.0)
    t.mark_productive(now=540.0, step=91, report_ts=539.0)
    assert t.lost_seconds(now=540.0) == pytest.approx(140.0 + 20.0)


def test_goodput_completion_freezes_lost_time():
    from dlrover_tpu.master.job_metrics import GoodputTracker

    t = GoodputTracker(now=0.0)
    t.mark_productive(now=5.0)            # startup stall closes at t+5
    # a worker finishes training at t+100 while a stall is open: the
    # stall is charged up to completion, then accounting freezes
    t.mark_stalled(now=90.0, at_step=60)
    t.mark_completed(now=100.0)
    assert t.lost_seconds(now=100.0) == pytest.approx(5.0 + 10.0)
    # a peer death detected AFTER completion (heartbeat timeout racing
    # teardown) opens no stall — its at_step equals the final step, so
    # no report could ever close it
    t.mark_stalled(now=120.0, at_step=60)
    assert t.lost_seconds(now=500.0) == pytest.approx(15.0)


def test_goodput_exported():
    from dlrover_tpu.master.job_metrics import GoodputTracker

    col = JobMetricCollector()
    col.goodput_tracker = GoodputTracker(now=0.0)
    col.goodput_tracker.mark_productive(now=0.0)
    assert "dlrover_tpu_goodput" in col.prometheus_text()
    out = json.loads(col.to_json())
    assert out["goodput"] is not None
    # raw terms for windowed (two-sample) goodput — the drill's
    # regression gate computes across-failure goodput from deltas
    assert out["goodput_lost_seconds"] >= 0.0
    assert out["goodput_wall_seconds"] >= 0.0


def test_metrics_export_http():
    col = JobMetricCollector()
    col.set_job_meta(job_name="j", model_name="tiny", num_params=123)
    col.collect_runtime(10, 2.5, 4, hbm_used_mb_avg=1000.0)
    col.inc("node_failures_total")
    server = MetricsHTTPServer(col, port=0)
    server.start()
    try:
        text = urllib.request.urlopen(
            f"http://localhost:{server.port}/metrics", timeout=5
        ).read().decode()
        assert "dlrover_tpu_global_step 10" in text
        assert "dlrover_tpu_node_failures_total 1" in text
        doc = json.loads(
            urllib.request.urlopen(
                f"http://localhost:{server.port}/json", timeout=5
            ).read()
        )
        assert doc["meta"]["model_name"] == "tiny"
        assert doc["records"][-1]["speed_steps_per_s"] == 2.5
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# runtime kernel timing (xpu_timer analog: periodic trace sampling)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_runtime_timer_samples_real_op_breakdown(tmp_path):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.observability.runtime_timer import RuntimeKernelTimer

    x = jnp.ones((256, 256))
    f = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
    f(x)  # compile outside the trace
    timer = RuntimeKernelTimer(interval_steps=3, top_k=8)
    # step 1, 2: plain calls; step 3: sampled
    for step in (1, 2):
        timer.profiled_call(step, f, x)
        assert timer.sampled_at == -1
    timer.profiled_call(3, f, x)
    assert timer.sampled_at == 3
    bd = timer.breakdown
    assert bd, "no ops parsed from the trace"
    names = " ".join(o.name for o in bd)
    assert "dot" in names  # the matmuls dominate
    # fractions normalize, python-frame noise filtered out
    assert abs(sum(o.fraction for o in bd) - 1.0) < 1e-6 or len(bd) == 8
    assert not any("$" in o.name or "/" in o.name for o in bd)
    text = timer.prometheus_text()
    assert "dlrover_tpu_kernel_time_us" in text and 'op="' in text


@pytest.mark.slow  # tier-1 budget: full Trainer loop (~23s); the timer
# itself is pinned fast by the forced-one-shot unit below
def test_runtime_timer_in_trainer(tmp_path):
    """profile_interval wires the timer around the live train step."""
    import numpy as np

    from dlrover_tpu.models import get_config
    from dlrover_tpu.parallel import MeshConfig, build_mesh
    from dlrover_tpu.train import Trainer, TrainerArgs, make_optimizer

    def data():
        import jax.numpy as jnp

        rng = np.random.RandomState(0)
        while True:
            base = rng.randint(0, 8, size=(8, 33))
            yield {
                "tokens": jnp.asarray(base[:, :-1], jnp.int32),
                "targets": jnp.asarray(base[:, 1:], jnp.int32),
            }

    cfg = get_config("tiny", n_layer=2, d_model=64, d_ff=128, n_head=4,
                     vocab_size=128, max_seq=32)
    args = TrainerArgs(
        output_dir=str(tmp_path), max_steps=4, log_interval=0,
        save_interval=0, report_to_master=False,
        detect_loss_spikes=False, profile_interval=2,
    )
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=2,
                         decay_steps=50)
    tr = Trainer(cfg, args, data(), opt,
                 mesh=build_mesh(MeshConfig(dp=-1)))
    tr.train()
    assert tr.runtime_timer.sampled_at in (2, 4)
    assert tr.runtime_timer.breakdown


# ---------------------------------------------------------------------------
# runtime-timer plumbing the watchdog's triggered captures rely on
# ---------------------------------------------------------------------------


def _traced_matmuls(logdir):
    """A real (CPU-backend) trace of two jitted matmuls, as the planes
    the program's reducer works on."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.observability import runtime_timer as rt

    f = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
    x = jnp.ones((128, 128))
    f(x)
    with jax.profiler.trace(str(logdir)):
        with jax.profiler.TraceAnnotation(rt.SAMPLE_SPAN):
            jax.block_until_ready(f(x))
    return rt.load_planes(rt.find_xplane(str(logdir)))


def test_reducer_on_a_real_trace_counts_executed_ops_only(tmp_path):
    """The successor of the perfetto parser's canned fixture: off the
    chip, device time is the events the runtime marks as executed HLO
    operations — never python frames, runtime threads or annotations."""
    from dlrover_tpu.observability import runtime_timer as rt

    planes = _traced_matmuls(tmp_path)
    profile = rt.reduce_planes(planes)
    names = [o.name for o in profile.by_op]
    assert any(n.startswith("dot") for n in names), names
    assert not any("$" in n or "/" in n or " " in n for n in names)
    assert rt.SAMPLE_SPAN not in names
    assert sum(o.fraction for o in profile.by_op) == pytest.approx(1.0)
    assert names == [
        o.name for o in sorted(profile.by_op, key=lambda o: -o.total_us)
    ]
    # busy cannot pass the sampled window, however many host threads ran
    assert 0 < profile.busy_s <= profile.window_s
    host_total = sum(
        d for p in planes if not rt.DEVICE_PLANE.match(p["name"])
        for line in p["lines"] for _n, _s, d in line["events"]
    ) / 1e9
    assert profile.busy_s < host_total


def test_timer_breakdown_is_the_top_k_of_the_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.observability.runtime_timer import RuntimeKernelTimer

    f = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
    x = jnp.ones((128, 128))
    f(x)
    timer = RuntimeKernelTimer(interval_steps=1, top_k=1)
    timer.profiled_call(1, f, x)
    assert len(timer.profile.by_op) > 1
    assert timer.breakdown == timer.profile.by_op[:1]
    assert timer.summary() == {
        timer.breakdown[0].name: timer.breakdown[0].total_us
    }
    assert timer.sample_wall_s > 0


def test_runtime_timer_forced_one_shot(tmp_path):
    """interval_steps=0 is forced-only mode: the cadence never fires,
    force_next() arms exactly one sample, and profiled_call records the
    block size it actually traced."""
    from dlrover_tpu.observability.runtime_timer import RuntimeKernelTimer

    with pytest.raises(ValueError):
        RuntimeKernelTimer(interval_steps=-1)

    timer = RuntimeKernelTimer(interval_steps=0, logdir=str(tmp_path))
    assert not any(timer.should_sample(s) for s in range(1, 50))
    timer.force_next()
    assert timer.should_sample(7)

    import jax
    import jax.numpy as jnp

    add = jax.jit(lambda a, b: a + b)
    out = timer.profiled_call(
        7, add, jnp.full((8,), 2.0), jnp.full((8,), 3.0), n_steps=4
    )
    assert float(out[0]) == 5.0
    assert timer.sampled_at == 7
    # a 4-step fused block is labeled as such, never as one step
    assert timer.sampled_block_k == 4
    # one-shot: the forced flag is consumed by the sample
    assert not any(timer.should_sample(s) for s in range(8, 50))


def test_loss_spike_publishes_numeric_event_with_culprits():
    """The spike detector is a telemetry producer: a detected spike
    lands on the hub as a NumericEvent whose detail names the worst
    offending sample ids (satellite: sample-id attribution)."""
    from dlrover_tpu.observability import telemetry
    from dlrover_tpu.observability.loss_spike import LossSpikeDetector

    telemetry.reset_hub()
    try:
        hub = telemetry.configure_hub()
        got = []
        hub.subscribe(got.append, types=("NumericEvent",))
        det = LossSpikeDetector(
            save_dir="", min_iter=0, min_loss=0.0, publish_events=True
        )
        for i in range(30):  # jittered baseline: sd > 0
            det.update(i, 1.0 + 0.01 * (i % 5))
        assert det.update(
            30,
            10.0,
            sample_ids=[3, 7, 9],
            per_sample_losses=[0.5, 9.0, 2.0],
        )
        (ev,) = got
        assert ev.kind == "loss_spike" and ev.step == 30
        assert ev.value == pytest.approx(10.0)
        assert ev.detail.startswith("7:9.0000")  # worst sample first
        assert "9:2.0000" in ev.detail and "3:0.5000" in ev.detail
    finally:
        telemetry.reset_hub()
