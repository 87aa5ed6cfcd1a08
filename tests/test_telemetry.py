"""Telemetry bus + trace spans: schema lint, zero-cost-off, sinks,
tracer clock/correlation, cross-process merge, HBM aggregation."""

import dataclasses
import json
import time
import tracemalloc

import pytest

import dlrover_tpu.cluster.brain  # noqa: F401 — registers TuningPlan/JobMetrics for the schema lint
from dlrover_tpu.common.constants import GraftEnv
from dlrover_tpu.observability import telemetry, tracing


@pytest.fixture(autouse=True)
def _fresh_bus():
    telemetry.reset_hub()
    tracing.reset_tracer()
    yield
    telemetry.reset_hub()
    tracing.reset_tracer()


# ---------------------------------------------------------------------------
# schema lint (tier-1): every registered record survives the wire
# ---------------------------------------------------------------------------


def _non_default(cls):
    """Instantiate with every field moved off its default, typed from
    the default's own type so new fields are linted automatically."""
    kwargs = {}
    for j, f in enumerate(dataclasses.fields(cls)):
        d = f.default
        if isinstance(d, bool):  # before int: bool is an int subclass
            kwargs[f.name] = not d
        elif isinstance(d, int):
            kwargs[f.name] = d + 13 + j
        elif isinstance(d, float):
            kwargs[f.name] = d + 2.25 + j  # exact binary fraction
        elif isinstance(d, str):
            kwargs[f.name] = f"{f.name}_x{j}"
        else:
            pytest.fail(
                f"{cls.__name__}.{f.name}: non-scalar default {d!r} "
                "breaks the lossless-JSON contract"
            )
    return cls(**kwargs)


def test_every_record_round_trips_losslessly():
    types = telemetry.record_types()
    assert len(types) >= 10  # the bus is not accidentally empty
    for name, cls in types.items():
        rec = _non_default(cls)
        line = rec.to_json()
        back = telemetry.from_json(line)
        assert type(back) is cls, name
        assert back == rec, name
        # and the envelope is one JSON object per line (JsonlSink shape)
        assert "\n" not in line and json.loads(line)["r"] == name


def test_from_json_rejects_unknown_record():
    with pytest.raises(KeyError):
        telemetry.from_json('{"r": "NoSuchRecord", "d": {}}')


def test_old_serving_recordings_replay_with_defaults():
    """Recordings taken BEFORE the speculative-decoding fields existed
    must still replay: ``from_json`` fills absent fields from dataclass
    defaults, so healthcheck replay of an old JSONL never KeyErrors."""
    old_line = json.dumps({
        "r": "ServingRecord",
        "d": {
            "replica": "replica-0", "active_slots": 2, "queue_depth": 1,
            "admitted": 9, "completed": 7, "re_admitted": 0,
            "tokens_per_s": 123.5, "p50_ms": 10.0, "p99_ms": 40.0,
            "ts": 1700000000.0,
        },
    })
    rec = telemetry.from_json(old_line)
    assert isinstance(rec, telemetry.ServingRecord)
    assert rec.completed == 7 and rec.tokens_per_s == 123.5
    # spec fields default cleanly
    assert rec.draft_tokens == 0
    assert rec.accepted_tokens == 0
    assert rec.spec_accept_rate == 0.0
    # phase-latency / drop-counter / histogram-envelope fields (the
    # serving-observability additions) default cleanly too
    assert rec.ttft_p99_ms == 0.0 and rec.tpot_p50_ms == 0.0
    assert rec.queue_wait_p99_ms == 0.0
    assert rec.rejected == 0 and rec.timed_out == 0 and rec.poisoned == 0
    assert rec.hists == ""
    # and a new-style line round-trips the new fields losslessly
    new = telemetry.ServingRecord(
        replica="r", draft_tokens=12, accepted_tokens=8,
        spec_accept_rate=8 / 12, ttft_p50_ms=5.0, ttft_p99_ms=11.0,
        tpot_p50_ms=1.5, tpot_p99_ms=2.0, queue_wait_p99_ms=0.3,
        rejected=2, timed_out=1, poisoned=1,
        hists='{"e2e": {"v": 1}}',
    )
    assert telemetry.from_json(new.to_json()) == new


# ---------------------------------------------------------------------------
# zero-cost when off (tier-1 overhead guard)
# ---------------------------------------------------------------------------


def test_disabled_hub_is_pinned_noop(monkeypatch):
    monkeypatch.delenv(GraftEnv.TELEMETRY_DIR, raising=False)
    hub = telemetry.get_hub()
    assert hub is telemetry.get_hub()  # pinned singleton, not per-call
    assert hub.enabled is False
    # publish resolves to the module no-op function — no bound-method
    # allocation, no record ever reaches it behind the enabled guard
    assert hub.publish is telemetry._noop
    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    for _ in range(2000):
        h = telemetry.get_hub()
        if h.enabled:  # the producer-side guard from trainer/saver
            pytest.fail("hub must stay disabled without configuration")
    grown = tracemalloc.get_traced_memory()[0] - base
    tracemalloc.stop()
    assert grown < 4096, f"disabled-hub hot path retained {grown}B"


def test_null_tracer_shared_span_discards_writes(monkeypatch):
    monkeypatch.delenv(GraftEnv.TRACE_DIR, raising=False)
    tr = tracing.get_tracer()
    assert tr is tracing.get_tracer() and not tr.enabled
    sp = tr.span("a", k=1)
    assert sp is tr.span("b")  # one shared no-op span, no allocation
    sp.args["pollute"] = 1  # annotating callers must not accumulate
    assert sp.args == {}
    assert sp.end(more=2) == 0.0
    with tr.span("c"):
        pass
    assert tr.events() == []


# ---------------------------------------------------------------------------
# hub fan-out + sinks
# ---------------------------------------------------------------------------


class _FakeCollector:
    def __init__(self):
        self.gauges = {}
        self.counters = {}

    def set_gauge(self, name, value):
        self.gauges[name] = value

    def inc(self, name):
        self.counters[name] = self.counters.get(name, 0) + 1


def test_hub_fanout_stamps_ts_and_detaches_failing_sink(tmp_path):
    class BadSink:
        def emit(self, record):
            raise RuntimeError("boom")

    path = tmp_path / "telemetry.jsonl"
    hub = telemetry.configure_hub(
        sinks=[BadSink()], jsonl_path=str(path)
    )
    assert telemetry.get_hub() is hub and hub.enabled
    got = []
    hub.subscribe(got.append, types=("StepRecord",))

    rec = telemetry.StepRecord(step=3, loss=1.5)
    assert rec.ts == 0.0
    hub.publish(rec)
    assert rec.ts > 0  # stamped at publish
    assert got == [rec]
    hub.publish(telemetry.NumericEvent(kind="nan"))  # type-filtered
    assert got == [rec]
    hub.publish(telemetry.StepRecord(step=4))  # bad sink already detached
    lines = path.read_text().splitlines()
    assert len(lines) == 3
    assert telemetry.from_json(lines[0]) == rec


def test_metrics_sink_projects_gauges_and_counters():
    c = _FakeCollector()
    sink = telemetry.MetricsSink(c)
    sink.emit(telemetry.StepRecord(step=1, loss=2.0, step_time_s=0.1,
                                   tokens_per_s=10.0))
    assert c.gauges["telemetry_loss"] == 2.0
    assert c.gauges["telemetry_tokens_per_s"] == 10.0
    sink.emit(telemetry.ElasticEvent(kind="rendezvous", seconds=1.25))
    assert c.counters["elastic_events_total"] == 1
    assert c.gauges["failover_rendezvous_s"] == 1.25
    sink.emit(telemetry.OverlapDriftRecord(
        step=2, planned_exposed_us=100.0, measured_collective_us=130.0,
        drift_us=30.0, drift_frac=0.3,
    ))
    assert c.gauges["overlap_drift_us"] == 30.0
    assert c.gauges["overlap_drift_frac"] == pytest.approx(0.3)
    sink.emit(telemetry.ResourceRecord(
        hbm_mb=100.0, hbm_peak_mb=140.0, tpu_type="TPU v5 lite",
        local_chips=4,
    ))
    assert c.gauges["hbm_peak_mb"] == 140.0
    # the agent's host-only report has no device half: it must not
    # read as "HBM is empty"
    sink.emit(telemetry.ResourceRecord(cpu_percent=50.0, mem_mb=1e3))
    assert c.gauges["hbm_used_mb"] == 100.0
    assert c.gauges["hbm_peak_mb"] == 140.0


def test_master_sink_never_forwards_per_step_records():
    class FakeClient:
        def __init__(self):
            self.sent = []

        def report_telemetry(self, line):
            self.sent.append(line)

    cl = FakeClient()
    sink = telemetry.MasterSink(cl)
    sink.emit(telemetry.StepRecord(step=1))  # hot path: no RPC per step
    sink.emit(telemetry.KernelSample(step=1, op="fusion"))
    assert cl.sent == []
    sink.emit(telemetry.ElasticEvent(kind="node_down"))
    sink.emit(telemetry.OverlapDriftRecord(step=2))
    assert len(cl.sent) == 2
    assert isinstance(
        telemetry.from_json(cl.sent[0]), telemetry.ElasticEvent
    )


def test_overlap_drift_helpers():
    class Op:
        def __init__(self, name, us):
            self.name = name
            self.total_us = us

    bd = [Op("fusion.1", 500.0), Op("all-reduce.3", 80.0),
          Op("all-gather-start", 40.0)]
    assert telemetry.measured_collective_us(bd) == 120.0
    d = telemetry.overlap_drift(7, 100.0, bd)
    assert d.measured_collective_us == 120.0
    assert d.drift_us == pytest.approx(20.0)
    assert d.drift_frac == pytest.approx(0.2)
    # pure-measurement mode: nothing planned → frac pinned at 0
    d0 = telemetry.overlap_drift(7, 0.0, bd)
    assert d0.drift_frac == 0.0 and d0.drift_us == 120.0


# ---------------------------------------------------------------------------
# tracer: clock, correlation, span semantics, merge
# ---------------------------------------------------------------------------


def test_tracer_span_carries_correlation_and_streams(monkeypatch, tmp_path):
    monkeypatch.setenv(GraftEnv.RUN_ID, "r1")
    monkeypatch.setenv(GraftEnv.NODE_ID, "1")
    monkeypatch.setenv(GraftEnv.RESTART_COUNT, "2")
    t = tracing.Tracer(role="worker", trace_dir=str(tmp_path))
    with t.span("failover.restore", step=5) as sp:
        time.sleep(0.01)
        sp.args["tier"] = "memory"
    t.instant("failover.first_step", step=6)
    t.counter("hbm", used_mb=3.0)
    t.close()

    evs = t.events()
    x = next(e for e in evs if e["ph"] == "X")
    assert x["name"] == "failover.restore"
    assert x["dur"] >= 5_000  # ≥5ms of the 10ms sleep, µs units
    args = x["args"]
    # identity stamped from env + explicit kwargs + live annotation
    assert args["role"] == "worker" and args["run"] == "r1"
    assert args["node"] == 1 and args["restart"] == 2
    assert args["step"] == 5 and args["tier"] == "memory"
    # wall-anchored monotonic clock lands near real epoch time
    assert abs(x["ts"] / 1e6 - time.time()) < 60
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["s"] == "p" and inst["args"]["step"] == 6
    ctr = next(e for e in evs if e["ph"] == "C")
    assert ctr["args"]["used_mb"] == 3.0
    # the per-process JSONL stream carries the same three events
    assert len(tracing.merge_trace_dir(str(tmp_path))) == 3


def test_span_end_semantics():
    t = tracing.Tracer(role="agent")
    sp = t.begin("phase")
    time.sleep(0.005)
    s1 = sp.end(k=1)
    s2 = sp.end()  # double-end: no-op returning the recorded duration
    assert s1 == s2 and s1 > 0
    assert len([e for e in t.events() if e["ph"] == "X"]) == 1

    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("x")
    ev = next(e for e in t.events() if e["name"] == "boom")
    assert ev["args"]["error"] == "ValueError"

    # an un-ended span records nothing (exception paths opt out)
    t.begin("never.closed")
    assert not any(e["name"] == "never.closed" for e in t.events())


def test_tracer_ring_is_bounded():
    t = tracing.Tracer(role="m", capacity=8)
    for i in range(20):
        t.instant(f"e{i}")
    evs = t.events()
    assert len(evs) == 8 and evs[0]["name"] == "e12"


def test_merge_trace_dir_sorts_and_tolerates_torn_lines(tmp_path):
    (tmp_path / "trace-worker-11.jsonl").write_text(
        json.dumps({"name": "late", "ph": "i", "ts": 2e6,
                    "args": {"role": "worker"}})
        + "\n" + '{"name": "torn tail'
    )
    (tmp_path / "trace-agent-22.jsonl").write_text(
        json.dumps({"name": "failover.x", "ph": "X", "ts": 1e6,
                    "dur": 5e5, "args": {"role": "agent"}}) + "\n"
    )
    out = tmp_path / "merged.jsonl"
    evs = tracing.merge_trace_dir(str(tmp_path), out_path=str(out))
    assert [e["name"] for e in evs] == ["failover.x", "late"]
    assert len(out.read_text().splitlines()) == 2

    iv = tracing.span_intervals(evs, prefix="failover.")
    assert iv == [{
        "name": "failover.x", "start_s": 1.0, "dur_s": 0.5,
        "role": "agent", "id": 0, "parent": 0,  # a recording from before ids
        "args": {"role": "agent"},
    }]


# ---------------------------------------------------------------------------
# agent monitor: HBM aggregation over all local devices
# ---------------------------------------------------------------------------


def test_get_tpu_stats_aggregates_all_local_devices(monkeypatch):
    import jax

    from dlrover_tpu.agent.monitor import get_tpu_stats

    class Dev:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    monkeypatch.setattr(jax, "local_devices", lambda: [
        Dev({"bytes_in_use": 2_000_000, "peak_bytes_in_use": 4_000_000}),
        Dev({"bytes_in_use": 3_000_000, "peak_bytes_in_use": 3_000_000}),
        Dev(None),  # backends without memory_stats report nothing
    ])
    s = get_tpu_stats()
    assert s["hbm_used_mb"] == pytest.approx(5.0)
    assert s["hbm_peak_mb"] == pytest.approx(7.0)  # sum of watermarks
