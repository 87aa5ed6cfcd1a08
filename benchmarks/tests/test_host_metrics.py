"""The four metrics that read the program's step clock
(``dlrover_tpu/observability/profiler.py``): the manifest lists them for
every cell, each reader returns the clock's value over the MEASURED
window of a hand-made run, a program without the clock leaves them out,
and the CPU rehearsal's traced run reports them all."""

import json

import pytest

from benchmarks.run import read_layer_metric
from benchmarks.tests.test_rehearsal import TINY, _manifest, _run_patched

LAYER = {
    "host.step_period_ms": "host loop",
    "host.stall_ms": "host loop",
    "host.beat_late_max_ms": "host loop",
    "input.place_ms": "data input",
}


def test_manifest_lists_them_for_every_cell():
    entries = {m["name"]: m for m in _manifest()["per_layer"]}
    for name, layer in LAYER.items():
        assert entries[name] == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": layer,
            "moves": "train_tokens_per_s",
        }


@pytest.fixture
def clock():
    from dlrover_tpu.observability import profiler

    profiler.reset_step_clock()
    clock = profiler.step_clock()
    clock._beat_on = False  # hand-made time: no beat thread
    yield clock
    profiler.reset_step_clock()


def _hand_made_run(clock):
    """Three warm-up ticks, a check of 30 s (no stall: nothing is learnt
    yet), a window of 12 periods of 0.5 s with one stalled interval of
    2.5 s, then a pause of 4 s outside the window (where a traced run
    starts its profiler; on the chip that returns in well under two
    periods and logs no stall) and three more steps: only the window is
    the metrics'."""
    t = 50.0
    for _ in range(3):
        clock.tick(t, t + 0.004)
        t += 0.5
    t += 30.0
    window_start = t - 0.45  # the window's first step runs on batch0
    for i in range(13):
        clock.tick(t, t + 0.002)
        if i == 4:
            clock.beat(now=t + 0.3, due=t + 0.26)  # a beat 40 ms late
        t += 2.5 if i == 7 else 0.5
    seconds = t - 0.5 + 0.45 - window_start
    t += 4.0
    for _ in range(3):
        clock.tick(t, t + 0.009)
        t += 0.5
    return {"window_start": window_start, "window": {"seconds": seconds}}


def test_readers_return_the_clock_s_value_over_the_window(clock):
    run = _hand_made_run(clock)
    assert len(clock.stalls) == 2  # the window's and the pause behind it
    start = run["window_start"]
    seen = clock.window(start, start + run["window"]["seconds"])
    assert seen["ticks"] == 12 and seen["stalls"] == 1
    assert read_layer_metric("host.step_period_ms", run) == pytest.approx(500.0)
    assert read_layer_metric("host.stall_ms", run) == pytest.approx(2000.0)
    assert read_layer_metric("host.beat_late_max_ms", run) == pytest.approx(40.0)
    assert read_layer_metric("input.place_ms", run) == pytest.approx(2.0)
    # a window with no whole interval inside: nothing to read
    empty = {"window_start": 10.0, "window": {"seconds": 1.0}}
    for name in LAYER:
        assert read_layer_metric(name, empty) is None


def test_program_without_the_clock_leaves_them_out(clock, monkeypatch):
    from dlrover_tpu.observability import profiler

    run = _hand_made_run(clock)
    monkeypatch.delattr(profiler, "step_clock")
    for name in LAYER:
        assert read_layer_metric(name, run) is None


def test_traced_run_reports_the_host_loop(monkeypatch, capsys):
    rc, _cell, _manifest_, lines = _run_patched(monkeypatch, capsys, TINY, 1)
    assert rc == 0
    metrics = {
        name: m["value"]
        for name, m in json.loads(lines[-1])["metrics"].items()
    }
    assert set(LAYER) <= set(metrics)
    # the inside twins (medians of a handful of CPU steps, each over
    # its own set of them: the same size, not the same number)
    step = metrics["train_step.step_ms"]
    assert step / 3 < metrics["host.step_period_ms"] < 3 * step + 5
    assert 0 < metrics["input.place_ms"] < 3 * metrics["input.wait_ms"]
    assert metrics["host.stall_ms"] == 0
