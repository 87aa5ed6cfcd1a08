"""The eight metrics under ``setup_s`` that read the program's compile
recorder (PR 39), on the CPU rehearsal's tiny cell: a traced run reports
them all, they are a subset of what ``compile_cache.compile_s`` adds up,
and a program without the counters leaves them out."""

import json

import pytest

from benchmarks.tests.test_rehearsal import TINY, _events, _run_patched

STEP = ["compile.step_trace_s", "compile.step_lower_s",
        "compile.step_backend_s"]
NEW = STEP + [
    "compile.step_fetch_s", "compile.init_state_s", "compile.other_s",
    "compile.cache_misses", "setup.before_build_s",
]


def _fresh_recorder():
    """The recorder is the process's and the tests before this one
    compiled: its sums start again, as in a process of the run's own."""
    from dlrover_tpu.common import compile_cache

    rec = compile_cache.watch_compiles()
    for key in rec.seconds:
        rec.seconds[key] = 0.0
    rec.step_fetch_s, rec.cache_misses = 0.0, 0
    return rec


def test_manifest_lists_them_for_every_cell():
    from benchmarks.tests.test_rehearsal import _manifest

    entries = {m["name"]: m for m in _manifest()["per_layer"]}
    for name in NEW:
        entry = entries[name]
        assert "workloads" not in entry and entry["moves"] == "setup_s"
        assert entry["source"] == "program_counter"
        assert entry["better"] == "lower"
        assert entry["layer"] == (
            "process start" if name.startswith("setup.") else "compile cache"
        )


def test_traced_run_reports_the_compile_path(monkeypatch, capsys):
    _fresh_recorder()
    rc, _cell, _manifest, lines = _run_patched(monkeypatch, capsys, TINY, 1)
    assert rc == 0
    metrics = {
        name: m["value"]
        for name, m in json.loads(lines[-1])["metrics"].items()
    }
    assert set(NEW) <= set(metrics)
    for name in STEP + ["compile.init_state_s", "compile.other_s",
                        "setup.before_build_s"]:
        assert metrics[name] > 0, name
    # a subset of the events the benchmark's CompileWatch adds up, each
    # trace once where that adds the nested ones again
    assert (
        sum(metrics[n] for n in STEP) + metrics["compile.init_state_s"]
        + metrics["compile.other_s"]
    ) <= metrics["compile_cache.compile_s"]
    assert metrics["compile.step_fetch_s"] <= metrics["compile.step_backend_s"]
    _checks, events = _events(lines)
    # the rehearsal's cache directory starts empty: everything is compiled
    assert metrics["compile.step_fetch_s"] == 0
    assert metrics["compile.cache_misses"] >= 2
    assert sum(metrics[n] for n in STEP) <= events["compiled"]["step_compile_s"]


def test_program_without_the_counters_leaves_them_out(monkeypatch, capsys):
    """The parent commit: no recorder, so no counter; the readers return
    nothing, the line lacks the eight and the run still succeeds."""
    from benchmarks.lib import counters

    monkeypatch.setattr(
        "dlrover_tpu.observability.tracing.counters",
        lambda: {"zero.exchange_bytes": 1.0},
    )
    assert counters.program_counters() == {"zero.exchange_bytes": 1.0}
    rc, _cell, _manifest, lines = _run_patched(monkeypatch, capsys, TINY, 1)
    assert rc == 0
    result = json.loads(lines[-1])
    assert result["correct"] is True
    assert not set(NEW) & set(result["metrics"])
    assert "compile_cache.compile_s" in result["metrics"]


@pytest.mark.parametrize("name", NEW)
def test_reader_is_the_counter(monkeypatch, name):
    from benchmarks import run as bench_run

    counter = name.replace("step_", "step.").replace(
        "init_state_s", "init_state.s"
    ).replace("other_s", "other.s")
    monkeypatch.setattr(
        "dlrover_tpu.observability.tracing.counters", lambda: {counter: 1.25}
    )
    assert bench_run.read_layer_metric(name, {}) == 1.25
    monkeypatch.setattr(
        "dlrover_tpu.observability.tracing.counters", lambda: {}
    )
    assert bench_run.read_layer_metric(name, {}) is None
