"""The two ZeRO readers on a hand-built ``run``: the value, and None
when the program keeps no such counter (the parent commit)."""

import importlib.util
import os

import pytest

from benchmarks.lib.spans import Spans

HERE = os.path.dirname(os.path.abspath(__file__))


def _reader(name):
    path = os.path.join(HERE, "..", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _run(collective_s=0.3, dispatches=3):
    spans = Spans()
    with spans.span("dispatch"):  # outside the traced window: not a step of it
        pass
    with spans.span("traced_window"):
        for _ in range(dispatches):
            with spans.span("dispatch"):
                pass
    return {"trace": {"collective_s": collective_s}, "spans": spans}


def _patched(name, monkeypatch, values):
    read = _reader(name)
    monkeypatch.setitem(read.__globals__, "program_counters", lambda: dict(values))
    return read


def test_exchange_mb_per_step(monkeypatch):
    values = {"zero.exchange_bytes": 3_280_000_000, "zero.gather_bytes": 3_280_000_000}
    read = _patched("zero.exchange_mb_per_step", monkeypatch, values)
    assert read(_run()) == pytest.approx(6560.0)
    del values["zero.gather_bytes"]
    assert read(_run()) is None


def test_wire_gb_per_s(monkeypatch):
    values = {"zero.exchange_bytes": 4e9, "zero.gather_bytes": 4e9}
    read = _patched("zero.wire_gb_per_s", monkeypatch, values)
    # 8 GB a step over 0.3 s of collectives in 3 traced steps = 80 GB/s
    assert read(_run(0.3, 3)) == pytest.approx(80.0)
    assert read(_run(0.0, 3)) is None
    assert read(_run(0.3, 0)) is None
    assert read({"trace": None, "spans": Spans()}) is None
    values.clear()
    assert read(_run()) is None


def test_a_program_without_counters_reads_as_empty(monkeypatch):
    import dlrover_tpu.observability.tracing as tracing

    from benchmarks.lib.counters import program_counters

    monkeypatch.delattr(tracing, "counters")
    assert program_counters() == {}
