"""The compile recorder (``common/compile_cache.py``): seconds by phase
and by program, each counted once, in the counter table; and the step's
own compile on the trainer's timeline (``train.compile``)."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from dlrover_tpu.common import compile_cache
from dlrover_tpu.models import get_config
from dlrover_tpu.observability import telemetry, tracing
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.train import (
    Trainer, TrainerArgs, TrainStepBuilder, init_train_state, make_optimizer,
)

PHASES = ("trace_s", "lower_s", "backend_s")
CLASSES = (compile_cache.STEP, compile_cache.INIT_STATE, compile_cache.OTHER)
STEP_COUNTERS = tuple(f"compile.step.{p}" for p in PHASES)


def _cfg():
    return get_config(
        "tiny", n_layer=2, d_model=64, d_ff=128, n_head=4,
        vocab_size=128, max_seq=32,
    )


def _batch(batch=8, seq=32):
    tokens = np.random.RandomState(0).randint(0, 8, size=(batch, seq + 1))
    return {
        "tokens": jnp.asarray(tokens[:, :-1], jnp.int32),
        "targets": jnp.asarray(tokens[:, 1:], jnp.int32),
    }


def _account(rec):
    """The recorder's sums and the counter table, as they stand."""
    return {
        "seconds": dict(rec.seconds), "totals": dict(rec.totals),
        "misses": rec.cache_misses, "fetch_s": rec.step_fetch_s,
        "counters": tracing.counters(),
    }


def _moved(before, after, cls):
    return sum(
        after["seconds"][cls, p] - before["seconds"][cls, p] for p in PHASES
    )


class _Events:
    """jax's three compile events as they arrive, beside the recorder."""

    def __init__(self):
        self.seen = []  # (phase, fun_name, start, end)

    def __call__(self, event, start, end, fun_name="", **_kw):
        phase = compile_cache._PHASES.get(event)
        if phase:
            self.seen.append((phase, fun_name, start, end))

    def __enter__(self):
        jax.monitoring.register_event_time_span_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_time_span_listener(self)


@pytest.fixture
def persistent_cache(tmp_path):
    """jax's persistent cache in a directory of this test's own, every
    executable cached however quick its compile."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    before = {name: getattr(jax.config, name) for name in names}
    for name, value in zip(names, (str(tmp_path / "cache"), 0.0, -1)):
        jax.config.update(name, value)
    cc.reset_cache()
    yield
    for name, value in before.items():
        jax.config.update(name, value)
    cc.reset_cache()


def test_cache_miss_then_hit(persistent_cache):
    """Compiled against an empty persistent cache: trace, lower, backend
    and one miss. The same function after ``jax.clear_caches()``: a hit,
    with the seconds jax reports for the retrieval, and no new miss."""
    rec = compile_cache.watch_compiles()

    def recorder_probe_step(x):
        return lax.dot(lax.sin(x), x)

    rec.step_program("recorder_probe_step")
    x = np.ones((16, 16), np.float32)
    start = _account(rec)
    jax.jit(recorder_probe_step)(x)
    cold = _account(rec)
    for p in PHASES:
        assert (
            cold["seconds"]["step", p] > start["seconds"]["step", p]
        ), p
    assert cold["misses"] == start["misses"] + 1
    assert cold["fetch_s"] == start["fetch_s"]
    assert rec.last_step["cache_hit"] is False

    jax.clear_caches()
    jax.jit(recorder_probe_step)(x)
    warm = _account(rec)
    assert warm["misses"] == cold["misses"]
    fetched = warm["fetch_s"] - cold["fetch_s"]
    assert 0 < fetched <= (
        warm["seconds"]["step", "backend_s"]
        - cold["seconds"]["step", "backend_s"]
    )
    assert rec.last_step["cache_hit"] is True
    table = warm["counters"]
    assert table["compile.step.fetch_s"] == warm["fetch_s"]
    assert table["compile.cache_misses"] == warm["misses"]
    for p in PHASES:
        assert table[f"compile.step.{p}"] == warm["seconds"]["step", p]


@pytest.fixture(scope="module")
def three_programs():
    """A builder's step, ``init_train_state`` and a third function, each
    made on its own; the recorder's account before and after each."""
    rec = compile_cache.watch_compiles()
    cfg = _cfg()
    mesh = build_mesh(MeshConfig(dp=2, fsdp=4))
    opt = make_optimizer(learning_rate=1e-3)
    batch, rng = _batch(), jax.random.key(0)  # their own small programs
    accounts = [_account(rec)]
    state = init_train_state(rng, cfg, mesh, opt)
    jax.block_until_ready(state)
    accounts.append(_account(rec))
    step = TrainStepBuilder(cfg, mesh, opt).build()
    step.lower(state, batch).compile()
    accounts.append(_account(rec))

    def recorder_probe_third(x):
        return jnp.tanh(x) @ x

    jax.jit(recorder_probe_third)(np.ones((8, 8), np.float32))
    accounts.append(_account(rec))
    return dict(zip(
        ("start", compile_cache.INIT_STATE, compile_cache.STEP,
         compile_cache.OTHER),
        accounts,
    ))


@pytest.mark.parametrize("cls", CLASSES)
def test_program_lands_in_its_class(three_programs, cls):
    """What the builder jits is the step's, what ``init_train_state``
    compiles is the initialisation's, anything else is other's — and
    each moved its own class alone."""
    order = ("start", compile_cache.INIT_STATE, compile_cache.STEP,
             compile_cache.OTHER)
    before = three_programs[order[order.index(cls) - 1]]
    after = three_programs[cls]
    assert _moved(before, after, cls) > 0
    for other in CLASSES:
        if other != cls:
            assert _moved(before, after, other) == 0, other


def test_classes_sum_to_totals_and_to_the_counters(three_programs):
    end = three_programs[compile_cache.OTHER]
    assert set(end["totals"]) == set(PHASES)
    for p in PHASES:
        assert end["totals"][p] == sum(end["seconds"][c, p] for c in CLASSES)
    table = end["counters"]
    for cls in (compile_cache.INIT_STATE, compile_cache.OTHER):
        assert table[f"compile.{cls}.s"] == pytest.approx(
            sum(end["seconds"][cls, p] for p in PHASES), abs=1e-9
        )
    step = three_programs[compile_cache.STEP]
    assert [step["counters"][n] for n in STEP_COUNTERS] == [
        step["seconds"]["step", p] for p in PHASES
    ]
    assert table["setup.before_build_s"] > 0


def test_before_build_survives_an_emptied_table(monkeypatch):
    """``setup.before_build_s`` closes once a process, and every builder
    sets that value again: a table another test emptied in between
    (``tracing._counters.clear()``) does not lose it."""
    rec = compile_cache.watch_compiles()
    rec.first_build()
    closed = rec.before_build_s
    monkeypatch.setattr(tracing, "_counters", {})
    rec.first_build()
    assert rec.before_build_s == closed
    assert tracing.counters() == {"setup.before_build_s": closed}


def test_a_trace_is_counted_once():
    """jax reports the jitted functions a traced function calls inside
    its own interval and before it: the recorder counts the outer
    interval alone, jax's events added up count them again."""
    rec = compile_cache.watch_compiles()

    def recorder_probe_nested(x):
        for _ in range(4):
            x = jnp.tanh(jnp.matmul(x, x))
        return x

    before = _account(rec)
    with _Events() as events:
        jax.jit(recorder_probe_nested)(np.ones((8, 8), np.float32))
    after = _account(rec)
    traces = [e for e in events.seen if e[0] == "trace_s"]
    outer = [e for e in traces if e[1] == "recorder_probe_nested"]
    assert len(outer) == 1 and len(traces) > 1
    _, _, start, end = outer[0]
    assert all(start <= s and e <= end for _, _, s, e in traces)
    moved = after["totals"]["trace_s"] - before["totals"]["trace_s"]
    assert moved == pytest.approx(end - start, abs=1e-9)
    assert moved < sum(e - s for _, _, s, e in traces)


def test_watch_compiles_twice_does_not_double_a_sum():
    rec = compile_cache.watch_compiles()
    assert compile_cache.watch_compiles() is rec
    from jax._src import monitoring

    listeners = monitoring.get_event_time_span_listeners()
    assert sum(cb == rec._on_span for cb in listeners) == 1

    def recorder_probe_flat(x):
        return lax.add(x, x)  # no jitted function inside

    before = _account(rec)
    with _Events() as events:
        jax.jit(recorder_probe_flat)(np.ones((4,), np.float32))
    after = _account(rec)
    assert sorted(e[0] for e in events.seen) == sorted(PHASES)
    for phase, _, start, end in events.seen:
        assert after["totals"][phase] - before["totals"][phase] == (
            pytest.approx(end - start, abs=1e-9)
        )


def test_compile_cache_module_imports_without_jax():
    """The agent resolves the cache directory and must not hold the
    chip: the module alone pulls in neither jax nor the tracer."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; from dlrover_tpu.common import compile_cache; "
         "print('jax' in sys.modules)"],
        capture_output=True, text=True, check=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.stdout.strip() == "False"


@pytest.fixture
def trainer_run(tmp_path, monkeypatch):
    """Two steps of a restarted worker's Trainer; the telemetry it
    published."""
    monkeypatch.setenv(
        "DLROVER_TPU_RUN_ID", f"cr{os.getpid()}_{time.time_ns()}"
    )
    monkeypatch.setenv("DLROVER_TPU_RESTART_COUNT", "1")
    tracing.reset_tracer()
    telemetry.reset_hub()
    published = []
    telemetry.configure_hub(sinks=[telemetry.CallbackSink(published.append)])

    def run():
        def data():
            while True:
                yield _batch()

        trainer = Trainer(
            _cfg(),
            TrainerArgs(
                output_dir=str(tmp_path), max_steps=2, save_interval=0,
                report_to_master=False,
            ),
            data(),
            make_optimizer(learning_rate=1e-3),
            mesh=build_mesh(MeshConfig(dp=2, fsdp=4)),
        )
        trainer.train()
        return trainer, published

    yield run
    tracing.reset_tracer()
    telemetry.reset_hub()


def test_trainer_lays_the_compile_into_its_first_step(trainer_run):
    tracer = tracing.configure_tracer("worker", force=True)
    trainer, published = trainer_run()
    spans = tracing.span_intervals(tracer.events(), prefix="train.")
    steps = [s for s in spans if s["name"] == "train.step"]
    compiles = [s for s in spans if s["name"] == "train.compile"]
    assert len(steps) == 2 and len(compiles) == 1
    first = min(steps, key=lambda s: s["start_s"])
    made = compiles[0]
    assert made["parent"] == first["id"]
    args = made["args"]
    phases = args["trace_s"] + args["lower_s"] + args["backend_s"]
    assert 0 < phases <= first["dur_s"]
    assert made["dur_s"] == pytest.approx(phases)
    assert first["start_s"] <= made["start_s"] + 1e-3
    assert args["cache_hit"] in (True, False)
    back = [r for r in published if getattr(r, "kind", "") == "first_step_back"]
    assert len(back) == 1
    detail = dict(kv.split("=") for kv in back[0].detail.split())
    assert detail["step"] == "1"
    for p in PHASES:
        assert float(detail[p]) == args[p]
    assert detail["cache_hit"] == str(args["cache_hit"])


def test_trainer_with_the_tracer_off_takes_the_null_span(trainer_run):
    assert tracing.get_tracer() is tracing._NULL_TRACER
    assert tracing.get_tracer().step_span("train.step", 1) is tracing._NULL_SPAN
    trainer, published = trainer_run()
    assert tracing.get_tracer().events() == []
    # the recorder saw the step all the same: the restart's telemetry has it
    assert trainer._step_compile["trace_s"] > 0
    back = [r for r in published if getattr(r, "kind", "") == "first_step_back"]
    assert len(back) == 1 and "backend_s=" in back[0].detail
