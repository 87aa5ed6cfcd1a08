"""One tracer from host span to device op (PR 25): the reducer of a
device trace, spans on the profiler's clock, span ids and self time,
the counter table, the save's phases."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.observability import runtime_timer as rt
from dlrover_tpu.observability import telemetry, tracing

MS = 1e6  # nanoseconds


@pytest.fixture(autouse=True)
def _clean():
    tracing.reset_tracer()
    tracing._counters.clear()
    yield
    tracing.reset_tracer()
    tracing._counters.clear()


# ---- (b) the reducer, on a hand-built plane set ----------------------------

_FLASH = (
    "%flash_fwd.1 = bf16[8,64]{1,0} custom-call(bf16[8,64]{1,0} %p), "
    'custom_call_target="tpu_custom_call"'
)
_OP_NAMES = {
    "fusion.1": "jit(step)/jvp()/while/body/closed_call/attn/dot_general",
    "flash_fwd.1": (
        "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
        "rematted_computation/attn/flash_fwd/pallas_call"
    ),
    "fusion.5": "jit(step)/transpose(jvp(head_loss))/mul",
    "fusion.7": "jit(step)/optimizer/add",
}


def _planes(sample_span=True):
    host_events = [
        ("$trainer.py:1 train", 0.0, 100 * MS),  # a python frame
        (rt._DISPATCH_SPAN, 0.0, 10 * MS),
        (rt._WAIT_SPAN, 10 * MS, 90 * MS),
    ]
    if sample_span:
        host_events.append((rt.SAMPLE_SPAN, 0.0, 100 * MS))
    return [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": host_events},
            # a host thread that ran for half a second
            {"name": "tf_XLAEigen/1",
             "events": [("fusion.9", 0.0, 500 * MS)]},
        ]},
        {"name": "/device:TPU:0", "lines": [
            # not the XLA Ops line: never summed
            {"name": "XLA Modules",
             "events": [("jit_step(1)", 5 * MS, 90 * MS)]},
            {"name": "XLA Ops", "events": [
                ("%while.1 = (s32[]) while((s32[]) %t)", 5 * MS, 60 * MS),
                ("%fusion.1 = bf16[8]{0} fusion(%p)", 5 * MS, 20 * MS),
                (_FLASH, 25 * MS, 30 * MS),
                ("%fusion.5 = f32[8]{0} fusion(%p)", 55 * MS, 5 * MS),
                ("%all-reduce.2 = f32[4]{0} all-reduce(%g)", 70 * MS, 10 * MS),
                ("%fusion.7 = f32[8]{0} fusion(%p)", 85 * MS, 10 * MS),
            ]},
        ]},
    ]


def _ms(seconds):
    return round(seconds * 1e3, 6)


REDUCER_CASES = {
    # busy is the union of the device's operations: 5..65, 70..80, 85..95;
    # the host thread's 500 ms and the module event are not in it
    "host-thread-does-not-count": lambda p: _ms(p.busy_s) == 80.0,
    "window-is-the-sample-span": lambda p: _ms(p.window_s) == 100.0,
    "idle-share": lambda p: p.idle_share == pytest.approx(0.2),
    # the while's 60 ms hold 55 ms of body: 5 ms are its own
    "while-not-on-top-of-its-body": lambda p: {
        o.name: round(o.total_us) for o in p.by_op
    }["while.1"] == 5000,
    "self-times-sum-to-busy": lambda p: _ms(
        sum(o.total_us for o in p.by_op) / 1e6
    ) == 80.0,
    "ranked-and-normalised": lambda p: (
        [o.name for o in p.by_op][:2] == ["flash_fwd.1", "fusion.1"]
        and sum(o.fraction for o in p.by_op) == pytest.approx(1.0)
    ),
    "kernel-keeps-its-name": lambda p: _ms(p.pallas_s) == 30.0,
    "phases": lambda p: {k: _ms(v) for k, v in p.by_phase.items()} == {
        "forward": 20.0, "recompute": 30.0, "backward": 5.0,
        "optimizer": 10.0, "exchange": 10.0, "other": 5.0,
    },
    # the innermost named scope of each operation; the while and the
    # all-reduce carry none
    "scopes": lambda p: {k: _ms(v) for k, v in p.by_scope.items()} == {
        "attn": 50.0, "head_loss": 5.0, "optimizer": 10.0, "": 15.0,
    } and {o.name: o.scope for o in p.by_op}["flash_fwd.1"] == "attn",
    "platform-is-the-planes": lambda p: (p.platform, p.devices) == ("TPU", 1),
    # 0..5 under the dispatch span; 65..70, 80..85, 95..100 under the wait
    "gap-named-by-host-span": lambda p: [
        (n, _ms(s)) for n, s in p.gaps
    ] == [(rt._WAIT_SPAN, 15.0), (rt._DISPATCH_SPAN, 5.0)],
}


@pytest.mark.parametrize("case", sorted(REDUCER_CASES))
def test_reduce_planes(case):
    profile = rt.reduce_planes(_planes(), _OP_NAMES)
    assert REDUCER_CASES[case](profile), profile


def test_reduce_planes_without_a_sample_span_or_op_names():
    profile = rt.reduce_planes(_planes(sample_span=False))
    # first operation start to last operation end
    assert _ms(profile.window_s) == 90.0 and _ms(profile.busy_s) == 80.0
    # a collective is known by its opcode; nothing else can be placed
    assert {k: _ms(v) for k, v in profile.by_phase.items()} == {
        "exchange": 10.0, "other": 70.0,
    }


def test_reduce_planes_without_a_device_plane():
    assert rt.reduce_planes(_planes()[:1]) is None


def test_op_names_from_hlo_text():
    text = """\
HloModule jit_step

%fused_computation.3 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(mlp)/mul"}
}

ENTRY %main.1 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.3
  ROOT %add.2 = f32[8]{0} add(%a, %fusion.3), metadata={op_name="jit(step)/optimizer/add" source_file="x.py"}
}
"""
    names = rt.op_names_from_hlo(text)
    assert names["add.2"] == "jit(step)/optimizer/add"
    # a fusion without metadata takes its computation's
    assert names["fusion.3"] == "jit(step)/jvp(mlp)/mul"
    assert rt.phase_of("%add.2 = f32[8] add(%a)", names["add.2"]) == "optimizer"
    assert rt.scope_of("jit(step)/transpose(jvp(embed))/scatter-add") == "embed"
    assert rt.scope_of("jit(s)/zero.exchange/zero.pack/dus") == "zero.pack"
    assert rt.phase_of("%dus.7 = f32[4] dynamic-update-slice(%a)",
                       "jit(s)/zero.pack/dynamic_update_slice") == "exchange"
    # the outermost wrapper decides: a derivative taken inside a
    # forward rule runs in the forward
    inner = "attn/attn.index_loss/transpose(jvp(bqjc,bsc->bjqs))/dot_general"
    for outer, phase in (
        ("jit(s)/jvp()/while/body/closed_call/", "forward"),
        ("jit(s)/transpose(jvp())/while/body/closed_call/", "backward"),
        ("jit(s)/transpose(jvp())/while/body/closed_call/checkpoint/"
         "rematted_computation/", "recompute"),
    ):
        assert rt.phase_of("%fusion.9 = f32[8] fusion(%a)", outer + inner) == phase


_RS = "channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true"
COLLECTIVE_CASES = {
    "reduce-scatter": (
        "  %reduce-scatter.3 = f32[4,128]{1,0} reduce-scatter(f32[16,128]{1,0} "
        f"%p), {_RS}, dimensions={{0}}, to_apply=%add",
        {"reduce-scatter": 1}, {"f32": 2048}, {"reduce-scatter": 2048},
    ),
    "all-reduce": (
        "  %all-reduce.1 = bf16[8,1024,1600]{2,1,0:T(8,128)(2,1)} all-reduce("
        f"bf16[8,1024,1600]{{2,1,0}} %g), {_RS}, to_apply=%add.1",
        {"all-reduce": 1}, {"bf16": 26214400}, {"all-reduce": 26214400},
    ),
    # the loss: a scalar has no dimension and one element
    "all-reduce of a scalar": (
        f"  ROOT %all-reduce.9 = f32[] all-reduce(f32[] %l), {_RS}, to_apply=%add",
        {"all-reduce": 1}, {"f32": 4}, {"all-reduce": 4},
    ),
    "all-gather": (
        "  %all-gather.4 = f32[782,8192,128]{2,1,0} all-gather(f32[782,2048,128]"
        f"{{2,1,0}} %shard), {_RS}, dimensions={{1}}",
        {"all-gather": 1}, {"f32": 3279945728}, {"all-gather": 3279945728},
    ),
    "all-to-all": (
        f"  %all-to-all.2 = s8[4,256]{{1,0}} all-to-all(s8[4,256]{{1,0}} %q), {_RS}",
        {"all-to-all": 1}, {"s8": 1024}, {"all-to-all": 1024},
    ),
    "collective-permute": (
        "  %collective-permute.2 = bf16[2,64]{1,0} collective-permute(bf16[2,64]"
        "{1,0} %x), channel_id=3, source_target_pairs={{0,1},{1,0}}",
        {"collective-permute": 1}, {"bf16": 256}, {"collective-permute": 256},
    ),
    # the dp=4 cell's bucket collectives run combined: one instruction,
    # a tuple result, every member's bytes summed under its own dtype
    "tuple all-reduce": (
        "  %all-reduce.5 = (f32[8192,128]{1,0}, f32[4096,128]{1,0}, bf16[16]{0})"
        " all-reduce(f32[8192,128]{1,0} %a, f32[4096,128]{1,0} %b, bf16[16]{0} "
        f"%c), {_RS}, to_apply=%add",
        {"all-reduce": 1}, {"f32": 6291456, "bf16": 32}, {"all-reduce": 6291488},
    ),
    # counted, but a dtype outside the byte table adds no bytes
    "unknown dtype": (
        "  %all-gather.7 = f8e4m3fn[1024]{0} all-gather(f8e4m3fn[256]{0} %w), "
        f"{_RS}, dimensions={{0}}",
        {"all-gather": 1}, {}, {},
    ),
    "collective-broadcast": (
        "  %collective-broadcast.1 = u32[2]{0} collective-broadcast(u32[2]{0} "
        "%k), channel_id=4, replica_groups={{0,1}}",
        {"collective-broadcast": 1}, {"u32": 8}, {"collective-broadcast": 8},
    ),
    # a collective as an OPERAND, a line that is no instruction, a
    # computation's header
    "no collective": (
        "  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %all-reduce.1), kind=kLoop\n"
        "  all-reduce(f32[8]{0} %p)\n"
        "%all-reduce.clone (a: f32[8]) -> f32[8] {",
        {}, {}, {},
    ),
    # the asynchronous pair is neither ``all-reduce(`` nor counted twice:
    # the compiled steps these tests and the dryruns read hold the
    # synchronous spelling
    "async pair": (
        "  %all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %p), "
        f"{_RS}, to_apply=%add\n"
        "  %all-reduce-done.1 = f32[8]{0} all-reduce-done(f32[8]{0} "
        "%all-reduce-start.1)\n"
        "  %all-gather-start.2 = (f32[2]{0}, f32[8]{0}) all-gather-start(f32[2]"
        f"{{0}} %s), {_RS}, dimensions={{0}}",
        {}, {}, {},
    ),
}


@pytest.mark.parametrize("case", sorted(COLLECTIVE_CASES))
def test_collective_stats(case):
    text, counts, by_dtype, by_op = COLLECTIVE_CASES[case]
    assert rt.collective_stats(text) == {
        "counts": counts, "bytes_by_dtype": by_dtype, "bytes_by_op": by_op,
    }


def test_collective_stats_sums_over_a_module():
    text = "\n".join(COLLECTIVE_CASES[c][0] for c in sorted(COLLECTIVE_CASES))
    stats = rt.collective_stats("HloModule jit_step\n\n" + text + "\n}\n")
    assert stats["counts"] == {
        "all-reduce": 3, "all-gather": 2, "reduce-scatter": 1,
        "all-to-all": 1, "collective-permute": 1, "collective-broadcast": 1,
    }
    assert stats["bytes_by_op"]["all-reduce"] == 26214400 + 4 + 6291488
    assert stats["bytes_by_dtype"]["f32"] == (
        2048 + 4 + 3279945728 + 6291456
    )
    assert sum(stats["bytes_by_op"].values()) == sum(
        stats["bytes_by_dtype"].values()
    )


SCOPE_CASES = {
    # the routed layer's scopes sit inside ``mlp``: the innermost counts
    "moe.sort": "jit(s)/jvp()/while/body/closed_call/mlp/moe.sort/argsort",
    "moe.combine": (
        "jit(s)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/"
        "moe.combine/scatter-add"
    ),
    "moe.route": "jit(s)/jvp(mlp)/moe.route/top_k",
    # lax.ragged_dot's kernel has no name stack: known by its own name
    "moe.experts": "ragged-dot-none",
    "mlp": "jit(s)/jvp()/while/body/closed_call/mlp/add",
    "": "jit(s)/moex.sort/moe.Sort/add",  # not the routed layer's
    # latent attention's own work sits inside ``attn``; its flash call
    # and output projection stay with ``attn``
    "attn.latent": (
        "jit(s)/transpose(jvp())/while/body/closed_call/checkpoint/attn/"
        "attn.latent/concatenate"
    ),
    "attn": "jit(s)/jvp()/while/body/closed_call/attn/flash_fwd/pallas_call",
    "moe.shared": "jit(s)/jvp()/while/body/closed_call/mlp/moe.shared/dot",
    # the prediction module: its projection, head and loss; its block
    # keeps the block's names
    "mtp": "jit(s)/jvp(head_loss)/mtp/reduce_sum",
}
MODULE_BLOCK = "jit(s)/jvp(mtp)/checkpoint/attn/attn.latent/dot_general"


@pytest.mark.parametrize("scope", sorted(SCOPE_CASES))
def test_scope_of_the_routed_layer(scope):
    assert rt.scope_of(SCOPE_CASES[scope]) == scope
    if scope == "mtp":
        assert rt.scope_of(MODULE_BLOCK) == "attn.latent"
    if scope == "moe.experts":
        # which pass a name-stack-less kernel belongs to cannot be told
        assert rt.phase_of("%ragged-dot-none.3 = bf16[8] custom-call(%a)",
                           SCOPE_CASES[scope]) == "other"


def test_find_xplane_picks_the_newest(tmp_path):
    assert rt.find_xplane(str(tmp_path)) is None
    paths = []
    for sub in ("a", "b"):
        d = tmp_path / "plugins" / "profile" / sub
        d.mkdir(parents=True)
        paths.append(d / "host.xplane.pb")
        paths[-1].write_bytes(b"")
    now = time.time()
    os.utime(paths[0], (now, now))
    os.utime(paths[1], (now - 60, now - 60))
    assert rt.find_xplane(str(tmp_path)) == str(paths[0])


# ---- (e) the sampled call runs the step once --------------------------------


@pytest.mark.parametrize("broken", ["reduce", "start", "load"])
def test_profiled_call_runs_the_step_once(monkeypatch, tmp_path, broken):
    def boom(*a, **k):
        raise RuntimeError("no trace today")

    target = {
        "reduce": (rt, "reduce_planes"),
        "load": (rt, "load_planes"),
        "start": (jax.profiler, "start_trace"),
    }[broken]
    monkeypatch.setattr(*target, boom)
    calls = []

    def step(x):
        calls.append(x)
        return x + 1

    timer = rt.RuntimeKernelTimer(interval_steps=1, logdir=str(tmp_path))
    assert timer.profiled_call(1, step, jnp.ones(4)).sum() == 8
    assert len(calls) == 1
    assert timer.sampled_at == -1 and timer.breakdown == []


def test_profiled_call_does_not_swallow_the_steps_own_error(tmp_path):
    calls = []

    def step():
        calls.append(1)
        raise ValueError("the step's own")

    timer = rt.RuntimeKernelTimer(interval_steps=1, logdir=str(tmp_path))
    with pytest.raises(ValueError):
        timer.profiled_call(1, step)
    assert calls == [1]
    # the session was closed: the next sample can start one
    f = jax.jit(lambda a: a @ a)
    timer.profiled_call(2, f, jnp.ones((64, 64)))
    assert timer.sampled_at == 2


def test_host_times_are_never_passed_off_as_a_chips(tmp_path, monkeypatch):
    """A trace without a device plane: where the CPU is jax's backend
    its executed operations stand in, and the profile says so; on any
    other backend the trace reduces to nothing and the sample is not
    taken for one."""
    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((64, 64))
    f(x)
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(f(x))
    path = rt.find_xplane(str(tmp_path))
    assert rt.reduce_planes(rt.load_planes(path)).platform == "CPU"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert rt.reduce_planes(rt.load_planes(path)) is None
    timer = rt.RuntimeKernelTimer(interval_steps=1)
    assert timer.profiled_call(1, f, x).shape == (64, 64)
    assert timer.sampled_at == -1 and timer.profile is None


# ---- (c) spans on the profiler's clock --------------------------------------


def _host_event_names(logdir):
    planes = rt.load_planes(rt.find_xplane(logdir))
    return {
        name
        for plane in planes
        if not rt.DEVICE_PLANE.match(plane["name"])
        for line in plane["lines"]
        for name, _s, _d in line["events"]
    }


def test_spans_land_in_the_profilers_trace(tmp_path):
    tracer = tracing.configure_tracer("test", force=True)
    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((64, 64))
    f(x)
    with jax.profiler.trace(str(tmp_path)):
        with tracer.span("train.input_wait", step=3):
            pass
        with tracer.step_span("train.step", 3):
            y = f(x)
        open_span = tracer.begin("serving.decode", rid="r1")
        jax.block_until_ready(y)
        open_span.end()
    names = _host_event_names(str(tmp_path))
    assert {"train.input_wait", "train.step", "serving.decode"} <= names
    # and the program's reducer sees them as what the host was doing
    planes = rt.load_planes(rt.find_xplane(str(tmp_path)))
    spans = {n for n, _s, _e in rt._host_spans(planes, rt.SPAN_PREFIXES)}
    assert {"train.input_wait", "train.step", "serving.decode"} <= spans


def test_null_tracer_writes_no_annotation(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with tracing.get_tracer().span("train.input_wait"):
            pass
    assert "train.input_wait" not in _host_event_names(str(tmp_path))


# ---- (d) ids, parents, self time --------------------------------------------


def test_span_ids_parents_and_self_time():
    tracer = tracing.Tracer("test")
    with tracer.span("ckpt.save_memory", step=1) as parent:
        with tracer.span("ckpt.plan") as plan:
            time.sleep(0.01)
        overlapping = tracer.begin("serving.decode")
        with tracer.span("ckpt.shm_alloc") as alloc:
            time.sleep(0.01)
        time.sleep(0.006)
        tracer.complete_span(
            "ckpt.d2h_wait", time.monotonic() - 0.005, dur_s=0.005
        )
        overlapping.end()
    after = tracer.span("train.hooks")
    after.end()
    iv = {i["name"]: i for i in tracing.span_intervals(tracer.events())}
    assert iv["ckpt.save_memory"]["parent"] == 0 and after.parent == 0
    for child in ("ckpt.plan", "ckpt.shm_alloc", "ckpt.d2h_wait"):
        assert iv[child]["parent"] == parent.id
    # an explicit-lifetime span has a parent but is nobody's
    assert iv["serving.decode"]["parent"] == parent.id
    assert alloc.parent == parent.id != overlapping.id
    assert len({i["id"] for i in iv.values()}) == len(iv)
    assert iv["ckpt.save_memory"]["args"]["step"] == 1  # correlation kept
    self_s = tracing.self_seconds(list(iv.values()))
    # serving.decode lies over ckpt.shm_alloc and ckpt.d2h_wait: what
    # two children cover together is covered once
    covered = iv["ckpt.plan"]["dur_s"] + iv["serving.decode"]["dur_s"]
    assert self_s[parent.id] == pytest.approx(
        iv["ckpt.save_memory"]["dur_s"] - covered, abs=1e-4
    )
    assert self_s[parent.id] < 0.002
    assert self_s[plan.id] == pytest.approx(iv["ckpt.plan"]["dur_s"])


def test_cancelled_span_records_nothing_and_leaves_the_stack():
    tracer = tracing.Tracer("test")
    outer = tracer.span("ckpt.restore_tree")
    outer.cancel()
    with tracer.span("train.hooks") as nxt:
        pass
    assert nxt.parent == 0
    assert [e["name"] for e in tracer.events()] == ["train.hooks"]


def test_spans_on_other_threads_do_not_nest():
    import threading

    tracer = tracing.Tracer("test")
    seen = []
    with tracer.span("train.hooks"):
        t = threading.Thread(
            target=lambda: seen.append(tracer.span("ckpt.persist").parent)
        )
        t.start()
        t.join()
    assert seen == [0]


# ---- (g) off means off -------------------------------------------------------


def test_null_tracer_hands_back_one_shared_span(tmp_path, monkeypatch):
    monkeypatch.delenv("DLROVER_TPU_TRACE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    t = tracing.get_tracer()
    assert not t.enabled
    spans = {
        id(t.span("train.input_wait", step=1)),
        id(t.step_span("train.step", 1)),
        id(t.begin("serving.decode")),
        id(tracing.get_tracer().span("train.hooks")),
    }
    assert len(spans) == 1
    with t.span("train.readback") as sp:
        sp.cancel()
    assert sp.end() == 0.0 and sp.id == 0
    assert os.listdir(tmp_path) == []


def test_tracing_module_never_imports_jax():
    """The mirror into the profiler's trace looks jax up in sys.modules;
    a process that has not imported it (the master) stays without."""
    import subprocess

    code = (
        "import importlib.util, sys\n"
        "spec = importlib.util.spec_from_file_location('t', sys.argv[1])\n"
        "m = importlib.util.module_from_spec(spec); spec.loader.exec_module(m)\n"
        "tr = m.Tracer('master')\n"
        "with tr.span('rdzv.round', step=1): pass\n"
        "tr.step_span('train.step', 2).end()\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "assert len(tr.events()) == 2\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", code, tracing.__file__],
        env=dict(os.environ, PYTHONPATH=repo), capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


# ---- (f) counters -------------------------------------------------------------


def test_counter_table():
    tracing.set_counter("zero.exchange_bytes", 10)
    tracing.set_counter("zero.exchange_bytes", 10)  # a value: not doubled
    tracing.set_counter("zero.gather_bytes", 7)
    table = tracing.counters()
    assert table == {"zero.exchange_bytes": 10, "zero.gather_bytes": 7}
    table["zero.gather_bytes"] = 99  # a copy
    assert tracing.counters()["zero.gather_bytes"] == 7


@pytest.mark.parametrize("tie", [True, False])
def test_zero_counters_are_the_plans_arithmetic(tie):
    from dlrover_tpu.models.config import get_config
    from dlrover_tpu.parallel import sharding as shd
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.train.optimizer import make_optimizer
    from dlrover_tpu.train.train_step import (
        TrainStepBuilder, abstract_train_state,
    )

    cfg = get_config(
        "tiny", n_layer=2, d_model=64, d_ff=128, n_head=4, vocab_size=128,
        max_seq=32, dtype="float32", tie_embeddings=tie,
    )
    mesh = build_mesh(MeshConfig(dp=-1))
    comm = shd.CommConfig(update_sharding="zero1", bucket_mb=0.05)
    builder = TrainStepBuilder(
        cfg, mesh, make_optimizer(learning_rate=1e-3), comm=comm
    )
    assert builder.update_sharding, builder.update_sharding_reason
    state = abstract_train_state(
        cfg, mesh, builder.optimizer, comm=builder.comm_resolved
    )
    batch = {
        k: jax.ShapeDtypeStruct((16, 32), jnp.int32)
        for k in ("tokens", "targets")
    }
    plan = builder._plan
    buckets = plan.n_buckets + (plan.n_tie_buckets if tie else 0)
    want = {
        "zero.exchange_bytes": buckets * plan.bucket_elems * 4,
        "zero.gather_bytes": plan.n_buckets * plan.bucket_elems * 4,
    }
    assert (plan.n_tie_buckets > 0) == tie
    for _ in range(2):  # traced twice: values, not increments
        jax.jit(builder.step_fn).lower(state, batch)
        got = tracing.counters()
        assert {k: got[k] for k in want} == want


def test_save_leaves_phase_spans_and_record(tmp_path, monkeypatch):
    from dlrover_tpu.checkpoint.engine import CheckpointEngine

    monkeypatch.setenv("DLROVER_TPU_RUN_ID", f"t25_{os.getpid()}")
    tracer = tracing.configure_tracer("worker", force=True)
    telemetry.reset_hub()
    hub = telemetry.configure_hub()
    records = []
    hub.subscribe(records.append, types=("CheckpointRecord",))
    state = {
        # large enough that the phases, not bookkeeping, are the stall
        "params": {"w": jnp.ones((8192, 8192), jnp.float32),
                   "b": jnp.arange(1024, dtype=jnp.float32)},
        "step": jnp.asarray(3, jnp.int32),
    }
    engine = CheckpointEngine(str(tmp_path), use_agent=False)
    try:
        assert engine.save_to_memory(3, state)
        assert engine.save_to_memory(4, state)  # segment reused: no alloc
        iv = tracing.span_intervals(tracer.events(), prefix="ckpt.")
        saves = [i for i in iv if i["name"] == "ckpt.save_memory"]
        assert len(saves) == 2
        first = [i for i in iv if i["parent"] == saves[0]["id"]]
        assert {i["name"] for i in first} == {
            "ckpt.plan", "ckpt.lock_wait", "ckpt.shm_alloc",
            "ckpt.d2h_wait", "ckpt.shm_copy",
        }
        second = [i for i in iv if i["parent"] == saves[1]["id"]]
        assert "ckpt.shm_alloc" not in {i["name"] for i in second}
        for save, children in ((saves[0], first), (saves[1], second)):
            assert sum(c["dur_s"] for c in children) == pytest.approx(
                save["dur_s"], rel=0.05
            )
            assert save["args"]["nbytes"] > 8192 * 8192 * 4
        phases = ("plan", "lock_wait", "shm_alloc", "d2h_wait", "shm_copy")
        assert [r.kind for r in records] == ["save_memory"] * 2
        by_phase = telemetry.parse_phases(records[0].phases)
        assert set(by_phase) == set(phases)
        assert records[0].nbytes == saves[0]["args"]["nbytes"]
        assert "shm_alloc" not in telemetry.parse_phases(records[1].phases)
        assert sum(by_phase.values()) == pytest.approx(
            records[0].seconds, rel=0.05
        )
        # the record survives the wire
        assert telemetry.from_json(records[0].to_json()) == records[0]

        # and the restore, phase by phase
        target = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state
        )
        restored = engine.load(target)
        np.testing.assert_array_equal(
            restored["params"]["b"], state["params"]["b"]
        )
        iv = tracing.span_intervals(tracer.events())
        tree = next(i for i in iv if i["name"] == "ckpt.restore_tree")
        names = {i["name"] for i in iv if i["parent"] == tree["id"]}
        assert names == {
            "ckpt.restore_read", "ckpt.restore_h2d",
            "ckpt.restore_device_wait",
        }
        restore = next(i for i in iv if i["name"] == "failover.restore")
        assert tree["parent"] == restore["id"]
        assert set(telemetry.parse_phases(records[-1].phases)) == {
            "restore_map", "read", "h2d", "device_wait",
        }
    finally:
        telemetry.reset_hub()
        if engine._shm is not None:
            engine._shm.close()
            engine._shm.unlink()


def test_skipped_save_records_no_stall(tmp_path, monkeypatch):
    from dlrover_tpu.checkpoint.engine import CheckpointEngine

    monkeypatch.setenv("DLROVER_TPU_RUN_ID", f"t25s_{os.getpid()}")
    tracer = tracing.configure_tracer("worker", force=True)
    engine = CheckpointEngine(str(tmp_path), use_agent=False)
    engine._lock.acquire()  # the saver is persisting the previous step
    try:
        assert not engine.save_to_memory(1, {"w": jnp.ones(8)})
    finally:
        engine._lock.release()
    names = [e["name"] for e in tracer.events()]
    assert "ckpt.save_memory" not in names
    with tracer.span("train.hooks") as nxt:
        pass
    assert nxt.parent == 0


@pytest.mark.parametrize("failing", ["plan_pack", "write_pack"])
def test_raising_save_leaves_no_open_span(tmp_path, monkeypatch, failing):
    """Before or under the lock: the save's span is closed unrecorded,
    so the next span on the thread is nobody's child."""
    from dlrover_tpu.checkpoint import core
    from dlrover_tpu.checkpoint.engine import CheckpointEngine

    monkeypatch.setenv("DLROVER_TPU_RUN_ID", f"t25r_{os.getpid()}")
    tracer = tracing.configure_tracer("worker", force=True)
    engine = CheckpointEngine(str(tmp_path), use_agent=False)

    def boom(*a, **k):
        raise RuntimeError("no save today")

    monkeypatch.setattr(core, failing, boom)
    try:
        with pytest.raises(RuntimeError):
            engine.save_to_memory(1, {"w": jnp.ones(8)})
        assert tracer._open_spans() == []
        assert engine._lock.acquire(blocking=False)  # the lock was given back
        engine._lock.release()
        with tracer.step_span("train.step", 2) as nxt:
            pass
        assert nxt.parent == 0
        assert "ckpt.save_memory" not in [
            e["name"] for e in tracer.events()
        ]
    finally:
        if engine._shm is not None:
            engine._shm.close()
            engine._shm.unlink()


@pytest.mark.parametrize("replan", ["mesh", "tuning"])
def test_raising_replan_leaves_no_open_span(replan):
    from dlrover_tpu.elastic.trainer import ElasticTrainer

    tracer = tracing.configure_tracer("worker", force=True)
    builds = []

    def build(accum):
        builds.append(accum)
        if len(builds) > 1:
            raise RuntimeError("no step today")
        return lambda state, batch: state

    replicas = [2]
    trainer = ElasticTrainer(
        16, 4, build, data_replicas_fn=lambda: replicas[0]
    )
    with pytest.raises(RuntimeError):
        if replan == "mesh":
            replicas[0] = 4
            trainer._refresh()
        else:
            trainer.apply_tuning({"version": 2, "batch_size": 2})
    assert tracer._open_spans() == []
    names = [e["name"] for e in tracer.events()]
    assert names == ["failover.mesh_replan"]  # the first, which built
