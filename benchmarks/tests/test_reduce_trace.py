"""The trace reduction on a hand-built trace with known answers: busy
union, idle share, self time under nesting, Pallas share, exposed
collective time, gap attribution, and that host planes never count as
device time."""

import pytest

from benchmarks.lib import trace

US = 1000.0  # the trace is in nanoseconds; the test thinks in microseconds

KERNEL = (
    "%closed_call.3 = (bf16[8,2,128,64]{3,2,1,0:T(8,128)(2,1)}) "
    "custom-call(bf16[8,2,128,64]{3,2,1,0} %q), "
    'custom_call_target="tpu_custom_call"'
)
FUSION = (
    "%fusion.7 = bf16[8,128,256]{2,1,0:T(8,128)(2,1)S(1)} "
    "fusion(bf16[8,128,256]{2,1,0} %x), kind=kOutput"
)
WHILE = "%while.1 = (s32[]{:T(128)}, bf16[8,128]{1,0}) while((s32[]) %t)"
AR_START = "%all-reduce-start.2 = f32[1000]{0} all-reduce-start(f32[1000]{0} %g)"
AR_DONE = "%all-reduce-done.2 = f32[1000]{0} all-reduce-done(f32[1000]{0} %s)"


def _ev(name, start_us, dur_us):
    return (name, start_us * US, dur_us * US)


def _device(n, shift_us=0.0):
    # window 0..1000 us. while 100..700 holds fusion 100..300, kernel
    # 300..500, fusion 500..650, the all-reduce's start op 650..660 and
    # fusion 660..700; the all-reduce is in flight 650..900 and its
    # done op waits 800..900; a last fusion 900..950. Busy: 100..700, 800..950 = 750 us. Idle gaps: 0..100,
    # 700..800, 950..1000.
    ops = [
        _ev(WHILE, 100 + shift_us, 600),
        _ev(FUSION, 100 + shift_us, 200),
        _ev(KERNEL, 300 + shift_us, 200),
        _ev(FUSION, 500 + shift_us, 150),
        _ev(AR_START, 650 + shift_us, 10),
        _ev(FUSION, 660 + shift_us, 40),
        _ev(AR_DONE, 800 + shift_us, 100),
        _ev(FUSION, 900 + shift_us, 50),
    ]
    return {
        "name": f"/device:TPU:{n}",
        "lines": [
            {"name": "Steps", "events": [_ev("0", 100, 850)]},
            {"name": "XLA Modules",
             "events": [_ev("jit_step", 100 + shift_us, 850)]},
            {"name": "XLA Ops", "events": ops},
            {"name": "Async XLA Ops",
             "events": [_ev(AR_START, 650 + shift_us, 250),
                        _ev("%copy-start.1 = (f32[1]{0}) copy-start(f32[1] %a)",
                            0, 1000)]},
        ],
    }


HOST = {
    "name": "/host:CPU",
    "lines": [{
        "name": "python3",
        "events": [
            _ev("bench.traced_window", 0, 1000),
            _ev("bench.input", 0, 90),
            _ev("bench.dispatch", 90, 20),
            _ev("bench.readback", 110, 890),
            # a host event as long as the window: never device time
            _ev("$array.py:297 __float__", 0, 1000),
        ],
    }],
}


def test_parse_op():
    assert trace.parse_op(FUSION) == ("fusion.7", "fusion", "bf16[8,128,256]")
    assert trace.parse_op(KERNEL)[1] == "custom-call"
    assert trace.parse_op(WHILE)[:2] == ("while.1", "while")
    assert trace.parse_op("all-gather.4") == ("all-gather.4", "all-gather", "")
    assert trace.is_pallas(KERNEL) and not trace.is_pallas(FUSION)
    assert trace.is_collective(AR_START) and trace.is_collective(AR_DONE)
    assert not trace.is_collective(FUSION)
    assert not trace.is_collective("%copy-start.1 = (f32[1]{0}) copy-start(f32[1] %a)")


def test_interval_arithmetic():
    u = trace.union([(0, 10), (5, 20), (30, 40), (40, 45), (50, 50)])
    assert u == [(0, 20), (30, 45)]
    assert trace.measure(u) == 35
    assert trace.subtract([(0, 100)], u) == [(20, 30), (45, 100)]
    assert trace.subtract(u, [(10, 35)]) == [(0, 10), (35, 45)]
    assert trace.subtract(u, []) == u


def test_one_device_known_answers():
    r = trace.reduce([HOST, _device(0)], window_span="bench.traced_window")
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["busy_s"] == pytest.approx(750e-6)
    # the kernel's own 200 us of 750 busy; the while adds nothing: its
    # 600 us are all its children's
    assert r["pallas_s"] == pytest.approx(200e-6)
    ops = dict(r["device_ops"])
    assert ops["fusion.7 fusion bf16[8,128,256]"] == pytest.approx(440e-6)
    assert ops.get(trace.label(WHILE), 0.0) == pytest.approx(0.0, abs=1e-12)
    # in flight 650..900; compute (leaf, not collective) covers
    # 660..700 of it; 650..660 is the start op itself, a collective.
    # Exposed: 650..660 and 700..900.
    assert r["collective_s"] == pytest.approx(250e-6)
    assert r["collective_exposed_s"] == pytest.approx(210e-6)
    gaps = dict(r["idle_gaps"])
    # a gap goes whole to the host span that covers most of it: 0..100
    # to input (90 of it; dispatch has 10), 700..800 and 950..1000 to
    # readback
    assert gaps["bench.input"] == pytest.approx(100e-6)
    assert "bench.dispatch" not in gaps
    assert gaps["bench.readback"] == pytest.approx(150e-6)
    assert sum(gaps.values()) == pytest.approx(250e-6)


def test_devices_are_averaged_and_host_is_not_device_time():
    # the second device runs 50 us later: busy 150..750 and 850..1000,
    # 750 us as well, all inside the window
    r = trace.reduce(
        [HOST, _device(0), _device(1, shift_us=50)],
        window_span="bench.traced_window",
    )
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(750e-6)
    assert [d["plane"] for d in r["per_device"]] == [
        "/device:TPU:0", "/device:TPU:1"
    ]
    # no window span: first op start to last op end on each device
    r = trace.reduce([_device(0)])
    assert r["window_s"] == pytest.approx(850e-6)
    assert r["busy_s"] == pytest.approx(750e-6)


def test_no_device_plane_gives_nothing():
    assert trace.reduce([HOST], window_span="bench.traced_window") is None
    empty = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": []}]}
    assert trace.reduce([HOST, empty]) is None


def test_by_name_table_is_whole_and_counts_calls():
    r = trace.reduce(
        [HOST, _device(0), _device(1, shift_us=50)],
        window_span="bench.traced_window", top=2,
    )
    assert len(r["device_ops"]) == 2  # the printed cut; the table is whole
    for dev in r["per_device"]:
        table = dev["by_name"]
        assert table["fusion.7 fusion bf16[8,128,256]"] == [
            pytest.approx(440e-6), 4
        ]
        assert table[trace.label(KERNEL)] == [pytest.approx(200e-6), 1]
        assert table[trace.label(WHILE)] == [pytest.approx(0.0, abs=1e-12), 1]
        assert table[trace.label(AR_START)][1] == 1
        assert table[trace.label(AR_DONE)] == [pytest.approx(100e-6), 1]
        assert len(table) == 5
        # operations on one line nest or follow each other, so their
        # self times add up to the busy time
        assert sum(s for s, _n in table.values()) == pytest.approx(
            dev["busy_s"]
        )
        assert sum(n for _s, n in table.values()) == 8
    assert dict(r["device_ops"]) == {
        k: v[0] for k, v in r["per_device"][0]["by_name"].items()
        if v[0] >= 200e-6
    }


# ---- op names: the compiled text's name stack, by instruction ------------
# A module text written by hand, in the compiler's form. ``fusion.7`` has
# metadata of its own; ``fusion.8`` has none and takes the first
# ``op_name`` inside the computation it calls; ``fusion.9`` calls a
# computation without any and gets none; the kernel the compiler puts in
# place of a primitive keeps no path, only its own name.
MODULE = """\
HloModule jit_step_fn, is_scheduled=true

%fused_computation.7 (p.0: bf16[8,128,256]) -> bf16[8,128,256] {
  %p.0 = bf16[8,128,256]{2,1,0} parameter(0)
  ROOT %mul.1 = bf16[8,128,256]{2,1,0} multiply(%p.0, %p.0), metadata={op_name="jit(step_fn)/jvp()/while/body/mlp/mul"}
}

%fused_computation.8 (p.1: bf16[8,128,256]) -> bf16[8,128,256] {
  %p.1 = bf16[8,128,256]{2,1,0} parameter(0)
  %gather.3 = bf16[8,128,256]{2,1,0} gather(%p.1), metadata={op_name="jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/moe.combine/gather" source_file="moe.py" source_line=499}
  ROOT %add.2 = bf16[8,128,256]{2,1,0} add(%gather.3, %p.1), metadata={op_name="jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/add"}
}

%fused_computation.9 (p.2: bf16[8,128,256]) -> bf16[8,128,256] {
  %p.2 = bf16[8,128,256]{2,1,0} parameter(0)
  ROOT %copy.4 = bf16[8,128,256]{2,1,0} copy(%p.2)
}

ENTRY %main.1 (x: bf16[8,128,256]) -> bf16[8,128,256] {
  %x = bf16[8,128,256]{2,1,0} parameter(0)
  %fusion.7 = bf16[8,128,256]{2,1,0:T(8,128)(2,1)S(1)} fusion(%x), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mlp/moe.sort/gather"}
  %fusion.8 = bf16[8,128,256]{2,1,0} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.8
  %fusion.9 = bf16[8,128,256]{2,1,0} fusion(%fusion.8), kind=kLoop, calls=%fused_computation.9
  %closed_call.3 = (bf16[8,2,128,64]{3,2,1,0}) custom-call(%fusion.9), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %while.1 = bf16[8,128,256]{2,1,0} copy(%fusion.9)
}
"""
SORT = (
    "jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/"
    "rematted_computation/mlp/moe.sort/gather"
)
COMBINE = (
    "jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/"
    "moe.combine/gather"
)


def test_op_names_from_a_module_text():
    names = trace.op_names(MODULE)
    # metadata on the instruction wins over what it calls
    assert names["fusion.7"] == SORT
    # none on the instruction: the first inside the called computation
    assert names["fusion.8"] == COMBINE
    # on neither: left out, not guessed
    assert "fusion.9" not in names and "while.1" not in names
    assert names["closed_call.3"] == "ragged-dot-none"
    assert names["mul.1"] == "jit(step_fn)/jvp()/while/body/mlp/mul"


def test_scopes_are_path_components():
    assert trace.has_scope(SORT, "moe.sort") and trace.has_scope(SORT, "mlp")
    assert trace.has_scope(SORT, "rematted_computation")
    assert trace.has_scope(SORT, "transpose") and trace.has_scope(SORT, "jvp")
    assert not trace.has_scope(SORT, "moe") and not trace.has_scope(SORT, "sort")
    assert not trace.has_scope(COMBINE, "rematted_computation")
    # older jax renders a scope inside the transforms around it, and the
    # compiler joins the paths of instructions it merged with ";"
    assert trace.has_scope("jit(step)/transpose(jvp(attn))/dot", "attn")
    assert trace.has_scope("a/mlp/reshape;checkpoint/mlp/moe.combine/r", "moe.combine")
    assert not trace.has_scope("ragged-dot-none", "moe.experts")


def test_reduce_carries_op_names_by_label():
    names = trace.op_names(MODULE)
    assert trace.module_name(MODULE) == "jit_step_fn"
    r = trace.reduce(
        [HOST, _device(0), _device(1, shift_us=50)],
        window_span="bench.traced_window", op_names=names, module="jit_step",
    )
    for dev in r["per_device"]:
        assert dev["modules"] == ["jit_step"]
        assert dev["op_names"] == {
            trace.label(FUSION): {SORT: pytest.approx(440e-6)},
            trace.label(KERNEL): {"ragged-dot-none": pytest.approx(200e-6)},
        }
        assert set(dev["op_names"]) <= set(dev["by_name"])
        assert trace.scope_seconds(dev, ("moe.sort", "moe.combine")) == {
            trace.label(FUSION): pytest.approx(440e-6)
        }
        assert trace.scope_seconds(dev, ("attn",)) == {}
    # the printed breakdown and the totals do not move with it
    plain = trace.reduce(
        [HOST, _device(0), _device(1, shift_us=50)],
        window_span="bench.traced_window",
    )
    for key in ("busy_s", "pallas_s", "device_ops", "idle_gaps"):
        assert r[key] == plain[key]
    assert [d["by_name"] for d in r["per_device"]] == [
        d["by_name"] for d in plain["per_device"]
    ]
    # without the compiled text every device's table is empty, and a
    # reader of scopes finds nothing to read
    assert all(d["op_names"] == {} for d in plain["per_device"])
    assert trace.scope_seconds(plain["per_device"][0], ("moe.sort",)) == {}


def _two_programs():
    # the step runs 100..600 us, another program 600..950 us, and the
    # other's ``fusion.7`` is no instruction of the step's text
    dev = _device(0)
    for line in dev["lines"]:
        if line["name"] == "XLA Modules":
            line["events"] = [
                _ev("jit_step(17)", 100, 500), _ev("jit_other(18)", 600, 350),
            ]
    return dev


def test_only_the_modules_own_events_are_looked_up():
    names = trace.op_names(MODULE)
    dev = trace.reduce(
        [HOST, _two_programs()], window_span="bench.traced_window",
        op_names=names, module="jit_step",
    )["per_device"][0]
    assert dev["modules"] == ["jit_other", "jit_step"]
    # the fusions that start at 100 and 500 us are the step's (200 and
    # 150 us); those at 660 and 900 us ran inside the other program
    assert dev["op_names"][trace.label(FUSION)] == {
        SORT: pytest.approx(350e-6)
    }
    assert dev["by_name"][trace.label(FUSION)][0] == pytest.approx(440e-6)
    # a module that never ran in the window names nothing
    none = trace.reduce(
        [HOST, _two_programs()], window_span="bench.traced_window",
        op_names=names, module="jit_step_fn",
    )["per_device"][0]
    assert none["op_names"] == {}
    # a trace without the line is one program's: everything is looked up
    bare = _device(0)
    bare["lines"] = [x for x in bare["lines"] if x["name"] != "XLA Modules"]
    whole = trace.reduce(
        [HOST, bare], window_span="bench.traced_window",
        op_names=names, module="jit_step_fn",
    )["per_device"][0]
    assert whole["modules"] == []
    assert whole["op_names"][trace.label(FUSION)] == {
        SORT: pytest.approx(440e-6)
    }


def test_a_label_two_instructions_share_is_split_by_path():
    # a label starts with the instruction's name and is cut at 96
    # characters: two names that differ only after that share a label,
    # and each event's time goes under its own instruction's path
    long = "fusion_" + "x" * 96
    a = FUSION.replace("fusion.7", long + ".1")
    b = FUSION.replace("fusion.7", long + ".2")
    assert trace.label(a) == trace.label(b)
    attn = "jit(step_fn)/jvp()/attn/dot_general"
    dev = {
        "name": "/device:TPU:0",
        "lines": [{"name": "XLA Ops", "events": [
            _ev(a, 100, 200), _ev(b, 300, 100), _ev(a, 400, 50),
        ]}],
    }
    r = trace.reduce(
        [HOST, dev], window_span="bench.traced_window",
        op_names={long + ".1": SORT, long + ".2": attn},
    )["per_device"][0]
    assert r["by_name"] == {trace.label(a): [pytest.approx(350e-6), 3]}
    assert r["op_names"] == {trace.label(a): {
        SORT: pytest.approx(250e-6), attn: pytest.approx(100e-6),
    }}
    assert trace.scope_seconds(r, ("moe.sort",)) == {
        trace.label(a): pytest.approx(250e-6)
    }
