"""From a profiler trace to numbers: the benchmark's own reduction.

Only the device planes count as device time. A plane is a device when
its name is ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event
per executed HLO operation, nested where an operation (a ``while``, a
``call``) runs others inside it. Host threads are other planes: their
events are never summed into device time; they are read only to find
the window and to name what the host was doing during a gap.

The neutral form the functions below work on, so that a hand-built
trace can test them:

    planes = [{"name": str,
               "lines": [{"name": str,
                          "events": [(name, start_ns, duration_ns), ...]}]}]

Definitions (all per device, then averaged over the devices used):

- window: the host span named ``window_span`` (the benchmark's own
  annotation around the traced steps; the profiler puts host and
  device events on one clock), else first event start to last end.
- busy: the measure of the union of the operation intervals, clipped
  to the window. idle share = 1 - busy / window.
- self time of an operation: its duration minus that of the operations
  nested in it, so that a ``while`` is not counted on top of its body.
- Pallas share: self time of the operations that are Pallas kernels
  (``is_pallas``) over busy.
- by name: every operation's self time and number of calls under its
  ``label`` (short name, opcode, kernel mark, output shape). The self
  times of a device add up to its busy time; ``device_ops`` is the
  first device's table cut to the largest few.
- op names: the TPU's trace carries no name stack per event, the
  compiled module's text does. ``op_names(compiled.as_text())`` maps an
  instruction to its ``op_name`` metadata, the path of jax transforms
  and of the program's ``jax.named_scope``s it was traced under
  (``jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/
  mlp/moe.combine/gather``); handed to ``reduce`` with the module's own
  name (``module_name``) it comes back per device as ``op_names``:
  label -> {that path: self seconds}, the label's time split by the
  path of the instruction each event came from, for the events whose
  instruction has one. Only an event that ran inside an execution of
  that module (the device's line ``XLA Modules``) is looked up: an
  instruction name says nothing about another program's ``fusion.3``.
  ``scope_seconds`` sums a device's rows under given scopes. A kernel
  the compiler itself puts in place of a primitive
  (``ragged-dot-none``) keeps no path: its ``op_name`` is the kernel's
  own name. A fusion the compiler left without metadata takes the
  FIRST ``op_name`` inside the computation it calls, whichever of its
  instructions takes the time.
- exposed collective time: measure of (union of collective intervals)
  minus (union of every other leaf operation's interval): the time a
  collective runs and no compute does. A collective's interval is its
  event on ``XLA Ops`` (a synchronous one, or the ``-start`` and
  ``-done`` halves of an asynchronous one) and, for an asynchronous
  one, its start-to-done span on the line ``Async XLA Ops``.
"""

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"  # one event per execution: ``<module>(<id>)``
ASYNC_LINE = "Async XLA Ops"  # start-to-done spans of asynchronous ops
COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)


def find_xplane(trace_dir):
    paths = sorted(
        glob.glob(
            os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
        )
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path):
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events
            ]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


_OPCODE = re.compile(r"(?:^|[\s)])([a-z][a-z0-9\-]*)\(")


def parse_op(name):
    """(short name, opcode, output shape) of an ``XLA Ops`` event. The
    TPU profiler names an event by its whole HLO instruction,
    ``%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(...), kind=...``; a
    bare name such as ``all-reduce.3`` is its own short name, with the
    opcode in front of the dot."""
    head, sep, rest = name.partition(" = ")
    short = head.lstrip("%")
    if not sep:
        return short, short.split(".")[0], ""
    m = _OPCODE.search(rest)
    if not m:
        return short, short.split(".")[0], ""
    shape = re.sub(r"\{[^{}]*\}", "", rest[: m.start(1)]).strip()
    return short, m.group(1), shape


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def op_names(hlo_text):
    """Instruction name -> ``op_name`` metadata, from a compiled
    module's text (``compiled.as_text()``). An instruction the compiler
    left without metadata (a fusion it made of several) takes the first
    ``op_name`` inside the computation it calls; one that has neither is
    left out."""
    names, first_inside, calls = {}, {}, []
    computation = ""
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            c = _COMPUTATION.match(line)
            if c:
                computation = c.group(1)
            continue
        found = _OP_NAME.search(line)
        if found:
            names[m.group(1)] = found.group(1)
            first_inside.setdefault(computation, found.group(1))
            continue
        called = _CALLS.search(line)
        if called:
            calls.append((m.group(1), called.group(1)))
    for instruction, called in calls:
        if called in first_inside:
            names[instruction] = first_inside[called]
    return names


_PATH = re.compile(r"[/;()]")


def has_scope(op_name, scope):
    """Whether ``scope`` (a ``jax.named_scope`` of the program, or a
    transform jax names: ``jvp``, ``transpose``, ``checkpoint``,
    ``rematted_computation``) is a component of the path ``op_name``.
    Components end at ``/``, at the brackets of a transform
    (``transpose(jvp(mlp))``) and at the ``;`` between the paths of
    instructions the compiler merged."""
    return scope in _PATH.split(op_name)


def module_name(hlo_text):
    """``jit_step_fn`` of ``HloModule jit_step_fn, is_scheduled=...``."""
    m = re.match(r"\s*HloModule ([\w.\-]+)", hlo_text)
    return m.group(1) if m else None


def scope_seconds(device, scopes):
    """{label: self seconds} of one device's rows (an entry of
    ``per_device``) under an ``op_name`` that has one of ``scopes`` as a
    path component; empty where ``reduce`` was given no ``op_names``."""
    out = {}
    for label, paths in (device.get("op_names") or {}).items():
        seconds = sum(
            s for path, s in paths.items()
            if any(has_scope(path, scope) for scope in scopes)
        )
        if seconds:
            out[label] = seconds
    return out


def label(name, width=96):
    short, opcode, shape = parse_op(name)
    kernel = " tpu_custom_call" if is_pallas(name) else ""
    return f"{short} {opcode}{kernel} {shape}".strip()[:width]


def is_collective(name):
    """all-reduce, all-gather, ... and their asynchronous ``-start`` /
    ``-done`` halves."""
    return parse_op(name)[1].startswith(COLLECTIVES)


def is_pallas(name):
    """A Pallas kernel in the device trace: the HLO custom call whose
    target is ``tpu_custom_call``. Until the kernels carry names of
    their own (the ``tracing`` issue) that is all that tells them from
    each other."""
    return "tpu_custom_call" in name


def union(intervals):
    """Sorted, disjoint (start, end) covering the same points."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Points of the disjoint sorted intervals ``a`` not in ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(events, t0, t1):
    out = []
    for name, start, dur in events:
        s, e = max(start, t0), min(start + dur, t1)
        if e > s:
            out.append((name, s, e))
    return out


def self_times(events):
    """[(name, start, end, self_ns, is_leaf)] for (name, start, end)
    events of one line, where an event inside another is its child."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out, stack = [], []  # stack of indexes into out
    for name, s, e in order:
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= min(e, parent[2]) - s
            parent[4] = False
        out.append([name, s, e, e - s, True])
        stack.append(len(out) - 1)
    return [tuple(x) for x in out]


def _host_spans(planes, prefix="bench."):
    spans = []
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(prefix):
                    spans.append((name, start, start + dur))
    return spans


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _within(intervals):
    """Whether a time lies in one of the sorted disjoint ``intervals``."""
    starts = [s for s, _e in intervals]

    def within(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < intervals[i][1]

    return within


def reduce(planes, window_span=None, top=10, op_names=None, module=None):
    """``op_names``: instruction -> ``op_name`` of the traced program
    (``op_names(compiled.as_text())``) and ``module`` its name
    (``module_name``), or None. Where a device has the line
    ``XLA Modules``, only the events inside an execution of ``module``
    are looked up; a trace without that line has one program's events."""
    op_names = op_names or {}
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"]) and _line(p, OPS_LINE)]
    if not devices:
        return None
    host = _host_spans(planes)
    windows = [(s, e) for n, s, e in host if n == window_span]
    per_device = []
    for plane in devices:
        ops = _line(plane, OPS_LINE)
        if windows:
            t0, t1 = windows[0]
        else:
            t0 = min(s for _n, s, _d in ops)
            t1 = max(s + d for _n, s, d in ops)
        timed = self_times(clip(ops, t0, t1))
        leaves = [ev for ev in timed if ev[4]]
        busy = union((s, e) for _n, s, e, _self, _leaf in timed)
        in_flight = clip(
            [ev for ev in _line(plane, ASYNC_LINE) if is_collective(ev[0])],
            t0, t1,
        )
        coll = union(
            [(s, e) for n, s, e, _x, _l in leaves if is_collective(n)]
            + [(s, e) for _n, s, e in in_flight]
        )
        comp = union(
            (s, e) for n, s, e, _x, _l in leaves if not is_collective(n)
        )
        executions = clip(_line(plane, MODULES_LINE), t0, t1)
        ours = _within(union(
            (s, e) for n, s, e in executions if n.split("(")[0] == module
        )) if executions and module else (lambda t: True)
        by_name, pallas_ns = {}, 0.0  # label -> [self ns, calls]
        paths = {}  # label -> {op_name of the event's instruction: self ns}
        for name, start, _e, self_ns, _leaf in timed:
            key = label(name)
            row = by_name.setdefault(key, [0.0, 0])
            row[0] += self_ns
            row[1] += 1
            if is_pallas(name):
                pallas_ns += self_ns
            path = op_names.get(parse_op(name)[0]) if ours(start) else None
            if path is not None:
                split = paths.setdefault(key, {})
                split[path] = split.get(path, 0.0) + self_ns
        gaps = subtract([(t0, t1)], busy)
        per_device.append({
            "plane": plane["name"],
            "window_s": (t1 - t0) / 1e9,
            "busy_s": measure(busy) / 1e9,
            "pallas_s": pallas_ns / 1e9,
            "collective_s": measure(coll) / 1e9,
            "collective_exposed_s": measure(subtract(coll, comp)) / 1e9,
            "by_name": {k: [ns / 1e9, n] for k, (ns, n) in by_name.items()},
            "op_names": {
                k: {path: ns / 1e9 for path, ns in split.items()}
                for k, split in paths.items()
            },
            "modules": sorted({n.split("(")[0] for n, _s, _e in executions}),
            "gaps": gaps,
        })
    # the breakdown names the first device's operations and gaps; the
    # totals are means over the devices
    first = per_device[0]
    gap_by_span = {}
    for s, e in first["gaps"]:
        span = _covering(host, s, e, skip=window_span)
        gap_by_span[span] = gap_by_span.get(span, 0.0) + (e - s) / 1e9
    totals = {
        key: sum(d[key] for d in per_device) / len(per_device)
        for key in (
            "window_s", "busy_s", "pallas_s", "collective_s",
            "collective_exposed_s",
        )
    }
    return {
        "devices": len(per_device),
        **totals,
        "device_ops": _ranked(
            {k: v[0] for k, v in first["by_name"].items()}, top
        ),
        "idle_gaps": _ranked(gap_by_span, top),
        # with each device's whole table of operations, ``by_name``:
        # label -> [self seconds, calls] inside the window,
        # ``op_names``: label -> {``op_name``: self seconds} and
        # ``modules``: the programs that ran in the window
        "per_device": [
            {k: v for k, v in d.items() if k != "gaps"} for d in per_device
        ],
    }


def _ranked(seconds_by_name, top):
    ranked = sorted(seconds_by_name.items(), key=lambda kv: -kv[1])
    return [[name, seconds] for name, seconds in ranked[:top]]


def _covering(host_spans, s, e, skip=None):
    """Name of the host span that covers most of [s, e]."""
    best, best_cover = "none", 0.0
    for name, hs, he in host_spans:
        if name == skip:
            continue
        cover = min(e, he) - max(s, hs)
        if cover > best_cover:
            best, best_cover = name, cover
    return best

