"""Continuous runtime kernel timing: periodic on-device trace sampling.

Reference: xpu_timer (atorch/dev/xpu_timer/nvidia/hook.cc) — an
LD_PRELOAD shim timing every CUDA kernel launch continuously in
production. TPU-native mechanism: XLA owns the schedule, so per-kernel
hooks don't exist; instead, every ``interval_steps`` one training step
runs under ``jax.profiler.trace`` and the ``.xplane.pb`` it writes is
reduced here, by the program itself (``jax.profiler.ProfileData``; no
perfetto conversion). Sampling costs one traced step per interval
instead of a per-launch tax, and the breakdown is the ACTUAL executed
schedule — fusions, kernels, collectives — not a compile-time estimate.

What counts as device time. Only the planes named ``/device:TPU:<n>``,
and of those only the line ``XLA Ops``: one event per executed HLO
operation, nested where an operation (a ``while``, a ``call``) runs
others inside it. Host threads are other planes; their events are never
summed into device time. They are read for one thing: the program's own
spans (``tracing.py`` mirrors every span into the profiler's trace),
which name what the host was doing during each idle gap of the device.
Where jax's backend is the CPU there is no device plane, and
``load_planes`` builds one from the operations the CPU executed:
``DeviceProfile.platform`` then says ``CPU``. On a TPU backend a trace
without a TPU plane reduces to nothing.

The reduction gives, per sampled step (or fused block of steps):

* time by operation — self time (an operation's duration minus that of
  the operations nested in it, so a ``while`` is not counted on top of
  its body), keyed by the HLO instruction's name; the Pallas kernels
  carry their own (``flash_fwd``, ``flash_bwd_dq``, ``norm_fwd``, ...);
* time by phase — ``forward``, ``recompute``, ``backward``,
  ``optimizer``, ``exchange``, ``other`` — told by jax's own wrappers
  and the step's named scopes (``optimizer``, ``zero.pack``, ...). The
  TPU's trace carries no name stack per event, so it comes from the
  compiled step's ``op_name`` metadata, looked up by instruction name;
* busy time (the union of the operation intervals) and the idle gaps of
  the sampled window, each put down to the host span covering most of it.

The breakdown feeds Prometheus via ``prometheus_text``; the Trainer
wires sampling around its live step via ``TrainerArgs.profile_interval``.
"""

import glob
import os
import re
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.observability.telemetry import COLLECTIVE_MARKERS

logger = get_logger(__name__)

DEVICE_PLANE = re.compile(r"^/device:(TPU|CPU):\d+$")
OPS_LINE = "XLA Ops"
PHASES = ("forward", "recompute", "backward", "optimizer", "exchange", "other")
# the step's named scopes (models/decoder.py, train/train_step.py,
# parallel/sharding.py; inside ``attn`` latent attention's
# ``attn.latent`` and a selecting attention's ``attn.index|select|
# index_loss``, inside ``mlp`` parallel/moe.py's
# ``moe.route|sort|experts|combine|shared``; ``mtp`` is the prediction
# module, whose block keeps these names beneath it, so the innermost
# scope of its operations is the block's and ``mtp`` is what is left:
# projection, norms, head and loss); jax renders a scope inside the
# transforms around it, ``transpose(jvp(embed))``, so delimiters are / ( )
_SCOPE = re.compile(
    r"[/(](embed|attn\.[a-z_]+|attn|mlp|head_loss|mtp|optimizer"
    r"|zero\.[a-z]+|moe\.[a-z]+)(?=[/)]|$)"
)
# A kernel the compiler itself puts in place of a primitive keeps no
# name stack: its whole ``op_name`` is the kernel's name
# (``lax.ragged_dot`` → ``ragged-dot-none``, ``ragged-dot-metadata``).
# Its scope is known by that name; whether a call is forward,
# recomputed or backward is not, and its phase reads ``other``.
_RAGGED_DOT = "ragged-dot"
# host events that are the program's own spans (tracing.py call sites)
SPAN_PREFIXES = ("train.", "ckpt.", "serving.", "failover.", "brain.")
# the annotations profiled_call opens inside its profiler session
SAMPLE_SPAN = "train.sample"
_DISPATCH_SPAN = "train.sample_dispatch"
_WAIT_SPAN = "train.sample_device_wait"


@dataclass
class OpTime:
    name: str
    total_us: float
    count: int
    fraction: float = 0.0
    phase: str = ""
    scope: str = ""


@dataclass
class DeviceProfile:
    """One reduced device trace. Seconds are means over the devices;
    the tables name the first device's operations and gaps."""

    devices: int = 0
    platform: str = ""  # TPU, or CPU where the backend is the CPU
    window_s: float = 0.0
    busy_s: float = 0.0
    pallas_s: float = 0.0
    by_op: List[OpTime] = field(default_factory=list)
    by_phase: Dict[str, float] = field(default_factory=dict)  # seconds
    # seconds by innermost named scope of the step ("" = outside them)
    by_scope: Dict[str, float] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0


# ---- reading -----------------------------------------------------------------


def find_xplane(logdir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``logdir``, or None."""
    paths = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return max(paths, key=os.path.getmtime) if paths else None


def load_planes(path: str) -> List[Dict]:
    """An ``.xplane.pb`` in the neutral form ``reduce_planes`` works on::

        [{"name": str,
          "lines": [{"name": str,
                     "events": [(name, start_ns, duration_ns), ...]}]}]

    The CPU backend writes no device plane: its executed operations are
    host events that carry an ``hlo_op`` stat. Where jax's backend is
    the CPU, those, and nothing else of the host, are gathered into a
    plane ``/device:CPU:0``, so the sampling works where the CPU is the
    device. On any other backend a trace without a device plane stays
    without one: host times are never passed off as a chip's."""
    import jax
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = [
            {
                "name": line.name,
                "events": [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                ],
            }
            for line in plane.lines
        ]
        planes.append({"name": plane.name, "lines": lines})
    if jax.default_backend() == "cpu" and not any(
        DEVICE_PLANE.match(p["name"]) for p in planes
    ):
        executed = [
            (e.name, float(e.start_ns), float(e.duration_ns))
            for plane in data.planes
            for line in plane.lines
            for e in line.events
            if any(key == "hlo_op" for key, _value in e.stats)
        ]
        planes.append({
            "name": "/device:CPU:0",
            "lines": [{"name": OPS_LINE, "events": executed}],
        })
    return planes


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")


def op_names_from_hlo(hlo_text: str) -> Dict[str, str]:
    """Instruction name → ``op_name`` metadata, from a compiled module's
    text (``compiled.as_text()``). A fusion the compiler left without
    metadata takes the first ``op_name`` inside the computation it calls."""
    names: Dict[str, str] = {}
    in_computation: Dict[str, str] = {}  # computation → first op_name in it
    pending: List[Tuple[str, str]] = []  # (instruction, called computation)
    computation = ""
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            c = _COMPUTATION.match(line)
            if c:
                computation = c.group(1)
            continue
        op = _OP_NAME.search(line)
        if op:
            names[m.group(1)] = op.group(1)
            in_computation.setdefault(computation, op.group(1))
            continue
        called = _CALLS.search(line)
        if called:
            pending.append((m.group(1), called.group(1)))
    for instruction, called in pending:
        if called in in_computation:
            names[instruction] = in_computation[called]
    return names


# bytes per element of the dtypes collectives carry
_HLO_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "s8": 1, "u8": 1, "pred": 1,
}
_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def collective_stats(hlo_text: str) -> dict:
    """The collectives of a compiled module's text
    (``compiled.as_text()``): ``{"counts": {kind: n}, "bytes_by_dtype":
    {dtype: B}, "bytes_by_op": {kind: B}}``. Bytes are those of each
    instruction's RESULT, every member of a tuple result summed; a dtype
    outside ``_HLO_DTYPE_BYTES`` adds none. Counted: a line whose
    right-hand side calls ``<kind>(`` for a kind in
    ``COLLECTIVE_MARKERS`` — so an asynchronous ``<kind>-start`` /
    ``-done`` pair is not. A replicated update coming back (a
    full-gradient all-reduce) or a changed wire dtype shows here."""
    counts = dict.fromkeys(COLLECTIVE_MARKERS, 0)
    bytes_by_dtype: Dict[str, int] = {}
    bytes_by_op: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        _lhs, sep, rhs = line.partition(" = ")
        if not sep:
            continue
        for kind in COLLECTIVE_MARKERS:
            at = rhs.find(kind + "(")
            if at >= 0:
                break
        else:
            continue
        counts[kind] += 1
        for dtype, dims in _SHAPE.findall(rhs[:at]):
            if dtype not in _HLO_DTYPE_BYTES:
                continue
            size = _HLO_DTYPE_BYTES[dtype]
            for d in dims.split(","):
                if d:
                    size *= int(d)
            bytes_by_dtype[dtype] = bytes_by_dtype.get(dtype, 0) + size
            bytes_by_op[kind] = bytes_by_op.get(kind, 0) + size
    return {
        "counts": {kind: n for kind, n in counts.items() if n},
        "bytes_by_dtype": bytes_by_dtype,
        "bytes_by_op": bytes_by_op,
    }


def short_name(event_name: str) -> str:
    """``%fusion.3 = bf16[8,128]{...} fusion(...)`` → ``fusion.3``; a
    bare name is its own."""
    return event_name.partition(" = ")[0].lstrip("%")


def is_pallas(event_name: str) -> bool:
    return "tpu_custom_call" in event_name


def is_collective(event_name: str) -> bool:
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return head.lstrip("%").startswith(COLLECTIVE_MARKERS)
    # the opcode: the word in front of the first "(" after the shape
    m = re.search(r"(?:^|[\s)])([a-z][a-z0-9\-]*)\(", rest)
    return bool(m) and m.group(1).startswith(COLLECTIVE_MARKERS)


def scope_of(op_name: str) -> str:
    """The innermost named scope of the step in an ``op_name``, or ""."""
    found = _SCOPE.findall(op_name)
    if found:
        return found[-1]
    return "moe.experts" if op_name.startswith(_RAGGED_DOT) else ""


def phase_of(event_name: str, op_name: str) -> str:
    """Which part of the step an operation belongs to. jax's own
    wrappers in the name stack tell forward (``jvp(``) from backward
    (``transpose(``) from recomputation (``rematted_computation``), the
    OUTERMOST of them: a derivative that a forward rule takes itself
    (``jvp()/.../transpose(jvp(...))``, the alignment term's) runs in
    the forward. The step's scopes tell the optimizer and ZeRO's
    exchange."""
    if is_collective(event_name):
        return "exchange"
    scope = scope_of(op_name)
    if scope in ("optimizer", "zero.update"):
        return "optimizer"
    if scope.startswith("zero."):
        return "exchange"  # pack, exchange, gather: ZeRO's bookkeeping
    if "rematted_computation" in op_name:
        return "recompute"
    outermost = re.search(r"transpose\(|jvp\(", op_name)
    if outermost is None:
        return "other"
    return "backward" if outermost.group() == "transpose(" else "forward"


# ---- reducing ----------------------------------------------------------------


def _union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_times(events):
    """[(name, start, end, self_ns)] for the (name, start, end) events of
    one line, where an event inside another is its child."""
    out, stack = [], []  # stack of indexes into out
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= min(e, parent[2]) - s
        out.append([name, s, e, e - s])
        stack.append(len(out) - 1)
    return out


def _line(plane: Dict, name: str):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _host_spans(planes, prefixes) -> List[Tuple[str, float, float]]:
    spans = []
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(prefixes):
                    spans.append((name, start, start + dur))
    return spans


def _covering(spans, s, e, skip) -> str:
    """The host span covering most of [s, e]; among equals the shortest
    (the innermost)."""
    best, best_cover, best_len = "none", 0.0, 0.0
    for name, hs, he in spans:
        if name == skip:
            continue
        cover = min(e, he) - max(s, hs)
        if cover > best_cover or (
            cover == best_cover and cover > 0 and he - hs < best_len
        ):
            best, best_cover, best_len = name, cover, he - hs
    return best


def reduce_planes(
    planes: List[Dict],
    op_names: Optional[Dict[str, str]] = None,
    window_span: Optional[str] = SAMPLE_SPAN,
    span_prefixes: Tuple[str, ...] = SPAN_PREFIXES,
) -> Optional[DeviceProfile]:
    """Reduce a trace (``load_planes``'s form) to a ``DeviceProfile``;
    None when it holds no device plane with operations.

    ``op_names`` maps instruction names to ``op_name`` metadata
    (``op_names_from_hlo``); without it every operation that is not a
    collective falls under phase ``other``. The window is the host span
    named ``window_span`` when the trace has one, else first operation
    start to last operation end."""
    op_names = op_names or {}
    devices = [
        p for p in planes
        if DEVICE_PLANE.match(p["name"]) and _line(p, OPS_LINE)
    ]
    if not devices:
        return None
    host = _host_spans(planes, span_prefixes)
    windows = [(s, e) for n, s, e in host if n == window_span]
    profile = DeviceProfile(
        devices=len(devices),
        platform=DEVICE_PLANE.match(devices[0]["name"]).group(1),
    )
    for i, plane in enumerate(devices):
        ops = _line(plane, OPS_LINE)
        if windows:
            t0, t1 = windows[0]
        else:
            t0 = min(s for _n, s, _d in ops)
            t1 = max(s + d for _n, s, d in ops)
        clipped = [
            (n, max(s, t0), min(s + d, t1))
            for n, s, d in ops
            if min(s + d, t1) > max(s, t0)
        ]
        timed = _self_times(clipped)
        busy = _union((s, e) for _n, s, e, _x in timed)
        busy_ns = sum(e - s for s, e in busy)
        profile.window_s += (t1 - t0) / 1e9 / len(devices)
        profile.busy_s += busy_ns / 1e9 / len(devices)
        profile.pallas_s += (
            sum(x for n, _s, _e, x in timed if is_pallas(n))
            / 1e9 / len(devices)
        )
        if i:
            continue  # the tables name the first device
        by_op: Dict[str, OpTime] = {}
        for name, _s, _e, self_ns in timed:
            short = short_name(name)
            op_name = op_names.get(short, "")
            phase, scope = phase_of(name, op_name), scope_of(op_name)
            row = by_op.get(short)
            if row is None:
                row = by_op[short] = OpTime(
                    short, 0.0, 0, phase=phase, scope=scope
                )
            row.total_us += self_ns / 1e3
            row.count += 1
            profile.by_phase[phase] = (
                profile.by_phase.get(phase, 0.0) + self_ns / 1e9
            )
            profile.by_scope[scope] = (
                profile.by_scope.get(scope, 0.0) + self_ns / 1e9
            )
        total_us = sum(o.total_us for o in by_op.values()) or 1.0
        for row in by_op.values():
            row.fraction = row.total_us / total_us
        profile.by_op = sorted(by_op.values(), key=lambda o: -o.total_us)
        gaps: Dict[str, float] = {}
        cursor = t0
        for s, e in busy + [(t1, t1)]:
            if s > cursor:
                span = _covering(host, cursor, s, skip=window_span)
                gaps[span] = gaps.get(span, 0.0) + (s - cursor) / 1e9
            cursor = max(cursor, e)
        profile.gaps = sorted(gaps.items(), key=lambda kv: -kv[1])
    return profile


# ---- sampling ----------------------------------------------------------------


class RuntimeKernelTimer:
    """Sample-and-reduce runtime op timing around a step callable."""

    def __init__(
        self,
        interval_steps: int = 200,
        top_k: int = 15,
        logdir: Optional[str] = None,
    ):
        """``interval_steps=0`` disables the cadence: the timer only
        samples when ``force_next()`` arms it (the watchdog's triggered
        captures). Negative intervals are a config error."""
        if interval_steps < 0:
            raise ValueError("interval_steps must be >= 0")
        self.interval_steps = interval_steps
        self.top_k = top_k
        self._logdir = logdir
        self._profile: Optional[DeviceProfile] = None
        self._sampled_at: int = -1
        self._sampled_block_k: int = 1
        self._forced: bool = False
        self._sample_wall_s: float = 0.0
        # (callable, argument shapes) → instruction name → op_name
        self._op_names: Dict[Tuple, Dict[str, str]] = {}

    def should_sample(self, step: int) -> bool:
        if self._forced:
            return True
        return (
            self.interval_steps > 0 and step % self.interval_steps == 0
        )

    def force_next(self) -> None:
        """Arm a one-shot sample: the next ``profiled_call`` traces
        regardless of the cadence (anomaly-triggered captures)."""
        self._forced = True

    def _op_names_for(self, fn, args, kwargs) -> Dict[str, str]:
        """``op_name`` by instruction of the program ``fn`` runs for
        these arguments, from its compiled text; fetched once per
        program (a compile-cache hit where the cache is on, else one
        more compile) and empty for a callable that cannot be lowered."""
        import jax

        key = (
            id(fn),
            tuple(
                (getattr(x, "shape", None), str(getattr(x, "dtype", "")))
                for x in jax.tree.leaves((args, kwargs))
            ),
        )
        if key not in self._op_names:
            names: Dict[str, str] = {}
            if hasattr(fn, "lower"):
                try:
                    t0 = time.perf_counter()
                    text = fn.lower(*args, **kwargs).compile().as_text()
                    names = op_names_from_hlo(text)
                    logger.info(
                        "runtime timer: %d op_names from the compiled "
                        "step in %.1fs", len(names),
                        time.perf_counter() - t0,
                    )
                except Exception:  # noqa: BLE001
                    logger.warning(
                        "runtime timer: compiled text unavailable; "
                        "phases will read 'other'", exc_info=True,
                    )
            self._op_names[key] = names
        return self._op_names[key]

    def profiled_call(self, step: int, fn, *args, n_steps: int = 1, **kwargs):
        """Run ``fn`` — once, whatever happens to the trace; when the
        cadence hits, under a profiler session, and refresh the
        breakdown from what it wrote. A session that cannot start means
        an untraced call; a reduction that fails keeps the last
        breakdown (the state ``fn`` donated is gone either way).

        ``n_steps``: how many train steps ``fn`` executes as one device
        program (the trainer's fused ``block_k`` path). The breakdown
        then covers the WHOLE block — ``sampled_block_k`` labels it so
        consumers never mistake a K-step capture for one step's budget.
        """
        if not self.should_sample(step):
            return fn(*args, **kwargs)
        self._forced = False
        import jax

        t_sample = time.perf_counter()
        op_names = self._op_names_for(fn, args, kwargs)
        logdir = self._logdir or tempfile.mkdtemp(prefix="dlrover_prof_")
        try:
            jax.profiler.start_trace(logdir)
        except Exception:  # noqa: BLE001
            logger.warning(
                "runtime trace sampling could not start at step %d",
                step, exc_info=True,
            )
            return fn(*args, **kwargs)
        annotate = jax.profiler.TraceAnnotation
        try:
            with annotate(SAMPLE_SPAN, step=step):
                with annotate(_DISPATCH_SPAN):
                    out = fn(*args, **kwargs)
                with annotate(_WAIT_SPAN):
                    jax.block_until_ready(out)
        finally:
            try:
                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001
                logger.warning("stopping the trace failed", exc_info=True)
        try:
            path = find_xplane(logdir)
            profile = (
                reduce_planes(load_planes(path), op_names) if path else None
            )
            if profile is None:
                logger.warning(
                    "the trace of step %d holds no device plane; the "
                    "last breakdown stays", step,
                )
            else:
                # self times: equal to busy on a device's one stream
                total = sum(profile.by_phase.values()) or 1.0
                self._profile = profile
                self._sampled_at = step
                self._sampled_block_k = max(int(n_steps), 1)
                self._sample_wall_s = time.perf_counter() - t_sample
                logger.info(
                    "step %d %s profile: busy %.1f ms of %.1f ms, "
                    "%s; sample took %.2fs", step, profile.platform,
                    profile.busy_s * 1e3,
                    profile.window_s * 1e3,
                    ", ".join(
                        f"{p} {100 * profile.by_phase.get(p, 0.0) / total:.1f}%"
                        for p in PHASES
                    ),
                    self._sample_wall_s,
                )
        except Exception:  # noqa: BLE001
            logger.warning(
                "reducing the trace of step %d failed", step, exc_info=True
            )
        finally:
            if self._logdir is None:
                shutil.rmtree(logdir, ignore_errors=True)
        return out

    @property
    def profile(self) -> Optional[DeviceProfile]:
        """The last sample, whole: phases, gaps, every operation."""
        return self._profile

    @property
    def breakdown(self) -> List[OpTime]:
        """The last sample's ``top_k`` operations by self time."""
        if self._profile is None:
            return []
        ops = self._profile.by_op
        return list(ops[: self.top_k] if self.top_k else ops)

    @property
    def sampled_at(self) -> int:
        return self._sampled_at

    @property
    def sampled_block_k(self) -> int:
        """Steps covered by the current breakdown (1 = a single step)."""
        return self._sampled_block_k

    @property
    def sample_wall_s(self) -> float:
        """Wall seconds the last sample took, reduction included."""
        return self._sample_wall_s

    def summary(self) -> Dict[str, float]:
        return {o.name: o.total_us for o in self.breakdown}

    def prometheus_text(self, prefix: str = "dlrover_tpu_kernel") -> str:
        lines = [
            f"# TYPE {prefix}_time_us gauge",
        ]
        for o in self.breakdown:
            name = re.sub(r"[^a-zA-Z0-9_.]", "_", o.name)
            lines.append(
                f'{prefix}_time_us{{op="{name}"}} {o.total_us:.1f}'
            )
        lines.append(f"# sampled_at_step {self._sampled_at}")
        return "\n".join(lines) + "\n"
