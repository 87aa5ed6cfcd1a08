"""Cross-process trace spans: a flight recorder from train step to failover.

Reference: the Chrome trace-event format (``ph``/``ts``/``dur`` in µs)
that Perfetto and ``chrome://tracing`` load directly.

Design constraints this module pins down:

* **Monotonic durations, mergeable timestamps.**  Each tracer anchors
  ``time.monotonic()`` to the wall clock once at construction
  (``ts_us = (wall0 + (monotonic() - mono0)) * 1e6``), so span
  durations are immune to NTP steps while events from *different
  processes on the same machine* still land on one shared timeline.
* **Cross-process correlation.**  Every event carries the job/run/
  restart/rendezvous-round identity from the ``DLROVER_TPU_*``
  environment (injected by the agent into workers), so one merged file
  interleaves worker, agent and master spans of the same failover.
* **Zero-cost when off.**  ``get_tracer()`` returns a module-pinned
  ``NullTracer`` unless tracing was configured (explicitly or via
  ``DLROVER_TPU_TRACE_DIR``); its ``span()`` hands back a shared
  no-op span object, so a disabled hot path allocates nothing.
* **One clock with the device.**  In a process that has imported jax,
  every span of an enabled tracer is also a
  ``jax.profiler.TraceAnnotation`` of the same name: outside a profiler
  session that is a flag test, inside one the span lands on the host
  plane of the same ``.xplane.pb`` as the device's operations, so an
  idle gap on the device can be put down to what the program was doing
  (``runtime_timer.reduce_planes``).  The agent and the master never
  import jax, and this module never imports it for them.
* **A tree, not a list.**  Each span has an ``id`` and the ``parent``
  that was open on its thread when it began; ``self_seconds`` gives a
  span's duration minus the part its children cover.

Beside the spans sits a process-wide **counter table**
(``set_counter`` / ``counters``), always on, for values taken at
boundaries that happen at most once a trace of the step — so it costs
the step loop nothing. A counter is added with the metric that reads it.
The step clock (``profiler.py``) sets none: its metrics read its ticks
over a window, which a whole-process counter cannot serve. Its one
span, ``host.stall``, reaches an enabled tracer twice over: held open
by the clock's beat while a step is overdue (so it is in a profiler
session's trace) and, when the tick comes, back-dated over the whole
period with the stall's record as its arguments (``complete_span``);
the open one is then dropped (``cancel``).

Producers stream one JSON event per line into
``$DLROVER_TPU_TRACE_DIR/trace-{role}-{pid}.jsonl`` (append-only, one
file per process — no cross-process locking); ``merge_trace_dir``
zips the per-process files into a single time-sorted timeline.
"""

import glob
import io
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from dlrover_tpu.common.constants import GraftEnv
from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

_RING_CAPACITY = 4096


def _correlation_from_env() -> Dict[str, object]:
    """Identity fields stamped onto every event of this process."""
    env = os.environ
    args: Dict[str, object] = {}
    run_id = env.get(GraftEnv.RUN_ID, "")
    if run_id:
        args["run"] = run_id
    job = env.get(GraftEnv.JOB_NAME, "")
    if job:
        args["job"] = job
    for key, envname in (
        ("node", GraftEnv.NODE_ID),
        ("restart", GraftEnv.RESTART_COUNT),
        ("rdzv_round", GraftEnv.RDZV_ROUND),
    ):
        val = env.get(envname, "")
        if val:
            try:
                args[key] = int(val)
            except ValueError:
                args[key] = val
    return args


_span_ids = itertools.count(1)
# the few small arguments a span carries into the profiler's trace
_ANNOTATED_ARGS = ("step", "rid", "nbytes")


class Span:
    """One open interval; close with ``end()`` or use as a context manager."""

    __slots__ = (
        "name", "args", "id", "parent", "dur_us",
        "_tracer", "_t0_mono", "_ts_us", "_annotation", "_scoped",
    )

    def __init__(
        self, tracer: "Tracer", name: str, args: Dict,
        scoped: bool = True, step: Optional[int] = None,
    ):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.id = next(_span_ids)
        stack = tracer._open_spans()
        self.parent = stack[-1].id if stack else 0
        # ``begin()`` spans may overlap and end on another thread: they
        # have a parent but never become one
        self._scoped = scoped
        if scoped:
            stack.append(self)
        self._annotation = _annotate(name, args, step)
        self._t0_mono = time.monotonic()
        self._ts_us = tracer._now_us()
        self.dur_us = -1.0  # open

    def end(self, **extra) -> float:
        """Close the span; returns the duration in seconds."""
        if self.dur_us >= 0:  # double-end is a no-op
            return self.dur_us / 1e6
        self._close()
        if extra:
            self.args.update(extra)
        self._tracer._emit_complete(self)
        return self.dur_us / 1e6

    def cancel(self) -> None:
        """Close the span without recording it (work that raised before
        it was done: only completed work lands on the timeline)."""
        if self.dur_us < 0:
            self._close()

    def _close(self) -> None:
        self.dur_us = (time.monotonic() - self._t0_mono) * 1e6
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        if self._scoped:
            stack = self._tracer._open_spans()
            if stack and stack[-1] is self:
                stack.pop()
            elif self in stack:
                stack.remove(self)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        self.end()
        return False


def _annotate(name: str, args: Dict, step: Optional[int]):
    """The span's twin on the profiler's clock, entered; None in a
    process that has not imported jax (the agent, the master)."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return None
    if step is not None:
        annotation = profiler.StepTraceAnnotation(name, step_num=step)
    else:
        annotation = profiler.TraceAnnotation(
            name, **{k: args[k] for k in _ANNOTATED_ARGS if k in args}
        )
    annotation.__enter__()
    return annotation


class _NullSpan:
    """Shared, stateless stand-in handed out by ``NullTracer``."""

    __slots__ = ()
    name = ""
    dur_us = 0.0
    id = 0
    parent = 0

    @property
    def args(self) -> Dict:
        # fresh dict per access: writes from callers annotating a live
        # span (``sp.args["k"] = v``) are silently discarded instead of
        # accumulating on a shared class attribute
        return {}

    def end(self, **extra) -> float:
        return 0.0

    def cancel(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_SPAN = _NullSpan()


class Tracer:
    """Thread-safe span recorder with Chrome-trace export.

    Events land in a bounded ring buffer (so a long run cannot grow
    memory without bound) and — when a ``trace_dir`` is set — are also
    streamed line-by-line to this process's JSONL file, which survives
    the process being SIGKILLed mid-failover (the exact moment the
    flight recorder exists for).
    """

    enabled = True

    def __init__(
        self,
        role: str = "proc",
        trace_dir: Optional[str] = None,
        capacity: int = _RING_CAPACITY,
    ):
        self.role = role
        self.pid = os.getpid()
        self._wall0 = time.time()
        self._mono0 = time.monotonic()
        self._events: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._common = _correlation_from_env()
        self._common["role"] = role
        self._file: Optional[io.TextIOWrapper] = None
        if trace_dir:
            try:
                os.makedirs(trace_dir, exist_ok=True)
                path = os.path.join(
                    trace_dir, f"trace-{role}-{self.pid}.jsonl"
                )
                self._file = open(path, "a", buffering=1)
            except OSError as e:
                logger.warning("tracing: cannot open trace file: %s", e)

    # ---- clock ----------------------------------------------------------

    def _now_us(self) -> float:
        """Wall-anchored monotonic µs: comparable across processes,
        immune to wall-clock steps within one process."""
        return (self._wall0 + (time.monotonic() - self._mono0)) * 1e6

    def _open_spans(self) -> List[Span]:
        """This thread's stack of open scoped spans."""
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    # ---- span API -------------------------------------------------------

    def span(self, name: str, **args) -> Span:
        """Open a span; close via ``with`` or explicit ``end()``. Spans
        opened while it is open on this thread are its children."""
        return Span(self, name, args)

    def step_span(self, name: str, step: int, **args) -> Span:
        """A span around one train step's dispatch: in the profiler's
        trace it is a ``StepTraceAnnotation`` carrying the step number."""
        args["step"] = step
        return Span(self, name, args, step=step)

    def begin(self, name: str, **args) -> Span:
        """Explicit-lifetime span: may overlap others and end on another
        thread, so it takes a parent but never becomes one."""
        return Span(self, name, args, scoped=False)

    def end(self, span: Span, **extra) -> float:
        return span.end(**extra)

    def complete_span(
        self, name: str, t0_mono: float, dur_s: Optional[float] = None,
        **args,
    ) -> float:
        """Emit a complete ("X") event back-dated to a monotonic start.

        For intervals whose start was stamped before a span could be
        opened — e.g. queue wait, measured from ``Request.submit_t``
        (taken on the submitting user thread) to admission (on the
        engine loop thread) — and, with ``dur_s``, for time accumulated
        over many short pieces (a save's device→host waits over its
        leaves), laid out as one interval from ``t0_mono``. Returns the
        duration in seconds."""
        if dur_s is None:
            dur_s = max(0.0, time.monotonic() - t0_mono)
        stack = self._open_spans()
        self._record(
            {
                "name": name,
                "ph": "X",
                "ts": (self._wall0 + (t0_mono - self._mono0)) * 1e6,
                "dur": dur_s * 1e6,
                "id": next(_span_ids),
                "parent": stack[-1].id if stack else 0,
                "args": args,
            }
        )
        return dur_s

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker event."""
        self._record(
            {
                "name": name,
                "ph": "i",
                "ts": self._now_us(),
                "s": "p",
                "args": args,
            }
        )

    def counter(self, name: str, **values) -> None:
        """A Chrome counter event (stacked series in the trace viewer)."""
        self._record(
            {"name": name, "ph": "C", "ts": self._now_us(), "args": values}
        )

    # ---- emission -------------------------------------------------------

    def _emit_complete(self, span: Span) -> None:
        self._record(
            {
                "name": span.name,
                "ph": "X",
                "ts": span._ts_us,
                "dur": span.dur_us,
                "id": span.id,
                "parent": span.parent,
                "args": span.args,
            }
        )

    def _record(self, ev: Dict) -> None:
        ev["pid"] = self.pid
        ev["tid"] = threading.get_ident() & 0x7FFFFFFF
        if self._common:
            merged = dict(self._common)
            merged.update(ev.get("args") or {})
            ev["args"] = merged
        with self._lock:
            self._events.append(ev)
            if self._file is not None:
                try:
                    self._file.write(json.dumps(ev) + "\n")
                except (OSError, ValueError):
                    self._file = None  # fd gone (shutdown); keep the ring

    # ---- export ---------------------------------------------------------

    def events(self) -> List[Dict]:
        with self._lock:
            return list(self._events)

    def chrome_trace(self) -> Dict:
        """The in-memory ring as a Chrome trace-event JSON object."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def export(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                try:
                    self._file.close()
                finally:
                    self._file = None


class NullTracer:
    """Disabled tracer: every call is a pinned no-op."""

    enabled = False
    role = ""

    def span(self, name: str, **args) -> _NullSpan:
        return _NULL_SPAN

    begin = span

    def step_span(self, name: str, step: int, **args) -> _NullSpan:
        return _NULL_SPAN

    def end(self, span, **extra) -> float:
        return 0.0

    def complete_span(
        self, name: str, t0_mono: float, dur_s: Optional[float] = None,
        **args,
    ) -> float:
        return 0.0

    def instant(self, name: str, **args) -> None:
        pass

    def counter(self, name: str, **values) -> None:
        pass

    def events(self) -> List[Dict]:
        return []

    def chrome_trace(self) -> Dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def close(self) -> None:
        pass


_NULL_TRACER = NullTracer()
_tracer: Optional[Tracer] = None
_tracer_lock = threading.Lock()


def configure_tracer(
    role: str, trace_dir: Optional[str] = None, force: bool = False
) -> Tracer:
    """Install the process tracer (idempotent unless ``force``).

    ``trace_dir=None`` falls back to ``$DLROVER_TPU_TRACE_DIR``; with
    neither set the tracer still records to its in-memory ring (useful
    in tests and for on-demand export).
    """
    global _tracer
    with _tracer_lock:
        if _tracer is not None and not force:
            return _tracer
        if _tracer is not None:
            _tracer.close()
        trace_dir = trace_dir or os.getenv(GraftEnv.TRACE_DIR) or None
        _tracer = Tracer(role=role, trace_dir=trace_dir)
        return _tracer


def get_tracer():
    """The process tracer, or the pinned ``NullTracer`` when tracing is
    off.  Auto-enables when ``DLROVER_TPU_TRACE_DIR`` is set (role from
    ``DLROVER_TPU_TRACE_ROLE``), so workers inherit tracing from the
    agent's environment injection without any code-side wiring."""
    if _tracer is not None:
        return _tracer
    trace_dir = os.getenv(GraftEnv.TRACE_DIR)
    if trace_dir:
        return configure_tracer(
            os.getenv(GraftEnv.TRACE_ROLE, "proc"), trace_dir
        )
    return _NULL_TRACER


def reset_tracer() -> None:
    """Drop the installed tracer (tests)."""
    global _tracer
    with _tracer_lock:
        if _tracer is not None:
            _tracer.close()
        _tracer = None


# ---- merging --------------------------------------------------------------


def merge_trace_dir(
    trace_dir: str, out_path: Optional[str] = None
) -> List[Dict]:
    """Merge every per-process ``trace-*.jsonl`` under ``trace_dir``
    into one time-sorted event list; optionally write it back out as a
    single JSONL timeline (one Chrome trace event per line).

    Tolerates truncated trailing lines — processes are routinely
    SIGKILLed mid-write during the drills this records.
    """
    events: List[Dict] = []
    for path in sorted(glob.glob(os.path.join(trace_dir, "trace-*.jsonl"))):
        try:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        events.append(json.loads(line))
                    except json.JSONDecodeError:
                        continue  # torn tail write
        except OSError:
            continue
    events.sort(key=lambda e: e.get("ts", 0.0))
    if out_path:
        with open(out_path, "w") as f:
            for ev in events:
                f.write(json.dumps(ev) + "\n")
    return events


def span_intervals(
    events: List[Dict], prefix: str = ""
) -> List[Dict]:
    """Complete-phase ("X") spans as ``{name, start_s, dur_s, role,
    id, parent, args}`` with seconds-since-epoch starts — the shape the
    drill's phase-attribution code consumes."""
    out = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = ev.get("name", "")
        if prefix and not name.startswith(prefix):
            continue
        args = ev.get("args") or {}
        out.append(
            {
                "name": name,
                "start_s": ev.get("ts", 0.0) / 1e6,
                "dur_s": ev.get("dur", 0.0) / 1e6,
                "role": args.get("role", ""),
                "id": ev.get("id", 0),
                "parent": ev.get("parent", 0),
                "args": args,
            }
        )
    return out


def self_seconds(intervals: List[Dict]) -> Dict[int, float]:
    """Self time of every span of one process, by span id: its duration
    minus the part of it that its child spans cover (children that
    overlap each other, on other threads, are covered once)."""
    children: Dict[int, List] = {}
    for iv in intervals:
        children.setdefault(iv["parent"], []).append(iv)
    out: Dict[int, float] = {}
    for iv in intervals:
        lo, hi = iv["start_s"], iv["start_s"] + iv["dur_s"]
        covered, reach = 0.0, lo
        for c in sorted(
            children.get(iv["id"], ()), key=lambda c: c["start_s"]
        ):
            s = max(c["start_s"], reach)
            e = min(c["start_s"] + c["dur_s"], hi)
            if e > s:
                covered += e - s
                reach = e
        out[iv["id"]] = iv["dur_s"] - covered
    return out


# ---- counters ---------------------------------------------------------------

_counters: Dict[str, float] = {}
_counters_lock = threading.Lock()


def set_counter(name: str, value: float) -> None:
    """Set a counter to a value (idempotent: what a step moves, what a
    plan holds — setting it twice must not double it)."""
    with _counters_lock:
        _counters[name] = value


def counters() -> Dict[str, float]:
    """A copy of this process's counter table."""
    with _counters_lock:
        return dict(_counters)
