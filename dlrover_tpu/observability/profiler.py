"""The process's step clock: what the host did, step by step.

``step_clock()`` hands back the one ``StepClock`` of the process. Nothing
switches it on: the program ticks it where every loop passes once a step
on the host, the placement of the batch on the device
(``train/data_utils.py``), so a loop the program does not own — the
benchmark's, an example's — is on the clock as the Trainer's is. What
the device did inside a step is the runtime timer's job
(``runtime_timer.py``); counts live in ``tracing.py``'s counter table.

**The tick.** One a batch, taken at the entry of the placement call on
``time.perf_counter()``: its time, the interval since the tick before,
the seconds inside the placement call, the largest beat lateness since
the tick before and the *period* it closed — the step period as the
program sees it —, in a ring of 4,096. The period is a loop
iteration's: a loop that places one batch a step closes one with every
tick, and there it is the interval. A loop that places K batches in a
burst and then runs one block of K steps (the Trainer's fused loop)
closes one a block: a tick that comes within an eighth of the last
period (of the stall threshold, where the last was a stall; a quarter
of a second at most, so a compile takes no steps with it) after the
tick that closed it belongs to that tick's burst, closes nothing and
has period 0.0, so the period runs from burst to burst and is the
block's. (A run's first burst has no period before it to be held
against, and each of its ticks closes one; the first block then closes
a period over 8 × as long as all of those together, and whenever one
does, what was learnt is forgotten: it was a burst. A stall of over
8 × the time the clock has run goes unrecorded the same way: nothing
learnt bore on it.) A tick costs a clock read, an append and two comparisons, one
with the burst's reach and one with the running median — the median of
the last 64 periods, taken again while those are being learnt, when a
period leaves [median / 1.5, 1.5 × median] and every 64th. The clock
follows the thread that ticks.

**The beat.** The first tick starts one daemon thread that wakes every
20 ms and keeps, in rings of 20 s: how late it woke; the ticking
thread's top Python frame (file, function, line: the *site*) and CPU
time; the process's CPU time; and every 250 ms the cumulative readings
of ``host_readings`` (the thread's run-queue seconds, the machine's
steal and iowait, the cgroup's throttled seconds, major faults: what a
record reports, and no more), each left out where its file is absent. ``gc.callbacks`` keeps every collection's start, pause and
generation; the compile recorder (``common/compile_cache.py``) keeps the
time and name of the last 256 ``jax.monitoring`` events. While the
period in progress exceeds the stall threshold the beat takes every
thread's Python stack once (eight frames each) and holds a
``host.stall`` span open until the next tick: under a profiler session
it lies on the host plane of the device's own trace.

**The stall.** With 8 periods learnt, one over 3 × the median AND over
the median + 0.25 s is a stall (a compile, the check between a
warm-up and a window, a profiler's start before a period is learnt are
not; a profiler's start after that is, and its site says so). The tick
that ends it gathers the rings over the period and names ONE cause,
``excess`` being the period minus the median and *half* half of it:

    ================  ==============================================  ================
    beats late        and                                             cause
    for >= half
    ================  ==============================================  ================
    yes               collections' pauses >= half                     ``gc``
    yes               else the process's CPU time while the beats     ``gil_held``
                      were late >= half (a thread ran and kept the
                      interpreter lock: ``thread_cpu_s`` says
                      whether the loop's own, the stacks which)
    yes               else (steal, throttled and run-queue seconds    ``process_frozen``
                      are beside it: not scheduled, a throttled
                      cgroup, a stopped process or VM; the site
                      of these three: where the beats that ran
                      saw the thread)
    no                the ticking thread's CPU time >= half           ``main_busy``
                      (Python on the loop's thread: a retrace)
    no                else its run-queue wait >= half                 ``main_runnable``
    no                else: in a call that released the lock (the     ``blocked``
                      runtime, the device, a transfer), at the site
                      that held most of the beats
    ================  ==============================================  ================

The record — ``{"event": "host.stall", "cause", "site", "site_share",
"t", "interval_s", "median_s", "excess_s", "place_s", "beats",
"beat_late_s", "beat_late_max_s", "late_cpu_s", "gc_s", "gc_gen",
"process_cpu_rate" (the process's CPU seconds a second over the ring
before the interval: what ``late_cpu_s`` is to be held against),
"thread_cpu_s", "runqueue_s", "steal_s", "iowait_s", "throttled_s",
"major_faults", "since_compile_s", "events", "stacks", "pid"}``, a
field left out where nothing read it — is kept (the last 64), logged
once as one line of JSON at WARNING, emitted as a back-dated
``host.stall`` span over the interval with the record as its arguments
(a ``NullTracer`` gets nothing). The ``Watchdog`` sees a slow step
before the loop places the next batch, so before the tick that closes
the stall: its ``step_time_regression`` asks ``overdue()``, which
gathers the period in progress up to now where that is a stall's
already, and names cause, excess and site in its ``detail`` — this
step's stall or none, never an older one. The record itself follows
with the tick.

**What reads it.** ``StepClock.window(t0, t1)`` over the ticks whose
interval lies inside (and the unclosed tail up to ``t1`` where that is
already a stall's: a window's last step has no tick behind it): the
benchmark's four host metrics are calls of it, and ``stalls`` is what an
operator reads. The clock sets no counter: ``tracing``'s table is the
whole process's and serves no window. The Trainer's measured step
seconds (``start`` / ``stop`` / ``record`` / ``last_s``: a fused block
of K steps counts K there) live on the same object.
"""

import collections
import gc
import json
import os
import statistics
import sys
import threading
import time
import traceback
import weakref
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.observability import tracing

logger = get_logger(__name__)

BEAT_S = 0.020
SLOW_S = 0.250
RING_S = 20.0
TICKS_KEPT = 4096
STALLS_KEPT = 64
MEDIAN_OVER = 64
LEARNT = 8  # periods before a stall can be told
STALL_FACTOR = 3.0
STALL_MARGIN_S = 0.25
RELEARN_FACTOR = 1.5
# a tick this share of the last period behind the tick that closed it
# is one more batch of that tick's burst
BURST_SHARE = 1 / 8
BURST_MAX_S = 0.25
STACK_FRAMES = 8
# a beat later than this was kept from running, not merely rescheduled
LATE_S = BEAT_S / 2

CAUSES = (
    "gc", "gil_held", "process_frozen",
    "main_busy", "main_runnable", "blocked",
)


class Tick(NamedTuple):
    t: float  # entry of the placement call, perf_counter
    interval: float  # since the tick before; 0.0 on the first
    place_s: float
    beat_late: float  # the largest since the tick before
    # the loop iteration this tick closed: the interval, where every
    # step places one batch; 0.0 on the first and inside a burst
    period: float
    excess: float  # period - median where the period is a stall, else 0.0


class Beat(NamedTuple):
    t: float
    late: float
    site: Optional[Tuple[str, str, int]]
    thread_cpu: Optional[float]
    process_cpu: float


# ---- the cheap cumulative readings -----------------------------------------

PROC = "/proc"
CGROUP = "/sys/fs/cgroup"
_USER_HZ = os.sysconf("SC_CLK_TCK")


def _read(path: str) -> Optional[str]:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def _cgroup_cpu_stat() -> Optional[str]:
    """The ``cpu.stat`` of this process's cgroup: v2, else v1's cpu
    controller."""
    own = _read(os.path.join(PROC, "self", "cgroup")) or ""
    for line in own.splitlines():
        _, controllers, path = (line.split(":", 2) + ["", ""])[:3]
        if controllers == "":
            text = _read(os.path.join(CGROUP, path.lstrip("/"), "cpu.stat"))
        elif "cpu" in controllers.split(","):
            text = _read(
                os.path.join(CGROUP, controllers, path.lstrip("/"), "cpu.stat")
            )
        else:
            continue
        if text:
            return text
    return None


def host_readings(native_tid: int = 0) -> Dict[str, float]:
    """The cumulative seconds and counts a stall's record reports, which
    tell a frozen process from a waiting one; a field whose file is
    absent or unreadable is left out, and nothing raises."""
    out: Dict[str, float] = {}
    try:
        text = native_tid and _read(
            os.path.join(PROC, "self", "task", str(native_tid), "schedstat")
        )
        if text:
            out["thread_runqueue_s"] = int(text.split()[1]) / 1e9
        text = _read(os.path.join(PROC, "stat"))
        if text:
            cpu = text.split("\n", 1)[0].split()
            if cpu[0] == "cpu" and len(cpu) > 8:
                out["iowait_s"] = int(cpu[5]) / _USER_HZ
                out["steal_s"] = int(cpu[8]) / _USER_HZ
        text = _cgroup_cpu_stat()
        if text:
            stat = dict(
                line.split()[:2] for line in text.splitlines() if line.strip()
            )
            if "throttled_usec" in stat:
                out["throttled_s"] = int(stat["throttled_usec"]) / 1e6
            elif "throttled_time" in stat:
                out["throttled_s"] = int(stat["throttled_time"]) / 1e9
        text = _read(os.path.join(PROC, "vmstat"))
        if text:
            for line in text.splitlines():
                key, _, value = line.partition(" ")
                if key == "pgmajfault":
                    out["major_faults"] = int(value)
                    break
    except (ValueError, IndexError):
        pass  # a format this kernel writes otherwise: what was read stays
    return out


# ---- the one rule ----------------------------------------------------------


def classify(
    excess: float, late_s: float, gc_s: float, late_cpu_s: float,
    thread_cpu_s: float, runqueue_s: float,
) -> str:
    """The cause of a stall of ``excess`` seconds: the table in this
    module's docstring, and nothing else."""
    half = excess / 2
    if late_s >= half:
        if gc_s >= half:
            return "gc"
        return "gil_held" if late_cpu_s >= half else "process_frozen"
    if thread_cpu_s >= half:
        return "main_busy"
    return "main_runnable" if runqueue_s >= half else "blocked"


def _site_text(site) -> str:
    return f"{site[0]}:{site[2]} in {site[1]}" if site else ""


def _first_leaf(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    return leaves[0] if leaves else None


class StepClock:
    """See the module's docstring; use ``step_clock()``. ``clock`` and
    ``beat=False`` are for tests: an injected clock, and the tick and
    ``beat()`` called directly so that nothing sleeps."""

    def __init__(
        self,
        clock: Callable[[], float] = time.perf_counter,
        beat: bool = True,
        compiles=None,
        readings: Callable[[int], Dict[str, float]] = host_readings,
    ):
        self._clock = clock
        self._beat_on = beat
        self._readings = readings
        if compiles is None:
            from dlrover_tpu.common.compile_cache import watch_compiles

            compiles = watch_compiles()
        self._compiles = compiles
        # the tracer stamps on time.monotonic(): the offset, taken once
        self._mono_offset = time.monotonic() - time.perf_counter()
        self._pid = os.getpid()
        self._lock = threading.Lock()

        self.ticks: collections.deque = collections.deque(maxlen=TICKS_KEPT)
        self.stalls: collections.deque = collections.deque(maxlen=STALLS_KEPT)
        self.beats: collections.deque = collections.deque(
            maxlen=int(RING_S / BEAT_S)
        )
        self.slow: collections.deque = collections.deque(
            maxlen=int(RING_S / SLOW_S)
        )
        # (start, pause, generation) of every collection
        self.collections: collections.deque = collections.deque(maxlen=1024)

        self._recent: collections.deque = collections.deque(maxlen=MEDIAN_OVER)
        self.learnt = 0  # periods
        self.median_s = 0.0
        self._stall_after = float("inf")
        self._learnt_s = 0.0  # all the periods there were, together

        self._t_tick: Optional[float] = None
        self._t_open = 0.0  # the tick that closed the last period
        self._burst_s = 0.0  # how far behind it a tick is of its burst
        self._tid = 0  # threading.get_ident() of the thread that ticks
        self._native_tid = 0
        self._cpu_clock: Optional[int] = None
        self._placed = None  # weakref to the first leaf of the last placed batch
        self._late_since_tick = 0.0

        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._beat_due = 0.0
        self._t_slow = float("-inf")
        self._gc_t0: Optional[float] = None
        self._open_span = None
        self._open_stacks: Optional[Dict[str, List[str]]] = None

        # the Trainer's measured step seconds
        self.steps = 0
        self.last_s = 0.0
        self._t_start: Optional[float] = None

    # ---- the loop's own measured seconds (the Trainer) ------------------

    def start(self) -> None:
        self._t_start = time.perf_counter()

    def stop(self, outputs=None) -> None:
        """Close the interval ``start()`` opened, after the outputs are
        ready: the device queue hides dispatch, so a step is over when
        its result is."""
        if outputs is not None:
            jax.block_until_ready(outputs)
        if self._t_start is None:
            return
        self.record(time.perf_counter() - self._t_start)
        self._t_start = None

    def record(self, dt: float, n_steps: int = 1) -> None:
        """One measured duration covering ``n_steps`` steps: a fused
        block reports once with ``n_steps=K`` and ``last_s`` keeps its
        per-step meaning."""
        n = max(int(n_steps), 1)
        self.last_s = dt / n
        self.steps += n

    # ---- the tick --------------------------------------------------------

    def placed_before(self, batch) -> bool:
        """Whether ``batch`` is what the last tick placed (it came out of
        ``form_global_batch`` and now passes ``prefetch_to_device``)."""
        ref = self._placed
        return ref is not None and ref() is _first_leaf(batch)

    def tick(self, entered: float, left: float, placed=None) -> None:
        """One batch placed: ``entered`` and ``left`` are the entry and
        the exit of the placement call on the clock, ``placed`` its
        result."""
        ident = threading.get_ident()
        closing = None
        with self._lock:
            prev, self._t_tick = self._t_tick, entered
            if ident != self._tid:
                self._follow(ident)
            late, self._late_since_tick = self._late_since_tick, 0.0
            span, self._open_span = self._open_span, None
            stacks, self._open_stacks = self._open_stacks, None
            place_s = left - entered
            interval = period = excess = 0.0
            if prev is None:
                self._t_open = entered
            else:
                interval = entered - prev
            # inside the burst's reach a tick is one more batch of it
            if prev is not None and entered - self._t_open >= self._burst_s:
                period = entered - self._t_open
                if BURST_SHARE * period > self._learnt_s:
                    # all the clock has seen lies within this period's
                    # burst and was one, a fused loop's first K
                    # batches: not what a period is learnt from
                    self._recent.clear()
                    self.learnt = 0
                    self._stall_after = float("inf")
                self._learnt_s += period
                median = self.median_s
                if period > self._stall_after:
                    excess = period - median
                    closing = (self._t_open, median)
                self._recent.append(period)
                self.learnt += 1
                if (
                    self.learnt <= MEDIAN_OVER
                    or self.learnt % MEDIAN_OVER == 0
                    or not median / RELEARN_FACTOR <= period
                    <= median * RELEARN_FACTOR
                ):
                    self._relearn()
                self._t_open = entered
                self._burst_s = min(
                    BURST_SHARE * min(period, self._stall_after), BURST_MAX_S
                )
            self.ticks.append(
                Tick(entered, interval, place_s, late, period, excess)
            )
        if placed is not None:
            leaf = _first_leaf(placed)
            try:
                self._placed = weakref.ref(leaf)
            except TypeError:
                self._placed = None
        if span is not None:
            span.cancel()  # the record's span covers the whole period
        if closing is not None:
            try:
                self._close_stall(
                    closing[0], entered, closing[1], place_s, stacks
                )
            except Exception:  # the recorder never takes the program down
                logger.warning("step clock: stall record failed", exc_info=True)
        if self._thread is None and self._beat_on:
            self._start_beat()

    def _follow(self, ident: int) -> None:
        """The clock follows the thread that ticks (called on it)."""
        self._tid = ident
        self._native_tid = threading.get_native_id()
        self._cpu_clock = None
        if self._beat_on and hasattr(time, "pthread_getcpuclockid"):
            self._cpu_clock = time.pthread_getcpuclockid(ident)

    def _relearn(self) -> None:
        self.median_s = sorted(self._recent)[len(self._recent) // 2]
        if self.learnt >= LEARNT:
            self._stall_after = max(
                STALL_FACTOR * self.median_s,
                self.median_s + STALL_MARGIN_S,
            )

    # ---- the beat --------------------------------------------------------

    def _start_beat(self) -> None:
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(
            target=self._run, name="step-clock-beat", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        clock = self._clock
        while not self._stop.is_set():
            due = self._beat_due = clock() + BEAT_S
            time.sleep(BEAT_S)
            try:
                self.beat(clock(), due)
            except Exception:  # the recorder never takes the program down
                logger.warning("step clock: beat failed", exc_info=True)
                time.sleep(1.0)

    def beat(self, now: float, due: float) -> None:
        """One wake of the beat thread, due at ``due`` and awake at
        ``now``."""
        late = max(0.0, now - due)
        frame = sys._current_frames().get(self._tid)
        site = thread_cpu = None
        if frame is not None:
            code = frame.f_code
            site = (code.co_filename, code.co_name, frame.f_lineno)
            del frame
            if self._cpu_clock is not None:
                try:
                    thread_cpu = time.clock_gettime(self._cpu_clock)
                except OSError:
                    pass  # the thread has gone
        beat = Beat(now, late, site, thread_cpu, time.process_time())
        slow = None
        if now - self._t_slow >= SLOW_S:
            self._t_slow = now
            slow = dict(self._readings(self._native_tid), t=now)
        with self._lock:
            self.beats.append(beat)
            if slow is not None:
                self.slow.append(slow)
            if late > self._late_since_tick:
                self._late_since_tick = late
            stalled = (
                self._open_stacks is None
                and self._t_tick is not None
                and now - self._t_open > self._stall_after
            )
            if stalled:
                self._open_stacks = _stacks()
                self._open_span = tracing.get_tracer().begin(
                    "host.stall", since_tick_s=now - self._t_open
                )

    def _on_gc(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._gc_t0 = self._clock()
        elif self._gc_t0 is not None:
            pause = self._clock() - self._gc_t0
            self.collections.append((self._gc_t0, pause, info["generation"]))

    def close(self) -> None:
        """Stop the beat (tests; a process just exits)."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if self._open_span is not None:
            self._open_span.cancel()
            self._open_span = None

    # ---- the stall -------------------------------------------------------

    def _delta(self, samples: List[Dict], key: str, t0: float) -> Optional[float]:
        """What a cumulative reading advanced by over the interval: its
        newest sample less the newest at or before ``t0`` (the oldest,
        where none is that old)."""
        have = [s for s in samples if key in s]
        if len(have) < 2:
            return None
        before = [s for s in have if s["t"] <= t0]
        first = before[-1] if before else have[0]
        return have[-1][key] - first[key]

    def _gather(
        self, t0: float, t1: float, median: float, stacks
    ) -> Dict:
        """The record of the period from ``t0`` to ``t1``, a stall's:
        the rings over it, and the one cause."""
        interval = t1 - t0
        excess = interval - median
        with self._lock:
            beats, slow = list(self.beats), list(self.slow)
        # one call, in which no collection's callback can run
        collected = list(self.collections)
        inside = [b for b in beats if t0 < b.t <= t1]
        late_s = sum(b.late for b in inside)
        late_cpu_s = 0.0
        for before, b in zip(beats, beats[1:]):
            if t0 < b.t <= t1 and b.late >= LATE_S:
                late_cpu_s += b.process_cpu - before.process_cpu
        # what the process's threads burn when nothing is wrong, CPU
        # seconds a second: a frozen process burns none of it while the
        # beats are late, one whose lock a waiting thread holds goes on
        before = [b for b in beats if b.t <= t0]
        cpu_rate = None
        if len(before) > 1 and before[-1].t > before[0].t:
            cpu_rate = (before[-1].process_cpu - before[0].process_cpu) / (
                before[-1].t - before[0].t
            )
        slow = [s for s in slow if s["t"] <= t1]
        thread_cpu = [b for b in beats if b.thread_cpu is not None and b.t <= t1]
        if self._thread is not None:
            # the beat may not have woken since: what it is late by now
            # (unless the wake it was due for is in the ring already),
            # and readings of this moment
            pending = t1 - self._beat_due
            if pending >= LATE_S and not (
                beats and beats[-1].t >= self._beat_due
            ):
                late_s += min(pending, interval)
                if beats:
                    late_cpu_s += time.process_time() - beats[-1].process_cpu
            slow.append(dict(self._readings(self._native_tid), t=t1))
            if self._cpu_clock is not None:
                thread_cpu.append(Beat(
                    t1, 0.0, None, time.clock_gettime(self._cpu_clock), 0.0
                ))
        thread_cpu_s = self._delta(
            [{"t": b.t, "cpu": b.thread_cpu} for b in thread_cpu], "cpu", t0
        )
        gcs = [c for c in collected if t0 <= c[0] <= t1]
        gc_s = sum(c[1] for c in gcs)
        runqueue_s = self._delta(slow, "thread_runqueue_s", t0)
        cause = classify(
            excess, late_s, gc_s, late_cpu_s, thread_cpu_s or 0.0,
            runqueue_s or 0.0,
        )
        sites = collections.Counter(b.site for b in inside if b.site)
        site, held = sites.most_common(1)[0] if sites else (None, 0)
        record = {
            "event": "host.stall",
            "cause": cause,
            "site": _site_text(site),
            "site_share": round(held / len(inside), 3) if inside else 0.0,
            "t": t1,
            "interval_s": interval,
            "median_s": median,
            "excess_s": excess,
            "beats": len(inside),
            "beat_late_s": late_s,
            "beat_late_max_s": max((b.late for b in inside), default=0.0),
            "late_cpu_s": late_cpu_s,
            "gc_s": gc_s,
        }
        if gcs:
            record["gc_gen"] = max(c[2] for c in gcs)
        for name, value in (
            ("process_cpu_rate", cpu_rate),
            ("thread_cpu_s", thread_cpu_s),
            ("runqueue_s", runqueue_s),
            ("steal_s", self._delta(slow, "steal_s", t0)),
            ("iowait_s", self._delta(slow, "iowait_s", t0)),
            ("throttled_s", self._delta(slow, "throttled_s", t0)),
            ("major_faults", self._delta(slow, "major_faults", t0)),
        ):
            if value is not None:
                record[name] = value
        compiled = getattr(self._compiles, "last_backend_end", None)
        if compiled is not None:
            record["since_compile_s"] = t1 - compiled
        events = getattr(self._compiles, "events", ())
        record["events"] = list(dict.fromkeys(
            name for t, name in list(events) if t0 <= t <= t1
        ))[:16]
        record["stacks"] = stacks or {}
        record["pid"] = self._pid
        return record

    def _close_stall(
        self, t0: float, t1: float, median: float, place_s: float, stacks
    ) -> None:
        """The tick at ``t1`` ended a stalled period begun at ``t0``:
        gather, keep, log, emit."""
        record = self._gather(t0, t1, median, stacks)
        record["place_s"] = place_s
        self.stalls.append(record)
        logger.warning("%s", json.dumps(record))
        tracing.get_tracer().complete_span(
            "host.stall", t0 + self._mono_offset, dur_s=t1 - t0,
            **{k: v for k, v in record.items() if k != "event"},
        )

    def overdue(self) -> Optional[Dict]:
        """The period in progress, gathered up to now and marked
        ``open``, where it is a stall's already; else None. It is not
        kept, logged or emitted: the tick that ends it does that."""
        now = self._clock()
        with self._lock:
            t0, median, stacks = self._t_open, self.median_s, self._open_stacks
            if self._t_tick is None or now - t0 <= self._stall_after:
                return None
        return dict(self._gather(t0, now, median, stacks), open=True)

    # ---- what reads it ---------------------------------------------------

    def window(self, t0: float, t1: float) -> Optional[Dict[str, float]]:
        """Over the ticks whose interval lies inside [``t0``, ``t1``]
        (``perf_counter``): the median period and the stalls' count and
        summed excess, of the periods that lie inside; the largest beat
        lateness and the median placement time; None where no period
        lies inside. A window's last step has no tick behind it (the
        loop stops before it places another batch): where the time from
        the last period's end to ``t1`` is already a stall's, it counts
        as one."""
        with self._lock:
            ticks = list(self.ticks)
            stall_after, median = self._stall_after, self.median_s
        inside = [
            k for k in ticks
            if k.interval > 0 and k.t - k.interval >= t0 and k.t <= t1
        ]
        closed = [k for k in inside if k.period > 0 and k.t - k.period >= t0]
        if not closed:
            return None
        tail = t1 - closed[-1].t
        tail_excess = tail - median if tail > stall_after else 0.0
        return {
            "ticks": len(inside),
            "period_s": statistics.median(k.period for k in closed),
            "stalls": sum(1 for k in closed if k.excess > 0)
            + (tail_excess > 0),
            "stall_s": sum(k.excess for k in closed) + tail_excess,
            "beat_late_max_s": max(k.beat_late for k in inside),
            "place_s": statistics.median(k.place_s for k in inside),
        }


def _stacks() -> Dict[str, List[str]]:
    """Every other thread's Python stack, innermost frame first, eight
    frames each (the caller is the beat: its own says nothing)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    me = threading.get_ident()
    for ident, frame in sys._current_frames().items():
        if ident == me:
            continue
        # no source lines: looking them up reads files
        frames = traceback.StackSummary.extract(
            traceback.walk_stack(frame), limit=STACK_FRAMES,
            lookup_lines=False,
        )
        out[names.get(ident, str(ident))] = [
            f"{f.filename}:{f.lineno} in {f.name}" for f in frames
        ]
    return out


_clock: Optional[StepClock] = None
_clock_lock = threading.Lock()


def step_clock() -> StepClock:
    """This process's one step clock (a forked child gets its own)."""
    global _clock
    clock = _clock
    if clock is None or clock._pid != os.getpid():
        with _clock_lock:
            if _clock is None or _clock._pid != os.getpid():
                _clock = StepClock()
            clock = _clock
    return clock


def overdue_stall() -> Optional[Dict]:
    """``overdue()`` of this process's clock, if it has one; never makes
    a clock."""
    try:
        return _clock.overdue() if _clock is not None else None
    except Exception:  # the recorder never takes the program down
        logger.warning("step clock: overdue failed", exc_info=True)
        return None


def reset_step_clock() -> None:
    """Stop and drop the process's clock (tests)."""
    global _clock
    with _clock_lock:
        if _clock is not None and _clock._pid == os.getpid():
            _clock.close()
        _clock = None
