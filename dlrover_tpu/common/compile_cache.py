"""Where the persistent XLA compile cache lives: one resolver for every
process that compiles.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set — jax reads it itself,
and nothing in this package sets another directory over it. Unset, the
cache is a fixed directory inside the checkout (git-ignored): the path is
part of what makes a cache persistent, so it never depends on the temp
dir, a uid, a pid or the time. A worker restarted after a crash, a
serving replica coming back and the next phase of the chip smoke all
find what the process before them compiled.

What a started worker pays before its first step is counted here too:
``watch_compiles()`` subscribes once a process to jax's own compile
events and keeps seconds by phase (tracing Python to a jaxpr, lowering
it to MLIR, the backend: compiling on a miss, fetching on a hit) and by
program (the train step, the state's initialisation, everything else),
each second once. The sums go into ``tracing``'s counter table
(``compile.*``, ``setup.before_build_s``); the trainer lays the step's
own onto the failover timeline (``train.compile``).

This module stays importable without touching jax, so the launcher's
agent (which must not hold the chip) can resolve the directory for the
workers it spawns; ``watch_compiles()`` and ``enable_compile_cache()``
import it, and only a process that compiles calls them.
"""

import contextlib
import os
import threading
import time
from collections import deque

ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_compile_cache")


def compile_cache_dir(configured: str = "") -> str:
    """The directory to cache in: the environment's, else ``configured``
    (the launcher's ``--compile-cache-dir``), else the fixed in-checkout
    path."""
    return os.environ.get(ENV) or configured or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Point this process's jax at the resolved directory and return it.
    With the environment variable set jax already has it; nothing is
    overridden."""
    watch_compiles()
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        import jax

        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---- the compile recorder ----------------------------------------------------

STEP, INIT_STATE, OTHER = "step", "init_state", "other"
# jax's three compile-duration events (jax/_src/dispatch.py), in the order
# a program passes them
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
# counter -> the sums it holds (spelled once: a Keye step fires ten
# thousand events)
_STEP_COUNTERS = tuple(
    (f"compile.step.{phase}", (STEP, phase)) for phase in _PHASES.values()
)
_CLASS_COUNTERS = tuple(
    (f"compile.{cls}.s", tuple((cls, phase) for phase in _PHASES.values()))
    for cls in (INIT_STATE, OTHER)
)
# fired inside the backend interval, without a name (jax/_src/compiler.py):
# they belong to the backend event that closes after them on their thread
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"


def _process_age_s() -> float:
    """Seconds since the operating system started this process, both
    ends on the kernel's boot clock (``/proc``, 10 ms ticks; psutil's
    ``create_time`` goes through a boot time in whole seconds)."""
    try:
        with open("/proc/self/stat") as f:
            # fields after the parenthesised command: the 22nd is the 20th
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            return float(f.read().split()[0]) - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        import psutil

        return time.time() - psutil.Process().create_time()


class CompileRecorder:
    """Seconds this process spent making executables, by program class
    and phase, each second once: jax reports an event when it CLOSES, the
    inner ones first (every jitted function a traced function calls is
    traced inside it), so an event takes back what closed inside its own
    interval on its thread. Use ``watch_compiles()``; one a process."""

    def __init__(self, set_counter):
        self._set_counter = set_counter
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._step_names = set()
        self.seconds = {
            (cls, phase): 0.0
            for cls in (STEP, INIT_STATE, OTHER)
            for phase in _PHASES.values()
        }
        self.step_fetch_s = 0.0
        self.cache_misses = 0
        # the step programs made so far, and the newest one's account
        # ({start, trace_s, lower_s, backend_s, cache_hit}; ``start`` on
        # time.time()): what the trainer lays onto its timeline
        self.step_programs = 0
        self.last_step = None
        self.before_build_s = None
        # for the step clock (``observability/profiler.py``): the time
        # (``perf_counter``) and name of the newest events of any kind,
        # and when the newest backend interval closed
        self.events = deque(maxlen=256)
        self.last_backend_end = None
        # what the recorder itself costs: listener calls that found one
        # of its events, and the seconds they took
        self.calls = 0
        self.listener_s = 0.0

    # ---- what the program tells it -------------------------------------

    def step_program(self, fun_name: str) -> None:
        """``fun_name`` is a function the step builder jits: jax names
        its trace event ``f`` and the other two ``jit(f)``."""
        self._step_names.update((fun_name, f"jit({fun_name})"))

    @contextlib.contextmanager
    def within(self, cls: str):
        """Everything this thread compiles inside the block is ``cls``'s."""
        state = self._thread()
        before, state.label = state.label, cls
        try:
            yield
        finally:
            state.label = before

    def first_build(self) -> None:
        """Called where the step builder is constructed: the first call
        closes ``setup.before_build_s`` (interpreter, imports, the
        runtime's start, the mesh); every call SETS that one value, so a
        counter table emptied since holds it again."""
        if self.before_build_s is None:
            self.before_build_s = _process_age_s()
        self._set_counter("setup.before_build_s", self.before_build_s)

    # ---- jax's listeners -----------------------------------------------

    def _thread(self):
        state = self._tls
        if not hasattr(state, "counted"):
            state.label = None
            # (start, class, phase, seconds) of the events counted and
            # not taken back, oldest first; only a suffix of it can lie
            # inside a later event, so the bound drops what is settled
            state.counted = deque(maxlen=1 << 16)
            state.hit, state.fetch_s = False, 0.0
            state.step = None
        return state

    def _on_event(self, event, **_kw):
        self.events.append((time.perf_counter(), event))
        if event == _CACHE_HIT:
            self._thread().hit = True

    def _on_duration(self, event, seconds, **_kw):
        self.events.append((time.perf_counter(), event))
        if event == _CACHE_FETCH:
            self._thread().fetch_s = seconds

    def _on_span(self, event, start, end, fun_name="", **_kw):
        t0 = time.perf_counter()
        self.events.append((t0, event))
        phase = _PHASES.get(event)
        if phase is None:
            return
        if phase == "backend_s":
            self.last_backend_end = t0
        state = self._thread()
        cls = state.label or (
            STEP if fun_name in self._step_names else OTHER
        )
        seconds = end - start
        with self._lock:
            counted = state.counted
            while counted and counted[-1][0] >= start:
                _, c, p, s = counted.pop()
                self.seconds[c, p] -= s
            self.seconds[cls, phase] += seconds
            counted.append((start, cls, phase, seconds))
            if phase == "backend_s":
                hit, fetch_s = state.hit, state.fetch_s
                state.hit, state.fetch_s = False, 0.0
                self.cache_misses += not hit
                if cls == STEP:
                    self.step_fetch_s += fetch_s
            if cls == STEP:
                if phase == "trace_s" or state.step is None:
                    state.step = {
                        "start": start, "trace_s": 0.0, "lower_s": 0.0,
                        "backend_s": 0.0, "cache_hit": False,
                    }
                state.step[phase] += seconds
                if phase == "backend_s":
                    state.step["cache_hit"] = hit
                    self.last_step, state.step = state.step, None
                    self.step_programs += 1
            self._publish()
            self.calls += 1
            self.listener_s += time.perf_counter() - t0

    @property
    def totals(self):
        """Seconds by phase, the three classes together."""
        return {
            phase: sum(self.seconds[cls, phase] for cls in (STEP, INIT_STATE, OTHER))
            for phase in _PHASES.values()
        }

    def _publish(self):
        """The sums SET into the counter table: a retrace adds to the
        sums once and cannot double a counter."""
        s, put = self.seconds, self._set_counter
        for name, key in _STEP_COUNTERS:
            put(name, s[key])
        put("compile.step.fetch_s", self.step_fetch_s)
        for name, keys in _CLASS_COUNTERS:
            put(name, s[keys[0]] + s[keys[1]] + s[keys[2]])
        put("compile.cache_misses", self.cache_misses)


_recorder = None
_recorder_lock = threading.Lock()


def watch_compiles() -> CompileRecorder:
    """This process's compile recorder, subscribed to ``jax.monitoring``
    the first time and handed back every time after."""
    global _recorder
    with _recorder_lock:
        if _recorder is None:
            import jax.monitoring as monitoring

            from dlrover_tpu.observability.tracing import set_counter

            rec = CompileRecorder(set_counter)
            monitoring.register_event_listener(rec._on_event)
            monitoring.register_event_duration_secs_listener(rec._on_duration)
            monitoring.register_event_time_span_listener(rec._on_span)
            _recorder = rec
    return _recorder
