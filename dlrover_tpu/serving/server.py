"""Threaded generation server: one engine, one scheduler, one loop.

``GenerationServer`` owns a ``Scheduler`` (request intake, latency
accounting) and a ``ServingEngine`` (continuous batching over the paged
KV cache) and drives the engine from a background thread. User threads
call ``submit`` (non-blocking, returns a ``concurrent.futures.Future``)
or ``generate`` (blocking convenience); the engine loop sleeps briefly
when fully idle instead of spinning.

``kill`` stops the loop abruptly WITHOUT resolving in-flight futures —
that is the eviction drill: a replica dying mid-stream leaves its
requests dangling until ``ReplicaRouter.poll`` migrates their live KV
pages to a survivor, or re-admits them when migration is unavailable
(serving/replica.py, serving/migration.py).

``paused()`` is the migration-side concurrency contract: the engine's
pools/allocator/slots are only ever mutated on the loop thread, so a
migrator that needs to reserve pages or import a slot parks the loop at
a step boundary first and gets exclusive access for the duration.
"""

import contextlib
import threading
import time

from dlrover_tpu.common import compile_cache
from dlrover_tpu.observability.tracing import get_tracer
from dlrover_tpu.serving.engine import ServingEngine
from dlrover_tpu.serving.scheduler import (
    AdmissionError, Request, SamplingParams, Scheduler,
)


class GenerationServer:
    """Single-replica serving front end (threaded loop around the engine)."""

    def __init__(
        self,
        params,
        cfg,
        *,
        hub=None,
        replica: str = "replica-0",
        max_queue: int = 256,
        publish_every: float = 0.5,
        idle_sleep: float = 0.002,
        step_period_s: float = 0.0,
        watchdog=None,
        **engine_kw,
    ):
        self.replica = replica
        self.scheduler = Scheduler(
            max_queue=max_queue, hub=hub, replica=replica
        )
        self.engine = self._build_engine(
            params, cfg, self.scheduler, **engine_kw
        )
        # optional SLO watchdog (observability/watchdog.ServingWatchdog):
        # observed per published record; its capture snapshot defaults
        # to this engine's frozen observability state
        self.watchdog = watchdog
        if watchdog is not None and watchdog.snapshot_fn is None:
            watchdog.snapshot_fn = self.engine.observability_snapshot
        self.publish_every = publish_every
        self.idle_sleep = idle_sleep
        # minimum wall time per WORKED step (0 = run free). Benches and
        # drills that model a multi-host fleet on one machine set this
        # to pace each replica like a fixed-rate accelerator host —
        # otherwise co-located engine loops share the same cores and
        # adding a "replica" adds no capacity, inverting every
        # scale-out comparison the fleet tier wants to make.
        self.step_period_s = step_period_s
        self._stop_evt = threading.Event()
        self._thread: threading.Thread | None = None
        self._pause_lock = threading.Lock()   # serializes paused() users
        self._pause_req = threading.Event()   # ask the loop to park
        self._pause_ack = threading.Event()   # loop parked at a boundary

    def _build_engine(self, params, cfg, scheduler, **engine_kw):
        """Engine factory hook: subclasses (serving/sparse_engine.py's
        recommendation server) swap the engine while inheriting the
        loop, pause protocol, and drain semantics unchanged."""
        return ServingEngine(params, cfg, scheduler, **engine_kw)

    # ---- lifecycle -------------------------------------------------------

    @property
    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> "GenerationServer":
        if self.alive:
            return self
        # a replica coming back finds the steps it compiled before
        compile_cache.enable_compile_cache()
        self._stop_evt.clear()
        self._thread = threading.Thread(
            target=self._loop, name=f"serving-{self.replica}", daemon=True
        )
        self._thread.start()
        return self

    def stop(self, timeout: float = 30.0) -> None:
        """Graceful: finish nothing extra, just stop the loop and join."""
        self._stop_evt.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    def kill(self) -> None:
        """Abrupt stop simulating a host eviction: the loop halts at the
        next step boundary and in-flight futures stay UNRESOLVED — the
        router's failover path picks them up."""
        self.stop()

    @contextlib.contextmanager
    def paused(self, timeout: float = 30.0):
        """Exclusive engine access at a step boundary.

        Parks the loop thread (it acknowledges between steps), yields,
        then resumes it. When the loop is dead (killed replica — the
        migration donor case) this is a pass-through: the caller already
        has exclusive access. Ack timeout falls through rather than
        deadlocking a migration on a wedged loop."""
        with self._pause_lock:
            if not self.alive:
                yield self.engine
                return
            self._pause_ack.clear()
            self._pause_req.set()
            self._pause_ack.wait(timeout)
            try:
                yield self.engine
            finally:
                self._pause_req.clear()

    def begin_drain(self) -> None:
        """Planned drain: stop admitting queued work so in-flight slots
        finish or migrate out; the queue itself is re-routed by the
        caller (ReplicaRouter / migrator)."""
        self.engine.draining = True

    def _loop(self) -> None:
        last_pub = time.monotonic()
        while not self._stop_evt.is_set():
            if self._pause_req.is_set():
                # re-ack every tick: a second paused() user can clear
                # the ack and re-raise the request before this thread
                # observes the gap between them — still parked at the
                # same step boundary, so acking again is always valid
                while self._pause_req.is_set() and not self._stop_evt.is_set():
                    self._pause_ack.set()
                    time.sleep(0.001)
                continue
            t_step = time.monotonic()
            worked = self.engine.step()
            if worked and self.step_period_s > 0.0:
                rem = self.step_period_s - (time.monotonic() - t_step)
                if rem > 0:
                    self._stop_evt.wait(rem)
            now = time.monotonic()
            if now - last_pub >= self.publish_every:
                self._publish()
                last_pub = now
            if not worked:
                self._stop_evt.wait(self.idle_sleep)
        # final snapshot so short-lived servers still leave telemetry
        self._publish()

    def _publish(self) -> None:
        stats = self.engine.stats()
        rec = self.scheduler.publish(stats)
        if self.watchdog is not None:
            self.watchdog.observe(rec)
        tr = get_tracer()
        if tr.enabled:
            tr.counter(
                f"serving.occupancy.{self.replica}",
                active_slots=stats["active_slots"],
                queue_depth=rec.queue_depth,
                free_pages=stats["free_pages"],
            )

    # ---- intake ----------------------------------------------------------

    def submit(
        self, prompt, max_new_tokens: int, eos_id=None, priority: int = 0,
        sampling: SamplingParams | None = None,
        deadline_s: float | None = None,
    ) -> Request:
        if len(prompt) + max_new_tokens > self.engine.max_len:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new({max_new_tokens}) "
                f"exceeds slot capacity {self.engine.max_len}"
            )
        return self.scheduler.submit(
            prompt, max_new_tokens, eos_id=eos_id, priority=priority,
            sampling=sampling, deadline_s=deadline_s,
        )

    @property
    def role(self) -> str:
        """This replica's pool in a disaggregated fleet:
        ``"prefill"`` | ``"decode"`` | ``"unified"``."""
        return self.engine.role

    def re_admit(self, req: Request) -> None:
        """Re-prefill failover intake — the migration ladder's fallback
        tier: requeue another replica's in-flight request under its
        original admission ticket; generation restarts from the prompt.
        ``req.sampling`` rides along, and position-indexed draws make
        the re-prefilled continuation identical to the original.

        Refused on a decode-role replica: a raw re-admission means a
        full chunked prefill on the decode critical path — exactly the
        interference the prefill/decode split removes. Role-aware
        callers (ReplicaRouter's migrator override) route the ticket
        through the prefill pool instead."""
        if self.engine.role == "decode":
            raise AdmissionError(
                f"decode-role replica {self.replica} cannot re-prefill "
                f"{req.rid} — route it through the prefill pool"
            )
        self.scheduler.re_admit(req)

    def generate(
        self, prompt, max_new_tokens: int, eos_id=None,
        timeout: float = 120.0, sampling: SamplingParams | None = None,
    ):
        """Blocking convenience: submit and wait for the full sequence."""
        return self.submit(
            prompt, max_new_tokens, eos_id=eos_id, sampling=sampling
        ).future.result(timeout)
