"""Lookahead prefetch for tiered embedding tables.

The serving scheduler knows the future: requests sitting in its queue
name the exact embedding keys the next steps will gather. The
``LookaheadPrefetcher`` peeks that queue (``Scheduler.peek`` — non
destructive), extracts and dedups the keys of the next ``lookahead``
requests, and promotes the cold subset hot **off-thread** in batched
cold-store multi-gets, so by the time the engine pops a request its
rows are resident and the step-time gather is a pure in-RAM hit.

Double-buffered: producers (the engine's submit/step hooks calling
``notify``, or the worker's own poll) stage keys into the fill buffer
while the worker drains the other buffer against the cold store; the
swap is O(1) under a mutex, so staging never waits on disk and the
worker always promotes a stable batch. Per-key fault serialization
lives in ``TieredTable`` (the promotion-epoch design), so a prefetch
racing a demand fault costs one disk read total, not two.
"""

import threading
import time
from collections import OrderedDict
from typing import Callable, Iterable, Optional

import numpy as np

from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)


class LookaheadPrefetcher:
    """Queue-peeking cold→hot promoter for a ``TieredTable``.

    ``peek(n)`` returns the next ``n`` queued requests in pop order
    (``Scheduler.peek``); ``extract_keys(req)`` maps one request to the
    int64 embedding keys its forward pass will gather. Neither is
    called under any prefetcher lock.
    """

    def __init__(
        self,
        table,
        peek: Callable[[int], Iterable],
        extract_keys: Callable[[object], np.ndarray],
        *,
        lookahead: int = 8,
        poll_interval_s: float = 0.002,
        recent_cap: int = 65536,
    ):
        self.table = table
        self._peek = peek
        self._extract = extract_keys
        self.lookahead = max(1, int(lookahead))
        self.poll_interval_s = float(poll_interval_s)
        self._mu = threading.Lock()
        # the double buffer: _buffers[_fill] stages, the other drains
        self._buffers = [set(), set()]
        self._fill = 0
        # keys staged recently — skip re-staging rows the worker already
        # promoted for a request still sitting in the queue
        self._recent: "OrderedDict[int, None]" = OrderedDict()
        self._recent_cap = int(recent_cap)
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._busy = False
        self._thread: Optional[threading.Thread] = None
        self.batches = 0
        self.keys_staged = 0
        self.keys_promoted = 0

    # ---- producer side ---------------------------------------------------

    def collect(self) -> int:
        """Peek the queue and stage fresh keys into the fill buffer.

        Cheap (no cold-store I/O): metadata peek + numpy dedup. Returns
        the number of newly staged keys."""
        reqs = list(self._peek(self.lookahead))
        if not reqs:
            return 0
        parts = [np.asarray(self._extract(r), np.int64) for r in reqs]
        parts = [p for p in parts if p.size]
        if not parts:
            return 0
        keys = np.unique(np.concatenate(parts))
        staged = 0
        with self._mu:
            buf = self._buffers[self._fill]
            for k in keys.tolist():
                if k in self._recent:
                    continue
                buf.add(k)
                self._recent[k] = None
                staged += 1
            while len(self._recent) > self._recent_cap:
                self._recent.popitem(last=False)
        if staged:
            self.keys_staged += staged
        return staged

    def notify(self) -> None:
        """Wake the worker now (engine submit / step-boundary hook)."""
        self._wake.set()

    # ---- worker side -----------------------------------------------------

    def _swap(self) -> Optional[np.ndarray]:
        with self._mu:
            batch = self._buffers[self._fill]
            if not batch:
                self._busy = False
                return None
            self._fill ^= 1
            self._buffers[self._fill].clear()
            self._busy = True
        return np.fromiter(batch, np.int64, len(batch))

    def _run(self):
        while not self._stop.is_set():
            self._wake.wait(self.poll_interval_s)
            self._wake.clear()
            self.collect()
            batch = self._swap()
            if batch is None:
                continue
            try:
                promoted = self.table.prefetch(batch)
            except Exception:
                logger.exception("prefetch batch of %d keys failed",
                                 batch.size)
                promoted = 0
            self.batches += 1
            self.keys_promoted += promoted
            with self._mu:
                # the drained buffer is done with: left full it would
                # keep drain() from ever seeing the prefetcher idle
                self._buffers[self._fill ^ 1].clear()
                self._busy = False

    def start(self) -> "LookaheadPrefetcher":
        if self._thread is not None:
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="sparse-prefetch", daemon=True
        )
        self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout)
        self._thread = None

    def drain(self, timeout: float = 5.0) -> bool:
        """Block until both buffers are empty and no promotion is in
        flight (test hook). True on quiesce, False on timeout."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._mu:
                idle = not self._busy and not any(self._buffers)
            if idle:
                return True
            self._wake.set()
            time.sleep(0.001)
        return False

    def stats(self) -> dict:
        return {
            "batches": self.batches,
            "keys_staged": self.keys_staged,
            "keys_promoted": self.keys_promoted,
        }
