"""Host wall-clock step timing.

``StepTimer`` is the Trainer's per-step clock (throughput, percentiles,
MFU gauges). What the device did inside a step is the runtime timer's
job (``runtime_timer.py``: a sampled device trace reduced by kernel and
by phase); counts live in ``tracing.py``'s counter table.
"""

import contextlib
import time
from collections import deque
from typing import Deque, Optional

import jax


class StepTimer:
    """Host wall-clock step timing ring buffer → throughput/MFU gauges.

    The device queue hides dispatch latency, so call ``stop()`` after a
    ``jax.block_until_ready`` on the step outputs (or pass the outputs to
    ``stop``) for honest numbers.
    """

    def __init__(self, window: int = 256, flops_per_step: float = 0.0,
                 peak_flops: float = 0.0):
        self._times: Deque[float] = deque(maxlen=window)
        self._t0: Optional[float] = None
        self.flops_per_step = flops_per_step
        self.peak_flops = peak_flops
        self.steps = 0

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self, outputs=None):
        if outputs is not None:
            jax.block_until_ready(outputs)
        if self._t0 is None:
            return
        self.record(time.perf_counter() - self._t0)
        self._t0 = None

    def record(self, dt: float, n_steps: int = 1):
        """Ingest one measured duration covering ``n_steps`` steps.

        Fused multi-step train blocks report once per block with
        ``n_steps=K``; the time is attributed per step so ``mean_s``,
        percentiles, ``steps_per_s`` and ``mfu`` keep their per-step
        meaning regardless of block size.
        """
        n = max(int(n_steps), 1)
        per = dt / n
        for _ in range(n):
            self._times.append(per)
        self.steps += n

    @contextlib.contextmanager
    def step(self):
        self.start()
        out_box = []
        yield out_box
        self.stop(out_box[0] if out_box else None)

    @property
    def last_s(self) -> float:
        return self._times[-1] if self._times else 0.0

    @property
    def mean_s(self) -> float:
        return sum(self._times) / len(self._times) if self._times else 0.0

    def percentile(self, p: float) -> float:
        if not self._times:
            return 0.0
        xs = sorted(self._times)
        idx = min(len(xs) - 1, int(p / 100.0 * len(xs)))
        return xs[idx]

    @property
    def steps_per_s(self) -> float:
        m = self.mean_s
        return 1.0 / m if m > 0 else 0.0

    @property
    def mfu(self) -> float:
        if not (self.flops_per_step and self.peak_flops and self.mean_s):
            return 0.0
        return self.flops_per_step / self.mean_s / self.peak_flops
