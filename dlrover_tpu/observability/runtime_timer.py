"""Continuous runtime kernel timing: periodic on-device trace sampling.

Reference: xpu_timer (atorch/dev/xpu_timer/nvidia/hook.cc) — an
LD_PRELOAD shim timing every CUDA kernel launch continuously in
production. TPU-native mechanism: XLA owns the schedule, so per-kernel
hooks don't exist; instead, every ``interval_steps`` one training step
runs under ``jax.profiler.trace(create_perfetto_trace=True)`` and the
emitted trace is parsed into a per-op time breakdown (name → total
device time). Sampling costs one traced step per interval (~2x that
step's wall time) instead of a per-launch tax, and the breakdown is
the ACTUAL executed schedule — fusions, collectives, transfers — not
compile-time cost estimates (KernelCensus covers those).

The breakdown feeds Prometheus via ``prometheus_text``; the Trainer
wires sampling around its live step via ``TrainerArgs.profile_interval``.
"""

import glob
import gzip
import json
import os
import re
import shutil
import tempfile
from dataclasses import dataclass
from typing import Dict, List, Optional

from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

# python-frame / harness events carry source locations or wrappers —
# everything else on a device/host-compute track is an executed op
_NOISE = re.compile(
    r"[$/\\]|^PjitFunction|^PjRt|^Thread |^process_|^thread_"
)


@dataclass
class OpTime:
    name: str
    total_us: float
    count: int
    fraction: float = 0.0


def parse_perfetto_dir(logdir: str, top_k: int = 0) -> List[OpTime]:
    """Aggregate complete ('X') events from the newest perfetto trace
    under ``logdir`` into per-op totals, largest first."""
    paths = sorted(
        glob.glob(
            os.path.join(logdir, "**", "perfetto_trace.json.gz"),
            recursive=True,
        ),
        key=os.path.getmtime,
    )
    if not paths:
        return []
    with gzip.open(paths[-1], "rt") as fh:
        tr = json.load(fh)
    events = tr["traceEvents"] if isinstance(tr, dict) else tr
    totals: Dict[str, List[float]] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = ev.get("name", "")
        if not name or _NOISE.search(name):
            continue
        cur = totals.setdefault(name, [0.0, 0])
        cur[0] += float(ev.get("dur", 0))
        cur[1] += 1
    out = [
        OpTime(name=n, total_us=t, count=int(c))
        for n, (t, c) in totals.items()
    ]
    out.sort(key=lambda o: -o.total_us)
    grand = sum(o.total_us for o in out) or 1.0
    for o in out:
        o.fraction = o.total_us / grand
    return out[:top_k] if top_k else out


class RuntimeKernelTimer:
    """Sample-and-parse runtime op timing around a step callable."""

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
        self._breakdown: List[OpTime] = []
        self._sampled_at: int = -1
        self._sampled_block_k: int = 1
        self._forced: bool = False

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

    def profiled_call(self, step: int, fn, *args, n_steps: int = 1, **kwargs):
        """Run ``fn``; when the cadence hits, run it under a trace and
        refresh the breakdown. Tracing failures degrade to an untimed
        call (the backend may not support device tracing).

        ``n_steps``: how many train steps ``fn`` executes as one device
        program (the trainer's fused ``block_k`` path). The breakdown
        then covers the WHOLE block — ``sampled_block_k`` labels it so
        consumers never mistake a K-step capture for one step's budget.
        """
        if not self.should_sample(step):
            return fn(*args, **kwargs)
        self._forced = False
        import jax

        logdir = self._logdir or tempfile.mkdtemp(prefix="dlrover_prof_")
        try:
            with jax.profiler.trace(logdir, create_perfetto_trace=True):
                out = fn(*args, **kwargs)
                jax.block_until_ready(out)
            self._breakdown = parse_perfetto_dir(logdir, self.top_k)
            self._sampled_at = step
            self._sampled_block_k = max(int(n_steps), 1)
        except Exception:  # noqa: BLE001
            logger.warning(
                "runtime trace sampling failed at step %d", step,
                exc_info=True,
            )
            return fn(*args, **kwargs)
        finally:
            if self._logdir is None:
                shutil.rmtree(logdir, ignore_errors=True)
        return out

    @property
    def breakdown(self) -> List[OpTime]:
        return list(self._breakdown)

    @property
    def sampled_at(self) -> int:
        return self._sampled_at

    @property
    def sampled_block_k(self) -> int:
        """Steps covered by the current breakdown (1 = a single step)."""
        return self._sampled_block_k

    def summary(self) -> Dict[str, float]:
        return {o.name: o.total_us for o in self._breakdown}

    def prometheus_text(self, prefix: str = "dlrover_tpu_kernel") -> str:
        lines = [
            f"# TYPE {prefix}_time_us gauge",
        ]
        for o in self._breakdown:
            name = re.sub(r"[^a-zA-Z0-9_.]", "_", o.name)
            lines.append(
                f'{prefix}_time_us{{op="{name}"}} {o.total_us:.1f}'
            )
        lines.append(f"# sampled_at_step {self._sampled_at}")
        return "\n".join(lines) + "\n"
