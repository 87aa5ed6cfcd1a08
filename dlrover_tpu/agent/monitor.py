"""Resource reports to the master (reference:
elastic_agent/monitor/resource.py:86).

Two reporters, split by who may touch the device. The agent's
``ResourceMonitor`` reports host CPU and memory only: a chip belongs to
one process at a time, and the agent must leave it to the worker it
spawns. The worker — the process that holds the chips — reports what
only it can see: device kind, chip count and HBM usage from jax
``memory_stats``. That report rides the step heartbeat every worker
already sends (``MasterClient.report_global_step``), so no training
script has to know about it.
"""

import sys
import threading
from typing import Optional

import psutil

from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)


def get_tpu_stats() -> dict:
    """HBM usage aggregated over ALL local devices. Worker-side: this
    initialises the jax backend.

    A host owns several chips (4 per v4/v5p host); reading only
    ``devices()[0]`` under-reports host HBM pressure by the chip count
    and misses a single hot chip entirely.  ``peak_bytes_in_use`` is
    the per-device high watermark since process start — its sum is the
    "would we have OOMed at a smaller HBM" signal the analyser's
    memory estimates get compared against.
    """
    import jax

    used = 0
    peak = 0
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        used += stats.get("bytes_in_use", 0)
        peak += stats.get("peak_bytes_in_use", 0)
    return {
        "hbm_used_mb": used / 1e6,
        "hbm_peak_mb": max(peak, used) / 1e6,
    }


def _host_stats() -> dict:
    return {
        "cpu_percent": psutil.cpu_percent(interval=None),
        "used_memory_mb": psutil.virtual_memory().used / 1e6,
    }


# how often a worker's step heartbeat carries its device report: the
# agent monitor's own default interval
DEVICE_REPORT_INTERVAL_S = 30.0


def holds_devices() -> bool:
    """True in a process whose jax backend is up: the chips' owner. Asks
    without initialising one, so the agent — which shares the master
    client with its workers — can never take the chip by asking."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def report_device_stats(client) -> bool:
    """Worker-side report: host stats plus what the chips' owner alone
    can read — device kind, local chip count, HBM in use and its peak.
    It is the master's only source of the device half."""
    import jax

    devices = jax.local_devices()
    return client.report_resource_stats(
        **_host_stats(),
        **get_tpu_stats(),
        tpu_type=devices[0].device_kind,
        local_chips=len(devices),
    )


class ResourceMonitor:
    """The agent's reporter: host CPU and memory on an interval."""

    def __init__(self, client, interval_s: float = 30.0):
        self._client = client
        self._interval_s = interval_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self):
        self._thread = threading.Thread(
            target=self._loop, name="resource-monitor", daemon=True
        )
        self._thread.start()

    def stop(self):
        self._stop.set()

    def _loop(self):
        while not self._stop.wait(self._interval_s):
            self.report_once()

    def report_once(self) -> bool:
        try:
            return self._client.report_resource_stats(**_host_stats())
        except Exception:  # noqa: BLE001 — the monitor thread must live
            logger.warning("resource report failed", exc_info=True)
            return False
