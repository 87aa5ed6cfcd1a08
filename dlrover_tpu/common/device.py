"""The one device probe: what this process runs on, and what that chip
can do.

Every kernel dispatch, capability table, memory estimate and benchmark
keys off ``device_info()``; nothing else in the package asks jax for its
backend. A device the tables below do not know is an error, never an
assumed v5e: a utilization computed against a guessed peak is worse
than none.

Calling into this module initialises the jax backend, which takes the
chip for this process. A launcher or benchmark parent that spawns device
children must therefore stay off it.
"""

from typing import NamedTuple

import jax


class DeviceInfo(NamedTuple):
    platform: str     # jax.devices()[0].platform: "tpu" | "cpu" | ...
    device_kind: str  # jax.devices()[0].device_kind, e.g. "TPU v5 lite"
    count: int        # len(jax.devices())


def device_info() -> DeviceInfo:
    devices = jax.devices()
    return DeviceInfo(
        devices[0].platform, devices[0].device_kind, len(devices)
    )


def on_tpu() -> bool:
    return device_info().platform == "tpu"


def on_cpu() -> bool:
    return device_info().platform == "cpu"


def require_tpu() -> DeviceInfo:
    """``device_info()`` for a run that was asked for the chip (the chip
    smoke): anything else is an error, not a slower place to run."""
    info = device_info()
    if info.platform != "tpu":
        raise RuntimeError(
            f"this run needs a TPU; jax reports platform "
            f"{info.platform!r} ({info.device_kind!r} x{info.count})"
        )
    return info


def require_kernels(compiled, what: str) -> int:
    """How many Pallas kernels (``tpu_custom_call``) a compiled program
    holds, for a run that was asked for the chip. None means the program
    took a reference path: what it computes or how long it takes is not
    the system's, so that is an error too."""
    n = compiled.as_text().count("tpu_custom_call")
    if n == 0:
        raise RuntimeError(
            f"the compiled {what} holds no tpu_custom_call: it took a "
            "reference path, not the kernels"
        )
    return n


class ChipSpec(NamedTuple):
    bf16_tflops: float  # peak dense bf16 matmul rate, TFLOP/s per chip
    hbm_bytes: float    # HBM capacity per chip


# Keyed by a substring of ``device_kind``. Source: Google Cloud TPU
# documentation, system architecture pages "TPU v4", "TPU v5e",
# "TPU v5p", "TPU v6e" (per-chip peak compute and HBM capacity).
_CHIPS = {
    "v4": ChipSpec(275.0, 32e9),
    "v5 lite": ChipSpec(197.0, 16e9),
    "v5e": ChipSpec(197.0, 16e9),
    "v5p": ChipSpec(459.0, 95e9),
    "v6 lite": ChipSpec(918.0, 32e9),
    "v6e": ChipSpec(918.0, 32e9),
}


def chip_spec(device_kind: str) -> ChipSpec:
    kind = device_kind.lower()
    for key, spec in _CHIPS.items():
        if key in kind:
            return spec
    raise KeyError(
        f"no peak/HBM entry for device kind {device_kind!r}: add it to "
        "dlrover_tpu.common.device._CHIPS with its source"
    )


def device_memory_bytes() -> float:
    """Bytes one device of this process can hold: what the runtime
    reports (``memory_stats()["bytes_limit"]``) where it does, the
    chip table for a TPU that does not, and the host's RAM on the CPU
    backend, whose arrays live there."""
    info = device_info()
    stats = jax.devices()[0].memory_stats() or {}
    if stats.get("bytes_limit"):
        return float(stats["bytes_limit"])
    if info.platform == "cpu":
        import psutil

        return float(psutil.virtual_memory().total)
    return chip_spec(info.device_kind).hbm_bytes
