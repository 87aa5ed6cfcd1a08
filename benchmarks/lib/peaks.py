"""The benchmark's own table of chip peaks: the yardstick every
utilization here is measured against.

A copy, on purpose, of the v5e row of
``dlrover_tpu/common/device.py``'s ``_CHIPS`` (compute and capacity)
with the HBM and interconnect rates added, so that no later change to
the program can move the yardstick. Keyed by a
substring of jax's ``device_kind``. A kind that is not here is an error:
a utilization against a guessed peak is worse than none.

Source: Google Cloud TPU documentation, system architecture page
"TPU v5e" (per chip: 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s,
1,600 Gbit/s of inter-chip interconnect). Only chips the benchmark has
run on have a row; another chip's first run adds its own, with its
source.
"""

from typing import NamedTuple


class ChipPeaks(NamedTuple):
    bf16_flops: float   # peak dense bf16 matmul rate, FLOP/s per chip
    hbm_bytes: float    # HBM capacity per chip, bytes
    hbm_bytes_s: float  # HBM bandwidth per chip, bytes/s
    ici_bits_s: float   # inter-chip interconnect per chip, bit/s


_PEAKS = {
    # jax names a v5e "TPU v5 lite"
    "v5 lite": ChipPeaks(197e12, 16e9, 819e9, 1600e9),
    "v5e": ChipPeaks(197e12, 16e9, 819e9, 1600e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    kind = device_kind.lower()
    for key, peaks in _PEAKS.items():
        if key in kind:
            return peaks
    raise KeyError(
        f"no peak entry for device kind {device_kind!r}: add a row to "
        "benchmarks/lib/peaks.py with its source"
    )
