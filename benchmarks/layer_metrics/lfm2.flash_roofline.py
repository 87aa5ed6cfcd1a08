"""A grouped-query attention at heads of 64: what the flash kernels
(forward, dq, dkv) reach of their roofline at 32 query heads on 8
key-value heads of 64 over eight causal sequences of 4,096, in percent,
from the device trace: each call's REQUIRED pair operations, or its
bytes where those take longer, over the kernels' self seconds
(``lib/lfm2.py``, by ``lib/mellum.py``'s counts; compute bound, so the
share is of the bf16 peak: a head of 64 fills half the matrix unit's
contraction). Masked pairs the kernels execute are not counted."""

from benchmarks.lib.lfm2 import flash_roofline


def read(run):
    return flash_roofline(run)
