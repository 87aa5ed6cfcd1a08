"""A rope per layer kind: what the flash kernels (forward, dq, dkv; the
window layers' banded calls and the full layer's) reach of their
roofline, in percent, from the device trace: each call's REQUIRED pair
operations under its own kind's mask, or its bytes where those take
longer, over the kernels' self seconds (``lib/mellum.py``: the existing
kernels at 32,768 tokens and a window of 1,024, a shape no other cell
has; compute bound in both kinds, so the share is of the bf16 peak).
Masked pairs the kernels execute are not counted: the share cannot read
high."""

from benchmarks.lib.mellum import flash_roofline


def read(run):
    return flash_roofline(run)
