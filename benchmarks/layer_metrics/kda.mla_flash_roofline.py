"""Latent attention at 192 score against 128 value channels: what the
flash kernels reach of their roofline, in percent, from the device
trace: for every call of ``flash_fwd``, ``flash_bwd_dq`` and
``flash_bwd_dkv`` the trace counts, the longer of the operations it is
REQUIRED to execute over ``peaks.bf16_flops`` and the bytes it has to
move over ``peaks.hbm_bytes_s`` (``lib/kda.py``: a product over the
scores costs 2 x 192 a useful pair and head, one over the values 2 x
128; the masked half of a diagonal block is not counted), over the
kernels' self seconds. The program pads v to 192 channels and executes
192 in every product: that reads as a lower share here, never a higher
one. Compute bound at 16,384 tokens. None where the trace holds no
flash kernel."""

from benchmarks.lib.kda import flash_roofline


def read(run):
    return flash_roofline(run)
