"""The gated short convolution: what the gated conv's pass reaches of
the memory's peak, in percent, from the device trace: the bytes the
OPERATION has to move (4 array-passes of batch x seq x 2048 x 2 bytes
forward, 7 backward, a forward twice under full rematerialisation, five
layers, every traced step: ``lib/lfm2.py``) at 819 GB/s over the self
seconds under the scope ``conv.gate``. The same count whichever body
implements it, so it cannot read above 100% and a later change of
kernel leaves the yardstick as it is."""

from benchmarks.lib.lfm2 import gated_conv_roofline


def read(run):
    return gated_conv_roofline(run)
