"""The gated short convolution: percent of the device's busy time spent
in the gated conv's own pass, ``y = C * conv(B * x)`` between the
mixer's two matmuls, from the device trace: self time under the
program's scope ``conv.gate`` (``ops/ssd.py::gated_conv``: the kernels
``gated_conv_fwd`` / ``gated_conv_bwd`` or the XLA body, whichever the
program took — the counter ``conv.kernel_layers`` says) over the busy
time. A program without the scope reads nothing."""

from benchmarks.lib.gdn import share


def read(run):
    return share(run, "lfm2.gated_conv_share", ("conv.gate",))
