"""The gated short convolution: percent of the device's busy time spent
in the five conv mixers, from the device trace: self time of the first
device's operations under the program's scope ``conv``
(``models/decoder.py::_gated_conv_block`` with its norm: the d -> 3d
in-projection, the gated conv's pass and the d -> d out-projection;
forward, recomputed and backward alike) over its busy time. The rows
summed go on a ``BENCH`` line (``event: scope_rows``); a program without
the scope reads nothing."""

from benchmarks.lib.gdn import share


def read(run):
    return share(run, "lfm2.conv_mixer_share", ("conv",))
