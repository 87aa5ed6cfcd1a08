"""Two leading dense layers: percent of the device's busy time spent in
the dense SwiGLU MLPs at 2048 x 7168, from the device trace: self time
under the program's scope ``mlp`` and under none of the routed block's
own (``lib/lfm2.py``: the routed parts' four input norms are read with
it). The two dense layers are 2 of this cell's 6 where the model has 2
of 24, so this share is about four times a deployment's."""

from benchmarks.lib.lfm2 import dense_mlp_share


def read(run):
    return dense_mlp_share(run)
