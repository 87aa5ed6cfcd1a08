"""A grouped-query attention at heads of 64: percent of the device's
busy time spent in the one attention layer of the six, from the device
trace: self time under the program's scope ``attn`` (the ``*`` part:
its norm, the projections at 32 / 8 heads of 64, the per-head norms of
q and k, rope over all 64 channels, the flash kernels over the causal
span of 4,096 and the out-projection; forward, recomputed and backward
alike) over the busy time. A program without the scope reads nothing."""

from benchmarks.lib.gdn import share


def read(run):
    return share(run, "lfm2.attn_share", ("attn",))
