"""Prediction module: percent of the device's busy time spent in the
multi-token-prediction module, from the device trace: self time of the
first device's operations under the program's scope ``mtp`` (the
embedding of the next tokens, the two norms and the projection, the
module's block with its attention and routed experts, its own norm, the
shared head and the cross-entropy; forward, recomputed and backward
alike) over its busy time. The block's grouped matmuls are not in it:
the compiler's ``ragged-dot`` kernels carry no scope. The rows summed
go on a ``BENCH`` line (``event: scope_rows``); a traced step with none
is an error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "mtp.share", "mtp")
