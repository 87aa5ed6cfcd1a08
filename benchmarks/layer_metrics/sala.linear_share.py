"""Linear attention: percent of the device's busy time spent in the
lightning (``lightning-attn``) parts whole, from the device trace: self
time of the first device's operations under the program's scope ``lin``
(``decoder._lightning_block``: the part's norm, five projections, the
per-head norms and rope on q and k, the scan, the output norm, the gate
and ``W_o``; forward, recomputed and backward) over its busy time. The
rows summed go on a ``BENCH`` line (``event: scope_rows``); a traced
step with none is an error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "sala.linear_share", "lin")
