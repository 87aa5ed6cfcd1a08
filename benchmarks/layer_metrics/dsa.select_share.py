"""Selected attention: percent of the device's busy time spent cutting
each query's keys out of its index scores, from the device trace: self
time of the first device's operations under the program's scope
``attn.select`` (``decoder._select_keys``: the scores' monotone keys,
the k-th largest by bisection over their bits, the cut of the ties, and
the int8 mask [B, S, S] put together; made once a layer a step, the
recomputed forward loads the mask) over its busy time. The rows summed
go on a ``BENCH`` line (``event: scope_rows``); a traced step with none
is an error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "dsa.select_share", "attn.select")
