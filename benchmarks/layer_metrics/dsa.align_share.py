"""Selected attention: percent of the device's busy time spent on the
indexer's alignment term, from the device trace: self time of the first
device's operations under the program's scope ``attn.index_loss``
(``decoder._alignment_kl``: a chunk of queries at a time, the
attention's scores again from q, k and the kernel's lse, their
head-mean on the selection, the index scores again, the KL and its
derivative for the indexer's operands; forward, recomputed and backward
alike) over its busy time. The rows summed go on a ``BENCH`` line
(``event: scope_rows``); a traced step with none is an error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "dsa.align_share", "attn.index_loss")
