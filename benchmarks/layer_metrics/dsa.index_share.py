"""Selected attention: percent of the device's busy time spent scoring
keys for the selection, from the device trace: self time of the first
device's operations under the program's scope ``attn.index``
(``models/decoder.py``: the indexer's three projections, its key norm
and rope, and for every chunk of queries past ``index_topk`` the index
products against every visible key, the ReLU and the weighted sum over
the index heads; forward, recomputed and backward alike) over its busy
time. The top-k is ``dsa.select_share``; the scores made again for the
alignment term are ``dsa.align_share``. The rows summed go on a
``BENCH`` line (``event: scope_rows``); a traced step with none is an
error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "dsa.index_share", "attn.index")
