"""Block-sparse attention: percent of the device's busy time spent
choosing each query's blocks, from the device trace: self time of the
first device's operations under the program's scope
``attn.block_select`` (``decoder._select_blocks``: the pooled keys, a
chunk of queries' products with them, the softmax over the ended ones,
the sum over a KV head's query heads, the max over a block's pooled
keys, the forced blocks and the top-k by bisection; made once a sparse
layer a step, the recomputed forward loads the units) over its busy
time. The rows summed go on a ``BENCH`` line (``event: scope_rows``); a
traced step with none is an error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "sala.select_share", "attn.block_select")
