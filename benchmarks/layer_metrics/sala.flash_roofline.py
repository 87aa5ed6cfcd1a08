"""Block-sparse attention: what the flash kernels that take a selection
reach of the chip's peak bf16 rate, in percent, from the device trace:
the USEFUL products of the ``flash_*_sel`` rows over their self seconds,
over ``peaks.bf16_flops`` — ``dsa.flash_roofline``'s reading for a
selection by BLOCKS.

A (query, key) pair costs 2 x ``d_head`` operations in each product a
kernel makes, for each of ``n_head`` heads; the products a kernel makes
are ``lib/sel_kernels.PRODUCTS``. Pairs: the keys of the blocks a query chose that it
may see, ``lib/flops.mean_span(seq, topk=index_topk,
block=select_block)`` a query (3,560.5 at 16,384 tokens, 64 blocks of
64). The kernels run every block under the causal diagonal dense under
the mask (8,192.5 pairs a query and the masked half of the diagonal
blocks besides), and none of that is counted: the share reads at most
3,560 / 8,192 of what the kernels reach of the peak and cannot read
high. A sequence of at most ``select_dense_len`` tokens runs the plain
kernels and this reader finds nothing. One call runs the whole batch
and every head; the calls are counted from the trace.

Bytes of a forward call: q and out [batch, seq, n_head, d_head], k and
v at ``n_kv_head`` heads, bf16, once each, and the int8 selection under
the diagonal once a head: at 1 x 16,384 x 32 / 2 x 128 that is 0.28 GB
+ 4.3 GB = 5.6 ms at 819 GB/s, against 2 x 2 x 128 x 32 x 16,384 x
8,192.5 = 2.2 TFLOP executed, 11.2 ms at 197 TFLOP/s: compute bound, so
the roofline share is the share of the bf16 peak.
"""

from benchmarks.lib.flops import mean_span
from benchmarks.lib.sel_kernels import peak_share


def _useful(sizes, seq):
    return sizes["n_head"], sizes["d_head"], mean_span(
        seq, topk=sizes["index_topk"], block=sizes["select_block"]
    )


def read(run):
    return peak_share(run, _useful)
