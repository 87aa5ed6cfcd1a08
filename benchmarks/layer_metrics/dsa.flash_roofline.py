"""Selected attention: what the flash kernels that take a selection
reach of the chip's peak bf16 rate, in percent, from the device trace:
the USEFUL products of the ``flash_*_sel`` rows over their self seconds,
over ``peaks.bf16_flops``.

A (query, key) pair costs 2 x ``head_dim`` operations in each product a
kernel makes, for each of ``n_head`` heads. The products, per head and
pair (``PRODUCTS``): ``flash_fwd_sel`` scores and p v: 2;
``flash_bwd_dq_sel`` the scores again, dp and dq: 3;
``flash_bwd_dkv_sel`` the scores again, dp, dv and dk: 4.

Pairs: the SELECTED ones. Query i attends to min(i + 1, index_topk)
keys, ``lib/flops.mean_span(seq, 0, index_topk)`` a query (1,792.125 at
8192 tokens and top-2048). The kernels run every block under the causal
diagonal dense under the mask (4,096.5 pairs a query and the masked
half of the diagonal blocks besides), and none of that is counted: the
share reads at most 1,792 / 4,096 of what the kernels reach of the peak
and cannot read high. One call runs the whole batch and every head
(``call_flops``). The calls are counted from the trace: under full
rematerialisation ``flash_fwd_sel`` runs twice a layer.

Bytes of a forward call (``call_bytes``): q and out [batch, seq, n_head,
head_dim], k and v at ``n_kv_head`` heads, bf16, once each, and the
selection [batch, seq, seq] int8 read once a head under the diagonal:
at 1 x 8192 x 32 / 4 x 128 that is 151 MB + 1.07 GB = 1.5 ms at 819
GB/s, against 2 x 2 x 128 x 32 x 8192 x 4,096.5 = 0.55 TFLOP executed,
2.8 ms at 197 TFLOP/s: compute bound, so the roofline share is the
share of the bf16 peak.
"""

from benchmarks.lib.flops import mean_span

# products per (head, query, key) pair, by kernel; the longer name first
PRODUCTS = (
    ("flash_bwd_dkv_sel", 4), ("flash_bwd_dq_sel", 3), ("flash_fwd_sel", 2),
)


def call_flops(products, batch, seq, heads, channels, topk):
    """Operations one call has to execute: ``products`` matrix products
    of 2 x channels operations over the selected pairs of every head of
    every sequence."""
    pairs = batch * heads * seq * mean_span(seq, 0, topk)
    return products * 2.0 * pairs * channels


def call_bytes(batch, seq, heads, kv_heads, channels, itemsize=2):
    """Bytes a forward call has to move at the least: q and out at
    ``heads``, k and v at ``kv_heads``, each once, and the int8
    selection under the diagonal once a head."""
    arrays = 2 * heads + 2 * kv_heads
    return (
        arrays * batch * seq * channels * itemsize
        + heads * batch * seq * mean_span(seq)
    )


def read(run):
    trace = run["trace"]
    if not trace or not trace["per_device"]:
        return None
    sizes, seq = run["sizes"], run["seq"]
    batch = run["window"]["tokens"] // seq
    seconds = flops = 0.0
    for label, (self_s, calls) in trace["per_device"][0]["by_name"].items():
        for kernel, products in PRODUCTS:
            if label.startswith(kernel):
                seconds += self_s
                flops += calls * call_flops(
                    products, batch, seq, sizes["n_head"],
                    sizes["head_dim"], sizes["index_topk"],
                )
                break
    if not seconds:
        raise LookupError("no flash_*_sel row in the traced step")
    return 100.0 * flops / seconds / run["peaks"].bf16_flops
