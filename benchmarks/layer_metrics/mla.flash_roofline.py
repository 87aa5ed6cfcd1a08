"""Latent attention: what the flash kernels reach of the chip's peak
bf16 rate, in percent, from the device trace: the products they have to
execute over their self seconds, over ``peaks.bf16_flops``.

Latent attention runs expanded in training: every head's q and k have
``qk_nope_head_dim + qk_rope_head_dim`` channels and its v
``v_head_dim``, equal here (256), so a (query, key) pair costs 2 x 256
operations in each product a kernel makes. The products, per head and
pair (``PRODUCTS``):

- ``flash_fwd``: scores q k^T and p v: 2;
- ``flash_bwd_dq``: the scores again, dp = do v^T, dq = ds k: 3;
- ``flash_bwd_dkv``: the scores again, dp = do v^T, dv = p^T do and
  dk = ds^T q: 4.

Pairs: under the causal mask query i sees i + 1 keys, so a sequence has
seq x (seq + 1) / 2 USEFUL pairs a head (``lib/flops.mean_span``). The
kernels also multiply the masked half of every block on the diagonal;
that is not counted, so the share cannot read high. One call runs the
whole batch and every head (``call_flops``). The calls are counted from
the trace: under full rematerialisation ``flash_fwd`` runs twice a
layer.

Bytes of a forward call (``call_bytes``), bf16: q, k, v read and out
written, 4 x batch x seq x heads x 256 x 2 = 671 MB at 2 x 8192 x 20,
0.82 ms at 819 GB/s
(k and v are read again for every block of queries from VMEM-sized
tiles, at most seq / block_q times: 8 x 2 x 168 MB more, 3.3 ms),
against 2 x 2 x 2 x 20 x 8192 x 4096.5 x 256 = 1.37 TFLOP, 7.0 ms at
197 TFLOP/s: compute bound, so the roofline share is the share of the
bf16 peak.
"""

from benchmarks.lib.flops import mean_span

# products per (head, query, key) pair, by kernel; the longer name first
PRODUCTS = (("flash_bwd_dkv", 4), ("flash_bwd_dq", 3), ("flash_fwd", 2))


def call_flops(products, batch, seq, heads, channels):
    """Operations one call has to execute: ``products`` matrix products
    of 2 x channels operations over the useful pairs of every head of
    every sequence."""
    pairs = batch * heads * seq * mean_span(seq)
    return products * 2.0 * pairs * channels


def call_bytes(arrays, batch, seq, heads, channels, itemsize=2):
    """Bytes one call has to move at the least: each of its ``arrays``
    [batch, seq, heads, channels] operands and results once (forward:
    q, k, v, out = 4; dq: q, k, v, do, dq = 5; dkv: the same and dv =
    6). The statistics (lse, delta) are 1/64 of one of them."""
    return arrays * batch * seq * heads * channels * itemsize


def read(run):
    trace = run["trace"]
    if not trace or not trace["per_device"]:
        return None
    sizes, seq = run["sizes"], run["seq"]
    channels = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    batch = run["window"]["tokens"] // seq
    seconds = flops = 0.0
    for label, (self_s, calls) in trace["per_device"][0]["by_name"].items():
        for kernel, products in PRODUCTS:
            if label.startswith(kernel):
                seconds += self_s
                flops += calls * call_flops(
                    products, batch, seq, sizes["n_head"], channels
                )
                break
    if not seconds:
        return None
    return 100.0 * flops / seconds / run["peaks"].bf16_flops
