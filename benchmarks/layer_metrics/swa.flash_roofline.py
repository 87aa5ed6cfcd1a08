"""Attention by layer kind: what the flash kernels of a model whose
layers differ in attention kind reach of the chip's peak bf16 rate, in
percent, from the device trace: the USEFUL products of the ``flash_*``
rows, each by the kind of the layer it ran in, over their self seconds,
over ``peaks.bf16_flops``.

A (query, key) pair costs 2 x ``head_dim`` operations in each product a
kernel makes, for each of ``n_head`` heads. The products, per head and
pair (``PRODUCTS``): ``flash_fwd`` scores and p v: 2; ``flash_bwd_dq``
the scores again, dp and dq: 3; ``flash_bwd_dkv`` the scores again, dp,
dv and dk: 4.

Pairs, by the KIND of the call's layer, which its ``op_name`` says (the
program's scopes ``attn.window`` and ``attn.full``): a window layer's
query i attends to min(i + 1, ``attn_window``) keys,
``lib/flops.mean_span(seq, attn_window)`` a query (1,920.06 at 16,384
tokens and a window of 2,048); a full layer's to i + 1,
``mean_span(seq)`` (8,192.5). The kernels also multiply the masked part
of every block they touch — the upper half of a diagonal block, and of
the band of key blocks a window layer's query block walks (since PR 48:
3 blocks of 1,024 forward, 2,880 keys a query executed; blocks of 512
backward, 2,400) the 960 and 480 keys a query the window leaves out —
and none of that is counted, so the share
cannot read high: no pair outside a window is counted. One call runs
the whole batch and every head (``call_flops``). The calls are counted
from the trace, kind by kind: under full rematerialisation a window
layer's ``flash_fwd`` runs twice, a full layer's, whose output is kept,
once.

Bytes of a forward call (``call_bytes``): q and out [batch, seq, n_head,
head_dim], k and v at ``n_kv_head`` heads, bf16, once each: 302 MB at
1 x 16,384 x 32 / 4 x 128, 0.37 ms at 819 GB/s, against 2 x 2 x 128 x
32 x 16,384 x 1,920 = 0.52 TFLOP useful in a window layer, 2.6 ms at
197 TFLOP/s, and 2.2 TFLOP, 11.2 ms, in a full one: compute bound in
both kinds, so the roofline share is the share of the bf16 peak.
"""

from benchmarks.lib.flops import mean_span
from benchmarks.lib.trace import has_scope

# products per (head, query, key) pair, by kernel; the longer name first
PRODUCTS = (("flash_bwd_dkv", 4), ("flash_bwd_dq", 3), ("flash_fwd", 2))
# the program's scope of a layer kind -> whether its window is live
KINDS = (("attn.window", True), ("attn.full", False))


def call_flops(products, batch, seq, heads, channels, window=0):
    """Operations one call has to execute: ``products`` matrix products
    of 2 x channels operations over the useful pairs of every head of
    every sequence, under a window of ``window`` keys (0 = none)."""
    pairs = batch * heads * seq * mean_span(seq, window)
    return products * 2.0 * pairs * channels


def call_bytes(batch, seq, heads, kv_heads, channels, itemsize=2):
    """Bytes a forward call has to move at the least: q and out at
    ``heads``, k and v at ``kv_heads``, each once."""
    return (2 * heads + 2 * kv_heads) * batch * seq * channels * itemsize


def kind_window(paths, window):
    """The window of the layer a kernel's row ran in, from the
    ``op_name`` paths of its instruction; None where they name no
    kind."""
    for scope, windowed in KINDS:
        if any(has_scope(path, scope) for path in paths):
            return window if windowed else 0
    return None


def read(run):
    trace = run["trace"]
    if not trace or not trace["per_device"]:
        return None
    first = trace["per_device"][0]
    sizes, seq = run["sizes"], run["seq"]
    batch = run["window"]["tokens"] // seq
    seconds = flops = 0.0
    for label, (self_s, calls) in first["by_name"].items():
        for kernel, products in PRODUCTS:
            if not label.startswith(kernel):
                continue
            window = kind_window(
                (first.get("op_names") or {}).get(label, ()),
                sizes["attn_window"],
            )
            if window is None:
                raise LookupError(
                    f"{label!r} ran under neither "
                    f"{' nor '.join(scope for scope, _ in KINDS)}"
                )
            seconds += self_s
            flops += calls * call_flops(
                products, batch, seq, sizes["n_head"], sizes["head_dim"],
                window,
            )
            break
    if not seconds:
        raise LookupError("no flash_* row in the traced step")
    return 100.0 * flops / seconds / run["peaks"].bf16_flops
