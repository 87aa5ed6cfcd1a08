"""What the delta rule with a decay a KEY CHANNEL (KDA) HAS to do, what
the latent attention's flash kernels have to at 192 score against 128
value channels, and the readers' shared parts for the cell that runs
them (``kda.*``; PR 65). The scope sums, the traced steps and the
rooflines' form are ``lib/gdn.py``'s.

The rule's count is of the RECURRENCE and not of any chunking, as
``lib/gdn.py``'s: 7 Dk Dv operations a token and head forward (the
decay ``Diag(α) S`` is still one multiply a cell), twice that backward,
so a training step REQUIRES 21 Dk Dv a token, head and layer.
Recomputation does not count.

Bytes, once a pass, as the program stores them (float32 between the
mixer's matmuls, ``decoder._kda_block``): the forward reads q, k and v
(H heads of D), g — ONE FLOAT32 A HEAD AND KEY CHANNEL, as wide as k,
where the scalar rule's is one a head — and β (H), and writes o; the
backward reads all of those and o's cotangent and writes the five
cotangents. The state never leaves the chip's fast memory in the
count.

The latent flash: a (query, key) pair of a head costs one product over
the score channels (``qk_nope + qk_rope``, 192) for ``q kᵀ`` and each
of its cotangent's two uses, and one over the VALUE channels
(``v_head_dim``, 128) for ``p v``, ``dO vᵀ`` and ``pᵀ dO``. The program
pads v to the score width (the kernels have one width) and so executes
192 in every product: the count is of what is REQUIRED, so the padding
reads as a lower share, never a higher one.
"""

from benchmarks.lib import gdn
from benchmarks.lib.flops import mean_span

# (score products, value products) per (head, query, key) pair, by
# kernel; the longer name first. flash_fwd: q kᵀ and p v; flash_bwd_dq:
# the scores again, dq = ds k, and dp = dO vᵀ; flash_bwd_dkv: the scores
# again, dk = dsᵀ q, and dp, dv = pᵀ dO
PRODUCTS = (
    ("flash_bwd_dkv", (2, 2)), ("flash_bwd_dq", (2, 1)), ("flash_fwd", (1, 1)),
)
# arrays [batch, seq, heads, channels] a call moves at the least, as
# (score-wide, value-wide): forward q, k | v, out; dq: q, k, dq | v, dO;
# dkv: q, k, dk | v, dO, dv
ARRAYS = {
    "flash_fwd": (2, 2), "flash_bwd_dq": (3, 2), "flash_bwd_dkv": (3, 3),
}


def layers(sizes):
    """The KDA layers of the configuration."""
    return sizes["layer_pattern"].count("K")


def rule_operations(sizes, tokens):
    """Operations a training step requires of the rule over ``tokens``
    tokens, every KDA layer: 21 a token, head and state cell."""
    cells = sizes["kda_head_dim"] ** 2
    return 21.0 * cells * sizes["kda_heads"] * tokens * layers(sizes)


def rule_bytes(sizes, tokens):
    """Bytes a training step's two passes over the rule move at the
    least, every KDA layer: q, k, v, o and g float32 at a head's
    channels, β one a head."""
    wide = sizes["kda_heads"] * sizes["kda_head_dim"] * 4
    operands = 4 * wide + sizes["kda_heads"] * 4  # q, k, v, g and β
    forward = operands + wide
    backward = operands + wide + operands
    return float(forward + backward) * tokens * layers(sizes)


def rule_roofline(run, metric="kda.rule_roofline"):
    """Percent of its roofline the rule reaches in the traced steps: the
    LONGER of operations over the bf16 peak and bytes over the memory's
    peak, over the self seconds under the scope ``kda.rule``."""
    got = gdn.scope_rows(run, metric, ("kda.rule",))
    if got is None:
        return None
    steps = gdn.traced_steps(run["spans"])
    if not steps:
        return None
    sizes, tokens = run["sizes"], run["window"]["tokens"] * steps
    floor = max(
        rule_operations(sizes, tokens) / run["peaks"].bf16_flops,
        rule_bytes(sizes, tokens) / run["peaks"].hbm_bytes_s,
    )
    return 100.0 * floor / got[0]


def score_channels(sizes):
    return sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]


def flash_call_flops(kernel, sizes, batch, seq):
    """Operations one call of ``kernel`` has to execute: its products
    over the useful pairs of every head of every sequence, each at the
    channels it is REQUIRED over."""
    score, value = dict(PRODUCTS)[kernel]
    pairs = batch * sizes["n_head"] * seq * mean_span(seq)
    return 2.0 * pairs * (
        score * score_channels(sizes) + value * sizes["v_head_dim"]
    )


def flash_call_bytes(kernel, sizes, batch, seq, itemsize=2):
    """Bytes one call has to move at the least, bf16."""
    score, value = ARRAYS[kernel]
    return float(batch * seq * sizes["n_head"] * itemsize) * (
        score * score_channels(sizes) + value * sizes["v_head_dim"]
    )


def flash_roofline(run):
    """Percent of their roofline the latent layer's flash kernels reach:
    for every call the trace counts, the longer of its required
    operations over the bf16 peak and its bytes over the memory's peak,
    over the kernels' self seconds. None where the trace holds no such
    kernel."""
    first = gdn.first_device(run)
    if first is None:
        return None
    sizes, seq = run["sizes"], run["seq"]
    batch = run["window"]["tokens"] // seq
    seconds = floor = 0.0
    for label, (self_s, calls) in first["by_name"].items():
        for kernel, _ in PRODUCTS:
            if label.startswith(kernel):
                seconds += self_s
                floor += calls * max(
                    flash_call_flops(kernel, sizes, batch, seq)
                    / run["peaks"].bf16_flops,
                    flash_call_bytes(kernel, sizes, batch, seq)
                    / run["peaks"].hbm_bytes_s,
                )
                break
    if not seconds:
        return None
    return 100.0 * floor / seconds
