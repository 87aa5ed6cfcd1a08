"""Operations a training step REQUIRES per token: the numerator of
``train_step.mfu``.

Convention (fixed here so that no later change can move it):

    required FLOPs per token
        = 6 x (parameters that are multiplied)
        + 12 x n_layer x (n_head x head_dim) x mean_span

- "Parameters that are multiplied": every projection and MLP matrix of
  every layer, and the output head once. Not the embedding gather, not
  the learned position table, not norm scales or biases: a gather and
  an elementwise scale are not matrix multiplications. A tied head
  counts once, as the head. The 6 is forward (2) plus backward (4).
- A routed layer (``n_experts`` > 0) counts what a token meets: the
  ``expert_top_k`` experts it is sent to and the router's
  ``d_model x n_experts`` matrix, not the experts it never visits.
  Sort, gather and scatter multiply nothing.
- The attention term is the score and the value matmul, forward and
  backward (2 x 2 x 3 = 12 per query-key pair and channel), over the
  keys a query really attends to: ``mean_span`` is the mean number of
  visible keys per query under the causal mask and the sliding window.
  For s queries, window w (0 = none): query i (0-based) sees
  min(i + 1, w or s) keys.
- Recomputation (remat) does not count: it is work the recipe chose,
  not work the model requires.

This reads LOWER than ``ModelConfig.flops_per_token`` in the program
(6 x every parameter, embedding and position table included, plus
12 x L x d x span with no causal half) for the same speed.

Sizes come from the configuration file's ``sizes`` group, in the
program's own vocabulary (``n_layer``, ``d_model``, ``n_head``,
``n_kv_head``, ``d_ff``, ``vocab_size``, ``act``, ``attn_window``,
and for a routed model ``n_experts`` and ``expert_top_k``; absent or 0
is a dense MLP).
"""


def mean_span(seq: int, window: int = 0) -> float:
    """Mean number of keys a query sees in a causal sequence of ``seq``
    tokens under a sliding window of ``window`` keys (0 = no window)."""
    w = min(window, seq) if window else seq
    # queries 0..w-1 see 1..w keys; the remaining seq-w see w each
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def multiplied_params(sizes: dict) -> int:
    d = sizes["d_model"]
    head_dim = d // sizes["n_head"]
    kv = sizes.get("n_kv_head") or sizes["n_head"]
    attn = 2 * d * sizes["n_head"] * head_dim + 2 * d * kv * head_dim
    mlp = (3 if sizes["act"] == "swiglu" else 2) * d * sizes["d_ff"]
    n_experts = sizes.get("n_experts") or 0
    if n_experts:
        mlp = sizes["expert_top_k"] * mlp + d * n_experts
    head = d * sizes["vocab_size"]
    return sizes["n_layer"] * (attn + mlp) + head


def required_flops_per_token(sizes: dict, seq: int) -> float:
    d_attn = sizes["n_head"] * (sizes["d_model"] // sizes["n_head"])
    span = mean_span(seq, sizes.get("attn_window", 0))
    return (
        6.0 * multiplied_params(sizes)
        + 12.0 * sizes["n_layer"] * d_attn * span
    )

