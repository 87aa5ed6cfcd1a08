"""Plain reference for LFM2-8B-A1B (``model_type: lfm2_moe``,
8.3B-A1.5B;
https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json): the
forward pass and the mean next-token loss in straightforward
``jax.numpy`` and float32. No kernel, no scan over layers, no sort of
rows, no ``ragged_dot``, no capacity, no drop, no remat, and no import
from the program. ``forward`` sets
``jax.default_matmul_precision("highest")`` (the runner does too).

It reads the program's parameter tree by name (``layers`` holding one
stack a kind of part — ``conv``, ``attention``, ``mlp``, ``experts`` —
a kind's parts possibly in several stacks end to end, ``conv``,
``conv.1``: ``_nth`` counts through them) and the configuration file's
``sizes``. A layer is TWO parts, ``sizes["layer_pattern"]`` names them
(``C-`` a conv mixer and the dense MLP, ``Ce`` a conv mixer and the
routed experts, ``*e`` an attention and the routed experts), pre-norm
twice, no bias anywhere:

    h <- h + mixer(rms(h; ln));  h <- h + ffn(rms(h; ln))

with ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``, eps
``sizes["norm_eps"]`` (1e-5).

``C``, the gated short convolution, on u = rms(h), d channels:

    [B | C | x] = u W_in                     (W_in d x 3d, in that order)
    z = B * x
    c_t = sum_{j < K} w_j * z_{t-K+1+j}      (depthwise, one K-vector a
          channel, K = ``conv_kernel`` = 3; z before a sequence's first
          token 0, each row of the batch by itself; no bias, NO
          activation)
    out = (C * c) W_out                      (d x d)

written as K shifted adds.

``*``, the attention: H query heads of hd channels over G key-value
heads (query head h reads key-value head h // (H / G)), an RMSNorm over
each head's hd channels of q and of k (one learned scale each, shared by
the heads), then rope at ``rope_theta`` over ALL hd channels (channel i
pairs with i + hd/2, rotate-half; f_i = theta^(-2i / hd)), causal
softmax at hd^-1/2, out-projection:

    q_h = turn(rms(u Wq,h; q_norm));  k_g = turn(rms(u Wk,g; k_norm))
    o_h = softmax_j<=i(q_h . k_g(h) / sqrt(hd)) v_g(h);  out = concat(o) Wo

``-``, the dense MLP: ``W_down(silu(W_gate u) * W_up u)`` at ``d_ff``.

``e``, the routed experts: with l = u W_r in float32, E wide,

    s = sigmoid(l)
    e_1..k = the k experts of largest s + b (free-running; b the
             selection bias ``expert_bias``, ZEROS: see below), or the
             ids handed in (teacher-forced)
    w_j = s[e_j] / (sum_j s[e_j] + 1e-6) x routed_scaling_factor
          (``norm_topk_prob``: over ALL k chosen, held here or not)
    out = sum_{j: e_j held here} w_j E_{e_j}(u)
    E(u) = (silu(u W_g) * (u W_u)) W_d       (width d_expert; no shared
           expert)

then the final RMSNorm and the logits through the TIED embedding, over
the rows of the vocabulary held here.

The chip holds experts ``[expert_offset, expert_offset +
n_experts_held)`` of the router's ``n_experts``: what the others would
have added is left out, here as in the program, and that partial sum is
what goes on. Every held expert runs over every token, one after
another, its output scaled by the token's weight for it (zero where the
token did not choose it). Under teacher forcing the ids are the ONLY
thing taken from the program. The objective has no other term: no
balance loss, no z-loss (``config.json`` carries no coefficient; the
model balances by its bias).

Departures from the published model and sizes it does not state, each
listed under ``assumed`` in the configuration file:

- ``expert_bias`` is a buffer without gradient, zero at initialisation,
  moved by the trainer from the measured load; it is carried as zeros
  and its update is not modelled (as for GLM-4.7-Flash), so the top-k is
  of s itself;
- the renormalisation's guard is the published ``sum + 1e-6``; the
  program's is ``max(sum, 1e-9)``: with four sigmoids in the sum (0.1
  and more at seeded weights) the two differ by under 1e-5 relative, far
  inside the logits' limits;
- the embedding is tied to the head (the catalog strips the key; tied is
  the Lfm2 family's default); rotate-half pairing; softmax scale
  hd^-1/2;
- weights are random from a seed, the context is the cell's.
"""

import jax
import jax.numpy as jnp

from benchmarks.lib import flops

F32 = jnp.float32
PART_NAMES = {"C": "conv", "*": "attention", "-": "mlp", "e": "experts"}


def required_terms(sizes, seq):
    """The two terms of ``lib/flops.py``'s convention for this
    architecture on this chip: parts counted kind by kind — a conv
    mixer its two matrices (4 d^2; the taps multiply no parameter worth
    the name: 2 x 3 operations a channel), an attention its four, the
    dense MLP its three, a routed part the router whole and k x h / E of
    its experts for a chip that holds h of E —, the tied head over the
    vocabulary as sliced, and the attention layers' pairs over the
    causal span."""
    d = sizes["d_model"]
    d_attn = sizes["n_head"] * sizes["head_dim"]
    met = sizes["expert_top_k"] * sizes["n_experts_held"] / sizes["n_experts"]
    part = {
        "C": 4 * d * d,
        "*": 2 * d * d_attn + 2 * d * sizes["n_kv_head"] * sizes["head_dim"],
        "-": 3 * d * sizes["d_ff"],
        "e": d * sizes["n_experts"] + met * 3 * d * sizes["d_expert"],
    }
    pattern = sizes["layer_pattern"]
    return {
        "multiplied_params": int(
            sum(part[c] for c in pattern) + d * sizes["vocab_size"]
        ),
        "attention_pair_channels": (
            pattern.count("*") * d_attn * flops.mean_span(seq)
        ),
    }


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(F32), tree)


def _rms(x, p, sizes):
    mean_sq = jnp.mean(x * x, -1, keepdims=True)
    return x * jax.lax.rsqrt(mean_sq + sizes["norm_eps"]) * p["scale"]


def _nth(stacks, name, k):
    """The k-th part of a kind: its stacks ``name``, ``name.1``, ... end
    to end."""
    stack, more = name, 0
    while True:
        n = jax.tree.leaves(stacks[stack])[0].shape[0]
        if k < n:
            return jax.tree.map(lambda t: t[k], stacks[stack])
        k -= n
        more += 1
        stack = f"{name}.{more}"


def _gated_conv(u, conv, sizes):
    """The conv mixer on u [B, S, d]: K shifted adds between the two
    gates."""
    taps = sizes["conv_kernel"]
    s = u.shape[1]
    gate_in, gate_out, x = jnp.split(u @ conv["w_in"], 3, axis=-1)
    z = gate_in * x
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    c = sum(padded[:, j:j + s] * conv["conv_w"][j] for j in range(taps))
    return (gate_out * c) @ conv["w_out"]


def _turn(x, positions, theta):
    # x [B, S, H, D]; lane i pairs with lane i + D/2, every lane turned
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = positions.astype(F32)[:, :, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _causal_attention(q, k, v, q_block):
    """softmax(q k^T / sqrt(hd)) v under the causal mask. q [B, S, H, D],
    k and v [B, S, G, D]: query head h reads key-value head h // (H /
    G). ``q_block`` query rows at a time; the arithmetic is the whole
    softmax's."""
    b, s, h, d = q.shape
    g = k.shape[2]
    q = q.reshape(b, s, g, h // g, d)
    q_block = min(q_block, s)
    if s % q_block:
        raise ValueError(f"sequence {s} is not a multiple of {q_block}")
    keys = jnp.arange(s)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=1)
        mask = keys[None, :] <= (start + jnp.arange(q_block))[:, None]
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k) * d ** -0.5
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum(
            "bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, -1), v
        )

    out = jax.lax.map(rows, jnp.arange(0, s, q_block))  # [nb, B, qb, G, R, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h * d)


def _attention(u, attn, sizes, positions, q_block):
    b, s, _ = u.shape
    nh, nkv, hd = sizes["n_head"], sizes["n_kv_head"], sizes["head_dim"]
    q = _rms((u @ attn["wq"]).reshape(b, s, nh, hd), attn["q_norm"], sizes)
    k = _rms((u @ attn["wk"]).reshape(b, s, nkv, hd), attn["k_norm"], sizes)
    v = (u @ attn["wv"]).reshape(b, s, nkv, hd)
    theta = sizes["rope_theta"]
    o = _causal_attention(
        _turn(q, positions, theta), _turn(k, positions, theta), v, q_block
    )
    return o @ attn["wo"]


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def _routed(u, moe, sizes, ids):
    """u [T, d], ids [T, k] or None -> (this chip's part of the block's
    output [T, d], router logits [T, E])."""
    k = sizes["expert_top_k"]
    first, held = sizes["expert_offset"], sizes["n_experts_held"]
    logits = u @ moe["w_gate"].astype(F32)
    score = jax.nn.sigmoid(logits)
    if ids is None:
        ids = jax.lax.top_k(score, k)[1]  # + expert_bias, zeros
    top = jnp.take_along_axis(score, ids, axis=-1)
    if sizes["moe_renorm_topk"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-6)
    top = sizes["routed_scaling_factor"] * top
    here = first + jnp.arange(held)
    # a token's weight for each expert held here: 0 where not chosen
    weight = jnp.sum(
        jnp.where(ids[:, :, None] == here, top[:, :, None], 0.0), axis=1
    )

    def expert(total, args):
        w_g, w_u, w_d, w_tok = args
        return total + _swiglu(u, w_g, w_u, w_d) * w_tok[:, None], None

    # the experts are cast to float32 one at a time, inside
    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(u),
        (moe["w_gate_proj"], moe["w_up"], moe["w_down"], weight.T),
    )
    return out, logits


def _part(x, letter, p, sizes, positions, q_block, ids=None):
    """One part. Returns (x, router logits [B, S, E] or None)."""
    b, s, d = x.shape
    u = _rms(x, _f32(p["ln"]), sizes)
    if letter == "C":
        return x + _gated_conv(u, _f32(p["conv"]), sizes), None
    if letter == "*":
        return x + _attention(
            u, _f32(p["attn"]), sizes, positions, q_block
        ), None
    if letter == "-":
        mlp = p["mlp"]
        return x + _swiglu(u, mlp["w_gate"], mlp["w_up"], mlp["w_down"]), None
    out, logits = _routed(
        u.reshape(b * s, d), p["moe"], sizes,
        None if ids is None else ids.reshape(b * s, -1),
    )
    return x + out.reshape(b, s, d), logits.reshape(b, s, -1)


def forward(params, tokens, sizes, q_block=1024, choices=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, router logits
    [routed parts, B, S, E] before the sigmoid, which is monotone: the
    top-k is the same). ``choices`` int32 [routed parts, B, S, k] forces
    the routing."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    # on a TPU a float32 matmul otherwise runs in bf16 passes
    with jax.default_matmul_precision("highest"):
        table = params["embed"]["tokens"].astype(F32)
        x = jnp.take(table, tokens, axis=0)
        seen = dict.fromkeys(PART_NAMES, 0)
        router = []
        for letter in sizes["layer_pattern"]:
            p = _nth(params["layers"], PART_NAMES[letter], seen[letter])
            seen[letter] += 1
            ids = None
            if letter == "e" and choices is not None:
                ids = choices[len(router)]
            x, logits = _part(x, letter, p, sizes, positions, q_block, ids)
            if logits is not None:
                router.append(logits)
        x = _rms(x, _f32(params["final_norm"]), sizes)
        return x @ table.T, jnp.stack(router)


def _mean_ce(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(logz - tgt)


def loss_and_logits(params, batch, sizes, q_block=1024):
    """Free-running: the reference's own top-k. Mean next-token
    cross-entropy (the objective has no other term) and the logits."""
    logits, _ = forward(params, batch["tokens"], sizes, q_block)
    return _mean_ce(logits, batch["targets"]), logits


def loss_and_logits_routed(params, batch, sizes, q_block, choices):
    """Teacher-forced: every token goes to the experts ``choices``
    names. Mean cross-entropy, logits, and ``routed``: the router logits
    per routed part (the objective has no term beside the
    cross-entropy)."""
    logits, router_logits = forward(
        params, batch["tokens"], sizes, q_block, choices
    )
    return (
        _mean_ce(logits, batch["targets"]), logits,
        {"router_logits": router_logits},
    )
