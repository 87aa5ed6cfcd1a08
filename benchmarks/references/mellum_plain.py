"""Plain reference for Mellum2-12B-A2.5B-Instruct (``model_type:
mellum``, 12B-A2.5B;
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json):
the forward pass, the mean next-token loss and the router's balance term
in straightforward ``jax.numpy`` and float32. No kernel, no scan over
layers (they differ in kind: each is taken from the stack by index), no
sort of rows, no ``ragged_dot``, no capacity, no drop, no remat, and no
import from the program: the YaRN table below is written from its own
formula. ``forward`` sets ``jax.default_matmul_precision("highest")``
(the runner does too).

It reads the program's parameter tree by name (``layers`` stacked on
axis 0) and the configuration file's ``sizes``. With x the residual
stream [B, S, d], rms an RMSNorm with a learned scale at ``norm_eps``,
H query heads of hd channels over G key-value heads (g(h) the one head h
reads), p the tokens' position ids, and layer l of kind
``layer_types[l]`` (``S`` sliding window under the plain rope, ``Y``
full under the scaled one):

    x_0 = Emb[t]                                   (no embedding scale)

    a   = rms(x; ln1)
    q_h = rms(a Wq,h; q_norm);  k_g = rms(a Wk,g; k_norm);  v_g = a Wv,g
          (the two norms run over ONE head's hd channels, one scale
           shared by the heads; no bias anywhere)
    pairs i = 0..hd/2-1 (channel i with i + hd/2, rotate-half),
    f_i = rope_theta^(-2i / hd):
    S:  cos(p f_i), sin(p f_i);
        query i sees key j iff 0 <= p_i - p_j < ``attn_window``
    Y:  YaRN (the ``transformers`` reading of ``rope_type: yarn``,
        ``truncate`` true, no ``mscale``):
          c(b)  = hd ln(rope_original_max / (2 pi b)) / (2 ln rope_theta)
          low   = max(floor(c(rope_beta_fast)), 0)
          high  = min(ceil(c(rope_beta_slow)), hd - 1)
          r_i   = clip((i - low) / (high - low), 0, 1)
          f'_i  = f_i (1 - r_i) + f_i / rope_factor r_i
          m     = rope_attn_factor  (0: 0.1 ln(rope_factor) + 1)
        m cos(p f'_i), m sin(p f'_i) turn q AND k, so the scores carry
        m^2; query i sees key j iff p_j <= p_i
    o_h = softmax_j(q_h . k_g(h) / sqrt(hd)) v_g(h)
    x   = x + concat_h(o_h) Wo

    h   = rms(x; ln2)
    l   = h W_r (float32, n_experts wide, no bias);  s = softmax(l)
    e_1..k = the k experts of largest s (free-running), or the ids
             handed in (teacher-forced)
    w_j = s[e_j] / sum_j s[e_j]   (``norm_topk_prob``: over ALL k
          chosen, held here or not)
    x   = x + sum_{j: e_j held here} w_j E_{e_j}(h)
    E(h) = (silu(h W_g) * (h W_u)) W_d      (width d_expert; no shared
           expert, no dense layer)

then the final RMSNorm and the untied head over the vocabulary held
here. At the published numbers (hd 128, theta 5e5, 8,192, 32 / 1, x 16)
c(32) = 18.08 and c(1) = 34.98: pairs 0-18 keep their frequency, pairs
35-63 turn sixteen times slower, the sixteen between are blended, and
m = 1.2772588722239782.

The chip holds experts ``[expert_offset, expert_offset +
n_experts_held)`` of the router's ``n_experts``: what the others would
have added is left out, here as in the program, and that partial sum is
what goes on. Every held expert runs over every token, one after
another, its output scaled by the token's weight for it (zero where the
token did not choose it). Under teacher forcing the ids are the ONLY
thing taken from the program.

The objective's other term, summed over the layers, under the name of
the program's step metric, coefficient included:

    moe_lb_loss = moe_aux_coef x E x sum_e f_e sbar_e
                  f_e expert e's share of the layer's (token, choice)
                  pairs, sbar_e its mean softmax probability, over the
                  router's full width E

Departures from the published model and sizes it does not state, each
listed under ``assumed`` in the configuration file:

- the per-head RMSNorm of q and k has no key in ``config.json``; its
  key set is the Qwen3-MoE family's, whose model code has the norm;
- YaRN's ``truncate`` true and no ``mscale`` (the ``transformers``
  defaults); the amplitude multiplies cos and sin; rotate-half pairing;
  the window counts the query's own position; softmax scale hd^-1/2;
- the balance term's coefficient (0.001) and form (OLMoE's, over the
  router's full width) have no key; no z-loss, no selection bias;
- no prediction module: the catalog's ``described_as`` names an "MTP
  head", ``config.json`` has no key for one;
- weights are random from a seed, the context is the cell's.
"""

import math

import jax
import jax.numpy as jnp

from benchmarks.lib import flops

F32 = jnp.float32
# a kind of ``sizes["layer_types"]`` -> (under the window, the scaled rope)
KINDS = {"S": (True, False), "Y": (False, True)}


def kind_window(sizes, kind):
    """Keys a query of a layer of ``kind`` may see (0 = every earlier
    one)."""
    return sizes["attn_window"] if KINDS[kind][0] else 0


def required_terms(sizes, seq):
    """The two terms of ``lib/flops.py``'s convention for this
    architecture on this chip: every layer its attention's four
    matrices, the router whole and k x h / E of its experts for a chip
    that holds h of E (no shared expert, no dense layer), the head over
    the vocabulary as sliced; each attention layer the span of its own
    kind (``mean_span(seq, attn_window)`` a window layer,
    ``mean_span(seq)`` a full one). The rope's turning multiplies no
    parameter and no pair."""
    d = sizes["d_model"]
    d_attn = sizes["n_head"] * sizes["head_dim"]
    attn = 2 * d * d_attn + 2 * d * sizes["n_kv_head"] * sizes["head_dim"]
    met = sizes["expert_top_k"] * sizes["n_experts_held"] / sizes["n_experts"]
    layer = attn + d * sizes["n_experts"] + met * 3 * d * sizes["d_expert"]
    kinds = sizes["layer_types"]
    if len(kinds) != sizes["n_layer"]:
        raise ValueError(f"{kinds!r} names not {sizes['n_layer']} layers")
    return {
        "multiplied_params": int(
            sizes["n_layer"] * layer + d * sizes["vocab_size"]
        ),
        "attention_pair_channels": d_attn * sum(
            flops.mean_span(seq, kind_window(sizes, kind)) for kind in kinds
        ),
    }


def yarn_range(sizes):
    """(low, high): the pairs up to ``low`` keep their frequency, those
    from ``high`` are interpolated whole."""
    hd, theta = sizes["head_dim"], sizes["rope_theta"]

    def pair(beta):
        turns = sizes["rope_original_max"] / (2 * math.pi * beta)
        return hd * math.log(turns) / (2 * math.log(theta))

    low = math.floor(pair(sizes["rope_beta_fast"]))
    high = math.ceil(pair(sizes["rope_beta_slow"]))
    return max(low, 0), min(high, hd - 1)


def rope_table(sizes, kind, positions):
    """(cos, sin) [B, S, 1, hd/2] float32 of a layer of ``kind`` at
    ``positions`` [B, S]: the plain table, or YaRN's with its
    amplitude."""
    hd = sizes["head_dim"]
    pairs = jnp.arange(hd // 2, dtype=F32)
    freq = sizes["rope_theta"] ** (-2.0 * pairs / hd)
    amplitude = 1.0
    if KINDS[kind][1]:
        low, high = yarn_range(sizes)
        if high == low:
            high += 0.001  # one pair between keeping and interpolating
        ramp = jnp.clip((pairs - low) / (high - low), 0.0, 1.0)
        freq = freq * (1.0 - ramp) + freq / sizes["rope_factor"] * ramp
        amplitude = sizes["rope_attn_factor"] or (
            0.1 * math.log(sizes["rope_factor"]) + 1.0
        )
    angle = positions.astype(F32)[:, :, None, None] * freq
    return amplitude * jnp.cos(angle), amplitude * jnp.sin(angle)


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(F32), tree)


def _rms(x, p, sizes):
    mean_sq = jnp.mean(x * x, -1, keepdims=True)
    return x * jax.lax.rsqrt(mean_sq + sizes["norm_eps"]) * p["scale"]


def _turn(x, table):
    # x [B, S, H, D]; lane i pairs with lane i + D/2
    cos, sin = table
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, positions, window, q_block):
    """softmax(q k^T / sqrt(hd)) v under the mask of one kind, built
    from ``positions`` [B, S]: key j is visible to query i iff
    0 <= p_i - p_j (< ``window`` where it is not 0). q [B, S, H, D], k
    and v [B, S, G, D]: query head h reads key-value head h // (H / G).
    ``q_block`` query rows at a time; the arithmetic is the whole
    softmax's."""
    b, s, h, d = q.shape
    g = k.shape[2]
    q = q.reshape(b, s, g, h // g, d)
    q_block = min(q_block, s)
    if s % q_block:
        raise ValueError(f"sequence {s} is not a multiple of {q_block}")

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=1)
        qpos = jax.lax.dynamic_slice_in_dim(positions, start, q_block, 1)
        back = qpos[:, :, None] - positions[:, None, :]  # [B, qb, S]
        mask = back >= 0
        if window:
            mask = mask & (back < window)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k) * d ** -0.5
        scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
        return jnp.einsum(
            "bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, -1), v
        )

    out = jax.lax.map(rows, jnp.arange(0, s, q_block))  # [nb, B, qb, G, R, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h * d)


def _expert(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def _routed(h, moe, sizes, ids):
    """h [T, d], ids [T, k] or None -> (this chip's part of the block's
    output [T, d], router logits [T, E], the balance term before its
    coefficient)."""
    n_exp, k = sizes["n_experts"], sizes["expert_top_k"]
    first, held = sizes["expert_offset"], sizes["n_experts_held"]
    logits = h @ moe["w_gate"].astype(F32)
    prob = jax.nn.softmax(logits, -1)
    if ids is None:
        ids = jax.lax.top_k(prob, k)[1]
    top = jnp.take_along_axis(prob, ids, axis=-1)
    if sizes["moe_renorm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    here = first + jnp.arange(held)
    # a token's weight for each expert held here: 0 where not chosen
    weight = jnp.sum(
        jnp.where(ids[:, :, None] == here, top[:, :, None], 0.0), axis=1
    )

    def expert(total, args):
        w_g, w_u, w_d, w_tok = args
        return total + _expert(h, w_g, w_u, w_d) * w_tok[:, None], None

    # the experts are cast to float32 one at a time, inside
    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (moe["w_gate_proj"], moe["w_up"], moe["w_down"], weight.T),
    )
    chosen = ids[:, :, None] == jnp.arange(n_exp)  # [T, k, E]
    share = jnp.mean(chosen.astype(F32), axis=(0, 1))  # over pairs: sums to 1
    balance = n_exp * jnp.sum(share * jnp.mean(prob, axis=0))
    return out, logits, balance


def _layer(x, p, kind, sizes, positions, q_block, ids=None):
    """One layer of ``kind``. Returns (x, router logits [B, S, E], the
    balance term)."""
    b, s, d = x.shape
    nh, nkv, hd = sizes["n_head"], sizes["n_kv_head"], sizes["head_dim"]
    attn = _f32(p["attn"])
    a = _rms(x, _f32(p["ln1"]), sizes)
    q = _rms((a @ attn["wq"]).reshape(b, s, nh, hd), attn["q_norm"], sizes)
    k = _rms((a @ attn["wk"]).reshape(b, s, nkv, hd), attn["k_norm"], sizes)
    v = (a @ attn["wv"]).reshape(b, s, nkv, hd)
    table = rope_table(sizes, kind, positions)
    o = _attention(
        _turn(q, table), _turn(k, table), v, positions,
        kind_window(sizes, kind), q_block,
    )
    x = x + o @ attn["wo"]
    h = _rms(x, _f32(p["ln2"]), sizes)
    f, logits, balance = _routed(
        h.reshape(b * s, d), p["moe"], sizes,
        None if ids is None else ids.reshape(b * s, -1),
    )
    return x + f.reshape(b, s, d), logits.reshape(b, s, -1), balance


def forward(params, tokens, sizes, q_block=1024, choices=None,
            positions=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, routed):
    ``routed["router_logits"]`` float32 [layers, B, S, E] (before the
    softmax, which is monotone: the top-k is the same) and
    ``routed["moe_lb_loss"]``. ``choices`` int32 [layers, B, S, k]
    forces the routing; ``positions`` [B, S] are the tokens' position
    ids (0..S-1 without)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    kinds = sizes["layer_types"]
    if len(kinds) != sizes["n_layer"] or sizes["n_dense_layer"]:
        raise ValueError("every layer is routed and layer_types names each")
    # on a TPU a float32 matmul otherwise runs in bf16 passes
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(F32)
        router_logits, balance = [], 0.0
        for i, kind in enumerate(kinds):
            p = jax.tree.map(lambda t: t[i], params["layers"])
            ids = None if choices is None else choices[i]
            x, logits, term = _layer(
                x, p, kind, sizes, positions, q_block, ids
            )
            router_logits.append(logits)
            balance = balance + term
        x = _rms(x, _f32(params["final_norm"]), sizes)
        routed = {
            "router_logits": jnp.stack(router_logits),
            "moe_lb_loss": sizes["moe_aux_coef"] * balance,
        }
        return x @ params["lm_head"]["w"].astype(F32), routed


def _mean_ce(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(logz - tgt)


def loss_and_logits(params, batch, sizes, q_block=1024):
    """Free-running: the reference's own top-k. Mean next-token
    cross-entropy (no other term) and the logits."""
    logits, _ = forward(params, batch["tokens"], sizes, q_block)
    return _mean_ce(logits, batch["targets"]), logits


def loss_and_logits_routed(params, batch, sizes, q_block, choices):
    """Teacher-forced: every token goes to the experts ``choices``
    names. Mean cross-entropy, logits, and ``routed``: the router logits
    per layer and the objective's other term."""
    logits, routed = forward(
        params, batch["tokens"], sizes, q_block, choices
    )
    return _mean_ce(logits, batch["targets"]), logits, routed
