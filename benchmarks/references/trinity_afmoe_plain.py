"""Plain reference for Trinity-Mini (``model_type: afmoe``, 26B-A3B;
https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json): the
forward pass, the mean next-token loss and the router's balance term in
straightforward ``jax.numpy`` and float32. No kernel, no scan over
layers (they differ in kind: each is taken from its stack by index), no
sort of rows, no ``ragged_dot``, no capacity, no drop, no remat. The
caller runs it under ``jax.default_matmul_precision("highest")``.

It reads the program's parameter tree by name (``dense_layers`` and
``layers`` stacked on axis 0) and the configuration file's ``sizes``.
With x the residual stream [B, S, d], rms an RMSNorm with a learned
scale at ``norm_eps``, H query heads of hd channels over G key-value
heads (g(h) the one head h reads), and layer l of kind
``layer_types[l]`` (``S`` sliding window, ``F`` full):

    x_0 = Emb[t] * sqrt(d)                         (``mup_enabled``)

    a   = rms(x; ln1)
    q_h = rms(a Wq,h; q_norm);  k_g = rms(a Wk,g; k_norm);  v_g = a Wv,g
          (the two norms run over ONE head's hd channels, one scale
           shared by the heads)
    gate = a Wg                                    (H x hd channels)
    S:  q, k <- rope(q), rope(k)  (theta ``rope_theta``, all hd
        channels, rotate-half, at the tokens' position ids);
        query i sees key j iff 0 <= p_i - p_j < ``attn_window``
    F:  NO positional term at all; query i sees key j iff p_j <= p_i
    o_h = softmax_j(q_h . k_g(h) / sqrt(hd)) v_g(h)
    o   = concat_h(o_h) * sigmoid(gate)            (channel by channel)
    x   = x + rms(o Wo; ln1_post)

    m   = rms(x; ln2)
    the first ``n_dense_layer`` layers:
        f = (silu(m W_gate) * (m W_up)) W_down     (width d_ff)
    every later layer, the routed block (no group stage: n_group 1):
        l = m W_r (float32, n_experts wide);  s = sigmoid(l)
        e_1..k = the k experts of largest s (free-running), or the ids
                 handed in (teacher-forced)
        w_j = routed_scaling_factor * s[e_j] / sum_j s[e_j]
              (``route_norm``: over ALL k chosen, held here or not)
        f = sum_{j: e_j held here} w_j E_{e_j}(m) + E_shared(m)
        E(m) = (silu(m W_g) * (m W_u)) W_d   (width d_expert; the shared
               one n_shared_experts * d_expert)
    x   = x + rms(f; ln2_post)

then the final RMSNorm and the untied head over the vocabulary held
here. The mask of each kind is built from the position ids p (0..S-1
where none are given), which is also all rope sees.

The chip holds experts ``[expert_offset, expert_offset +
n_experts_held)`` of the router's ``n_experts``: what the others would
have added is left out, here as in the program, and that partial sum is
what goes on. Every held expert runs over every token, one after
another, its output scaled by the token's weight for it (zero where the
token did not choose it). Under teacher forcing the ids are the ONLY
thing taken from the program.

The objective's other term, summed over the routed layers, under the
name of the program's step metric, coefficient included:

    moe_lb_loss = moe_aux_coef x E x sum_e f_e sbar_e
                  f_e expert e's share of the layer's (token, choice)
                  pairs, sbar_e its mean sigmoid score, over the
                  router's full width E

Departures from the published model, each so that program and reference
can agree, each listed in the configuration file:

- what ``config.json`` has no key for is the ``afmoe`` model code's:
  the output gate, the per-head norm of q and k, no positions on full
  layers, the norms on the two parts' outputs, the embedding scale;
- the selection bias that balances the experts (a buffer without
  gradient, moved by a rule outside the loss) is held at zero, so it is
  in neither program nor reference (GLM-4.7-Flash's stated departure);
- rope pairs channel i with i + hd/2 (rotate-half), no scaling
  (``rope_scaling: null``);
- the balance term's scope (the router's full width, sigmoid scores)
  has no key beside its coefficient ``load_balance_coeff``.
"""

import jax
import jax.numpy as jnp

from benchmarks.lib import flops
from benchmarks.references.decoder_plain import F32, _norm


def kind_window(sizes, kind):
    """Keys a query of a layer of ``kind`` may see (0 = every earlier
    one)."""
    return sizes["attn_window"] if kind == "S" else 0


def required_terms(sizes, seq):
    """The two terms of ``lib/flops.py``'s convention for this
    architecture on this chip: layers counted kind by kind — the gate's
    matrix among the attention's, a dense layer at ``d_ff``, a routed
    layer's router whole, its shared expert whole and k x h / E of its
    experts for a chip that holds h of E —, each attention layer the
    span of its own kind (``mean_span(seq, attn_window)`` a window
    layer, ``mean_span(seq)`` a full one), the vocabulary as sliced."""
    d = sizes["d_model"]
    d_attn = sizes["n_head"] * sizes["head_dim"]
    attn = 3 * d * d_attn + 2 * d * sizes["n_kv_head"] * sizes["head_dim"]
    met = (
        sizes["expert_top_k"] * sizes["n_experts_held"] / sizes["n_experts"]
        + sizes["n_shared_experts"]
    )
    routed = attn + d * sizes["n_experts"] + met * 3 * d * sizes["d_expert"]
    dense = attn + 3 * d * sizes["d_ff"]
    n_dense = sizes["n_dense_layer"]
    kinds = sizes["layer_types"]
    if len(kinds) != sizes["n_layer"]:
        raise ValueError(f"{kinds!r} names not {sizes['n_layer']} layers")
    return {
        "multiplied_params": int(
            n_dense * dense + (sizes["n_layer"] - n_dense) * routed
            + d * sizes["vocab_size"]
        ),
        "attention_pair_channels": d_attn * sum(
            flops.mean_span(seq, kind_window(sizes, kind)) for kind in kinds
        ),
    }


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(F32), tree)


def _rms(x, p, sizes):
    return _norm(x, p, "rmsnorm", sizes["norm_eps"])


def _rope(x, theta, positions):
    # x [B, S, H, D], positions [B, S]; lane i pairs with lane i + D/2
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, positions, window, q_block):
    """softmax(q k^T / sqrt(hd)) v under the mask of one kind, built
    from ``positions`` [B, S]: key j is visible to query i iff
    0 <= p_i - p_j (< ``window`` where it is not 0). ``q_block`` query
    rows at a time; the arithmetic is the whole softmax's."""
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
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
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        scores = jnp.where(mask[:, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(rows, jnp.arange(0, s, q_block))  # [nb, B, qb, H, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h * d)


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate.astype(F32)) * (g @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def _routed(g, moe, sizes, ids):
    """g [T, d], ids [T, k] or None -> (this chip's part of the block's
    output [T, d], router logits [T, E], the balance term before its
    coefficient)."""
    n_exp, k = sizes["n_experts"], sizes["expert_top_k"]
    first, held = sizes["expert_offset"], sizes["n_experts_held"]
    logits = g @ moe["w_gate"].astype(F32)
    score = jax.nn.sigmoid(logits)
    if ids is None:
        ids = jax.lax.top_k(score, k)[1]
    top = jnp.take_along_axis(score, ids, axis=-1)
    if sizes["moe_renorm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = sizes["routed_scaling_factor"] * top
    here = first + jnp.arange(held)
    # a token's weight for each expert held here: 0 where not chosen
    weight = jnp.sum(
        jnp.where(ids[:, :, None] == here, top[:, :, None], 0.0), axis=1
    )

    def expert(total, args):
        w_g, w_u, w_d, w_tok = args
        return total + _swiglu(g, w_g, w_u, w_d) * w_tok[:, None], None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(g),
        (moe["w_gate_proj"], moe["w_up"], moe["w_down"], weight.T),
    )
    shared = moe["shared"]
    out = out + _swiglu(g, shared["w_gate"], shared["w_up"], shared["w_down"])
    chosen = ids[:, :, None] == jnp.arange(n_exp)  # [T, k, E]
    share = jnp.mean(chosen.astype(F32), axis=(0, 1))  # over pairs: sums to 1
    balance = n_exp * jnp.sum(share * jnp.mean(score, axis=0))
    return out, logits, balance


def _layer(x, p, kind, sizes, positions, q_block, ids=None):
    """One layer of ``kind``; routed where it holds ``moe``. Returns
    (x, router logits [B, S, E] or None, the balance term or None)."""
    b, s, d = x.shape
    nh, nkv, hd = sizes["n_head"], sizes["n_kv_head"], sizes["head_dim"]
    attn = _f32(p["attn"])
    a = _rms(x, _f32(p["ln1"]), sizes)
    q = _rms((a @ attn["wq"]).reshape(b, s, nh, hd), attn["q_norm"], sizes)
    k = _rms((a @ attn["wk"]).reshape(b, s, nkv, hd), attn["k_norm"], sizes)
    v = (a @ attn["wv"]).reshape(b, s, nkv, hd)
    if kind == "S":
        theta = sizes["rope_theta"]
        q, k = _rope(q, theta, positions), _rope(k, theta, positions)
    o = _attention(q, k, v, positions, kind_window(sizes, kind), q_block)
    o = o * jax.nn.sigmoid(a @ attn["wg"])
    x = x + _rms(o @ attn["wo"], _f32(p["ln1_post"]), sizes)
    m = _rms(x, _f32(p["ln2"]), sizes)
    logits = balance = None
    if "moe" not in p:
        mlp = p["mlp"]
        f = _swiglu(m, mlp["w_gate"], mlp["w_up"], mlp["w_down"])
    else:
        # the experts are cast one at a time, inside
        f, logits, balance = _routed(
            m.reshape(b * s, d), p["moe"], sizes,
            None if ids is None else ids.reshape(b * s, -1),
        )
        f, logits = f.reshape(b, s, d), logits.reshape(b, s, -1)
    return x + _rms(f, _f32(p["ln2_post"]), sizes), logits, balance


def forward(params, tokens, sizes, q_block=1024, choices=None,
            positions=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, routed):
    ``routed["router_logits"]`` float32 [routed layers, B, S, E] (before
    the sigmoid, which is monotone: the top-k is the same) and
    ``routed["moe_lb_loss"]``. ``choices`` int32 [routed layers, B, S,
    k] forces the routing; ``positions`` [B, S] are the tokens'
    position ids (0..S-1 without)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    kinds, n_dense = sizes["layer_types"], sizes["n_dense_layer"]
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(F32)
    if sizes["scale_embedding"]:
        x = x * sizes["d_model"] ** 0.5
    router_logits, balance = [], 0.0
    for i, kind in enumerate(kinds):
        stack = params["dense_layers"] if i < n_dense else params["layers"]
        j = i if i < n_dense else i - n_dense
        p = jax.tree.map(lambda t: t[j], stack)
        ids = None if choices is None or i < n_dense else choices[j]
        x, logits, term = _layer(x, p, kind, sizes, positions, q_block, ids)
        if logits is not None:
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
    per routed layer and the objective's other term."""
    logits, routed = forward(
        params, batch["tokens"], sizes, q_block, choices
    )
    return _mean_ce(logits, batch["targets"]), logits, routed
