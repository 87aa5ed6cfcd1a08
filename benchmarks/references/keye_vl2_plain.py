"""Plain reference for Keye-VL-2.0-30B-A3B's language tower
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json:
``text_config`` and ``sa_config``; the vision tower is not built and the
tokens are text ids): the forward pass, the mean next-token loss, the
indexer's alignment term and the router's balance term in
straightforward ``jax.numpy`` and float32. No kernel, no sort of rows, no
``ragged_dot``, no capacity, no drop, no remat. The caller runs it under
``jax.default_matmul_precision("highest")``.

It reads the program's parameter tree by name (``decoder.init``'s
layout, per-layer tensors stacked on axis 0) and the configuration
file's ``sizes``. With x the residual stream [B, S, d], rms an RMSNorm
with a learned scale, H query heads of hd channels over G key-value
heads (g(h) the one head h reads), every layer:

    h = rms(x; ln1)
    q_h = rope(rms(h Wq,h; q_norm));  k_g = rope(rms(h Wk,g; k_norm))
    v_g = h Wv,g        (the two norms run over ONE head's hd channels,
                         one scale shared by the heads; ``qk_head_norm``)

the DeepSeek-Sparse-Attention indexer (DeepSeek-V3.2-Exp's report), for
s <= t, J = index_n_heads, C = index_head_dim:

    qI_t,j = rope(h_t WI_q)_j  (j = 1..J, C channels)
    kI_s   = rope(rms(h_s WI_k; indexer.k_norm))        (one key head)
    w_t    = h_t WI_w x (J x C)^-1/2
    I_t,s  = sum_j w_t,j relu(qI_t,j . kI_s)
    S_t    = the min(t + 1, index_topk) visible keys of largest I_t,s,
             ties to the lower s (free-running), or the keys handed in
             (teacher-forced)
    o_t,h  = sum_{s in S_t} softmax_{s in S_t}(q_t,h . k_s,g(h) / sqrt(hd)) v_s,g(h)
    x = x + concat_h(o_h) Wo

then on g = rms(x; ln2) the routed block (``norm_topk_prob`` true, no
shared expert):

    l = g W_r  (float32, n_experts wide);  p = softmax(l)
    e_1..k = the k experts of largest p (free-running), or the ids
             handed in (teacher-forced)
    w_j = p[e_j] / sum_j p[e_j]   (over ALL k chosen, held here or not)
    x = x + sum_{j: e_j held here} w_j (silu(g Wg[e_j]) * (g Wu[e_j])) Wd[e_j]

The chip holds experts ``[expert_offset, expert_offset +
n_experts_held)`` of the router's ``n_experts``: what the others would
have added is left out, here as in the program, and that partial sum is
what goes on. Then the final RMSNorm and the untied head over the
vocabulary held here.

The objective's other terms, per layer and summed over layers, under
the names of the program's step metrics, coefficients included:

    indexer_loss = indexer_loss_coef x mean_t KL(p_t || softmax_{s in S_t} I_t,s)
                   p_t = the head-mean of the attention's probabilities
                   (they live on S_t); 0 log 0 = 0
    moe_lb_loss  = moe_aux_coef x E x sum_e f_e pbar_e
                   f_e expert e's share of the layer's (token, choice)
                   pairs, pbar_e its mean probability, over the router's
                   full width E

Attention runs ``q_block`` query rows at a time, and so do the index
scores and the selection's statistics (``lib/selected.selection_stats``
on each block, stacked to [L, B, S]): no float [L, B, S, S] array is
ever whole. Every held expert runs over every token, one after another.

Under teacher forcing the masks and the expert ids are the ONLY thing
taken from the program: hidden states, index scores, softmax, router
logits, experts and both terms are this file's own.

Departures from the published model, each so that program and reference
can agree, each listed in the configuration file under ``assumed``: the
indexer's key norm is an RMSNorm and rope turns all its channels;
rotate-half pairing (channel i with i + C/2); M-RoPE's three sections on
text positions are plain rope; the alignment term and both coefficients
have no key in ``config.json``; ``rms_norm_eps`` arrives as
``sizes["norm_eps"]``.
"""

import jax
import jax.numpy as jnp

from benchmarks.lib import flops
from benchmarks.lib.selected import selection_stats
from benchmarks.references.decoder_plain import F32, _norm, _rope


def required_terms(sizes, seq):
    """The two terms of ``lib/flops.py``'s convention for this
    architecture on this chip: every layer the same block; the attention
    counts the keys it selects, the indexer its three projections and
    heads x channels / 2 over every visible key; a chip that holds h of
    E experts counts k x h / E of them a token and the router whole; the
    vocabulary as sliced."""
    d, hd = sizes["d_model"], sizes["head_dim"]
    nj, nc = sizes["index_n_heads"], sizes["index_head_dim"]
    attn = 2 * d * sizes["n_head"] * hd + 2 * d * sizes["n_kv_head"] * hd
    indexer = d * (nj * nc + nc + nj)
    met = sizes["expert_top_k"] * sizes["n_experts_held"] / sizes["n_experts"]
    layer = (
        attn + indexer + d * sizes["n_experts"]
        + met * 3 * d * sizes["d_expert"]
    )
    pairs = (
        sizes["n_head"] * hd * flops.mean_span(seq, 0, sizes["index_topk"])
        + nj * nc / 2 * flops.mean_span(seq, 0)
    )
    return {
        "multiplied_params": int(
            sizes["n_layer"] * layer + d * sizes["vocab_size"]
        ),
        "attention_pair_channels": sizes["n_layer"] * pairs,
    }


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(F32), tree)


def _rms(x, p, sizes):
    return _norm(x, p, "rmsnorm", sizes["norm_eps"])


def top_selection(scores, k):
    """bool like ``scores`` [..., Q, S] (``-inf`` at invisible keys):
    the min(visible, k) keys of largest score, ties to the lower s."""
    size = jnp.minimum(jnp.sum(jnp.isfinite(scores), -1), k)
    ranked = jnp.sort(scores, axis=-1, descending=True)
    kth = jnp.take_along_axis(ranked, size[..., None] - 1, axis=-1)
    above, ties = scores > kth, scores == kth
    room = size - jnp.sum(above, -1)
    return above | (ties & (jnp.cumsum(ties, -1) <= room[..., None]))


def index_scores(qi, ki, w):
    """qi [B, Q, J, C], ki [B, S, C], w [B, Q, J] -> I [B, Q, S]."""
    dots = jax.nn.relu(jnp.einsum("bqjc,bsc->bjqs", qi, ki))
    return jnp.einsum("bjqs,bqj->bqs", dots, w)


def _selecting_attention(q, k, v, qi, ki, w, sizes, q_block, forced):
    """Attention over each query's selection, q block by q block.
    Returns (out [B, S, H x hd], KL per query [B, S], selection
    statistics [B, S] each or None when free-running)."""
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    q_block = min(q_block, s)
    if s % q_block:
        raise ValueError(f"sequence {s} is not a multiple of {q_block}")
    kpos = jnp.arange(s)[None, :]
    topk = sizes["index_topk"]

    def rows(start):
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, start, q_block, 1)
        qpos = start + jnp.arange(q_block)[:, None]
        index = jnp.where(
            (kpos <= qpos)[None], index_scores(take(qi), ki, take(w)),
            -jnp.inf,
        )
        if forced is None:
            chosen, stats = top_selection(index, topk), None
        else:
            chosen = take(forced)
            stats = selection_stats(index, chosen, topk)
        scores = jnp.einsum("bqhd,bkhd->bhqk", take(q), k) * d ** -0.5
        probs = jax.nn.softmax(
            jnp.where(chosen[:, None], scores, -jnp.inf), axis=-1
        )
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        # the alignment term: both distributions live on the selection
        p = jnp.mean(probs, axis=1)
        log_i = jax.nn.log_softmax(
            jnp.where(chosen, index, -jnp.inf), axis=-1
        )
        kl = jnp.sum(
            jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_i),
                      0.0),
            axis=-1,
        )
        return out, kl, stats

    out, kl, stats = jax.lax.map(rows, jnp.arange(0, s, q_block))
    join = lambda a: jnp.moveaxis(a, 0, 1).reshape(b, s, *a.shape[3:])
    return (
        join(out).reshape(b, s, h * d), join(kl),
        None if stats is None else jax.tree.map(join, stats),
    )


def _routed(g, moe, sizes, ids):
    """g [T, d], ids [T, k] or None -> (this chip's part of the block's
    output [T, d], router logits [T, E], the balance term before its
    coefficient)."""
    n_exp, k = sizes["n_experts"], sizes["expert_top_k"]
    first, held = sizes["expert_offset"], sizes["n_experts_held"]
    logits = g @ moe["w_gate"].astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    if ids is None:
        ids = jax.lax.top_k(probs, k)[1]
    top = jnp.take_along_axis(probs, ids, axis=-1)
    if sizes["moe_renorm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    here = first + jnp.arange(held)
    # a token's weight for each expert held here: 0 where not chosen
    weight = jnp.sum(
        jnp.where(ids[:, :, None] == here, top[:, :, None], 0.0), axis=1
    )

    def expert(total, args):
        w_g, w_u, w_d, w_tok = args
        y = jax.nn.silu(g @ w_g.astype(F32)) * (g @ w_u.astype(F32))
        return total + (y @ w_d.astype(F32)) * w_tok[:, None], None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(g),
        (moe["w_gate_proj"], moe["w_up"], moe["w_down"], weight.T),
    )
    chosen = ids[:, :, None] == jnp.arange(n_exp)  # [T, k, E]
    share = jnp.mean(chosen.astype(F32), axis=(0, 1))  # over pairs: sums to 1
    balance = n_exp * jnp.sum(share * jnp.mean(probs, axis=0))
    return out, logits, balance


def forward(params, tokens, sizes, q_block=1024, choices=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, forced) with
    ``forced`` the objective's two terms and, under teacher forcing
    (``choices``: ``attn_selected`` bool [L, B, S, S] and
    ``moe_choices`` int32 [L, B, S, k]), the selection's statistics and
    the router logits."""
    b, s = tokens.shape
    nh, nkv, d = sizes["n_head"], sizes["n_kv_head"], sizes["d_model"]
    hd = sizes["head_dim"]
    nj, nc = sizes["index_n_heads"], sizes["index_head_dim"]
    theta = sizes["rope_theta"]
    choices = choices or {}
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(F32)

    def layer(x, inp):
        p, mask, ids = inp
        moe = p["moe"]  # cast expert by expert: a layer's are large
        attn, idx = _f32(p["attn"]), _f32(p["indexer"])
        h = _rms(x, _f32(p["ln1"]), sizes)
        q = _rms((h @ attn["wq"]).reshape(b, s, nh, hd), attn["q_norm"], sizes)
        k = _rms((h @ attn["wk"]).reshape(b, s, nkv, hd), attn["k_norm"], sizes)
        v = (h @ attn["wv"]).reshape(b, s, nkv, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        qi = _rope((h @ idx["wq"]).reshape(b, s, nj, nc), theta)
        ki = _rope(
            _rms(h @ idx["wk"], idx["k_norm"], sizes)[:, :, None], theta
        )[:, :, 0]
        w = (h @ idx["w"]) * (nj * nc) ** -0.5
        a, kl, stats = _selecting_attention(
            q, k, v, qi, ki, w, sizes, q_block, mask
        )
        x = x + a @ attn["wo"]
        g = _rms(x, _f32(p["ln2"]), sizes)
        m, logits, balance = _routed(
            g.reshape(b * s, d), moe, sizes,
            None if ids is None else ids.reshape(b * s, -1),
        )
        return x + m.reshape(b, s, d), {
            "kl": jnp.mean(kl), "selection": stats, "balance": balance,
            "router_logits": logits.reshape(b, s, -1),
        }

    x, out = jax.lax.scan(
        layer, x,
        (params["layers"], choices.get("attn_selected"),
         choices.get("moe_choices")),
    )
    x = _rms(x, _f32(params["final_norm"]), sizes)
    forced = {
        "indexer_loss": sizes["indexer_loss_coef"] * jnp.sum(out["kl"]),
        "moe_lb_loss": sizes["moe_aux_coef"] * jnp.sum(out["balance"]),
        "router_logits": out["router_logits"],
    }
    if out["selection"] is not None:
        forced["selection"] = out["selection"]
    return x @ params["lm_head"]["w"].astype(F32), forced


def _mean_ce(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(logz - tgt)


def loss_and_logits(params, batch, sizes, q_block=1024):
    """Free-running: the reference's own selection and routing. Mean
    next-token cross-entropy (no other term) and the logits."""
    logits, _ = forward(params, batch["tokens"], sizes, q_block)
    return _mean_ce(logits, batch["targets"]), logits


def loss_and_logits_selected(params, batch, sizes, q_block, choices):
    """Teacher-forced: every query attends to the keys
    ``choices["attn_selected"]`` names and every token goes to the
    experts ``choices["moe_choices"]`` names. Mean cross-entropy,
    logits, and ``forced``: the selection's statistics, the router
    logits, and the objective's other terms."""
    logits, forced = forward(params, batch["tokens"], sizes, q_block, choices)
    return _mean_ce(logits, batch["targets"]), logits, forced
