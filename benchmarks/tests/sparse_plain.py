"""A plain reference for a decoder whose attention selects its keys,
kept with the tests: the dense family's block
(``references/decoder_plain.py``: RMSNorm, rope, grouped-query
attention, SwiGLU) with, in every layer, a DeepSeek-Sparse-Attention
indexer (DeepSeek-V3.2-Exp's report; ``sa_config`` of
Kwai-Keye/Keye-VL-2.0-30B-A3B) in front of the attention. It is what
``tests/test_selected.py`` and the chip rehearsal of the ``selected``
comparison (PERF.md section 4, PR 36) were judged against; no committed
configuration names it. A configuration for such a model brings a
reference of its own in ``references/`` to this contract.

For a layer's normed input h and positions s <= t:

    qI_t,j = rope(h_t WI_q)_j  (j = 1..index_n_heads, index_head_dim channels)
    kI_s   = rope(rmsnorm(h_s WI_k))          (one key head)
    w_t    = h_t WI_w x (index_n_heads x index_head_dim)^-1/2
    I_t,s  = sum_j w_t,j relu(qI_t,j . kI_s)                  (the index score)
    S_t    = the min(t + 1, index_topk) visible keys of largest I_t,s,
             ties to the lower s (free-running), or the keys handed in
             (teacher-forced)
    o_t,h  = sum_{s in S_t} softmax_{s in S_t}(q_t,h . k_s,g(h) / sqrt(hd)) v_s,g(h)

with q and k under a per-head RMSNorm where ``sizes["qk_norm"]``. The
indexer's own term, per layer and summed over layers, is
``indexer_loss`` = ``indexer_loss_coef`` x mean over queries of
KL(p_t || softmax_{s in S_t} I_t,s), p_t the attention's probabilities
averaged over heads (they live on S_t). The MLP is a dense SwiGLU, or
``tests/moe_plain.py``'s dropless mixture where ``sizes["n_experts"]``.

Attention runs ``q_block`` query rows at a time, and so do the index
scores and the selection's statistics (``lib/selected.selection_stats``
on each block, stacked to [L, B, S]): no float [L, B, S, S] array is
ever whole.

Under teacher forcing the masks (and the expert ids) are the ONLY thing
taken from the program: hidden states, index scores, softmax, experts
and losses are this file's own.
"""

import jax
import jax.numpy as jnp

from benchmarks.lib.selected import selection_stats
from benchmarks.references.decoder_plain import F32, _norm, _rope
from benchmarks.tests.moe_plain import _experts, _mean_ce


def _rms(x, p, eps):
    return _norm(x, p, "rmsnorm", eps)


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
    topk, window = sizes["index_topk"], sizes.get("attn_window", 0)

    def rows(start):
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, start, q_block, 1)
        qpos = start + jnp.arange(q_block)[:, None]
        visible = kpos <= qpos
        if window:
            visible = visible & (qpos - kpos < window)
        index = jnp.where(
            visible[None], index_scores(take(qi), ki, take(w)), -jnp.inf
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
        # the indexer's alignment term: both distributions live on the
        # selection; 0 log 0 = 0
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


def forward(params, tokens, sizes, q_block=1024, choices=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, forced) with
    ``forced`` the terms of the objective (``indexer_loss``, and the
    router's where the model routes), and under teacher forcing
    (``choices``: ``attn_selected`` bool [L, B, S, S], ``moe_choices``
    int32 [L, B, S, k] where it routes) the selection's statistics and
    the router logits."""
    b, s = tokens.shape
    nh, d = sizes["n_head"], sizes["d_model"]
    nkv = sizes.get("n_kv_head") or nh
    hd = sizes.get("head_dim") or d // nh
    nj, nc = sizes["index_n_heads"], sizes["index_head_dim"]
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]
    routes = bool(sizes.get("n_experts"))
    choices = choices or {}
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(F32)

    def layer(x, inp):
        p, mask, ids = inp
        moe = p.get("moe")  # cast expert by expert: a layer's are large
        p = jax.tree.map(
            lambda w: w.astype(F32), {n: v for n, v in p.items() if n != "moe"}
        )
        attn, idx = p["attn"], p["indexer"]
        h = _rms(x, p["ln1"], eps)
        q = (h @ attn["wq"]).reshape(b, s, nh, hd)
        k = (h @ attn["wk"]).reshape(b, s, nkv, hd)
        v = (h @ attn["wv"]).reshape(b, s, nkv, hd)
        if sizes.get("qk_norm"):
            q, k = _rms(q, attn["q_norm"], eps), _rms(k, attn["k_norm"], eps)
        q, k = _rope(q, theta), _rope(k, theta)
        qi = _rope((h @ idx["wq"]).reshape(b, s, nj, nc), theta)
        ki = _rope(_rms(h @ idx["wk"], idx["k_norm"], eps)[:, :, None], theta)
        w = (h @ idx["w"]) * (nj * nc) ** -0.5
        a, kl, stats = _selecting_attention(
            q, k, v, qi, ki[:, :, 0], w, sizes, q_block, mask
        )
        x = x + a @ attn["wo"]
        h = _rms(x, p["ln2"], eps)
        out = {"kl": jnp.mean(kl), "selection": stats}
        if routes:
            m, logits, balance, z = _experts(
                h.reshape(b * s, d), moe, sizes,
                None if ids is None else ids.reshape(b * s, -1),
            )
            m = m.reshape(b, s, d)
            out.update(
                router_logits=logits.reshape(b, s, -1), balance=balance, z=z
            )
        else:
            mlp = p["mlp"]
            m = (jax.nn.silu(h @ mlp["w_gate"]) * (h @ mlp["w_up"])) \
                @ mlp["w_down"]
        return x + m, out

    x, out = jax.lax.scan(
        layer, x,
        (params["layers"], choices.get("attn_selected"),
         choices.get("moe_choices")),
    )
    x = _rms(x, jax.tree.map(lambda w: w.astype(F32), params["final_norm"]),
             eps)
    if sizes["tie_embeddings"]:
        head = params["embed"]["tokens"].astype(F32).T
    else:
        head = params["lm_head"]["w"].astype(F32)
    forced = {"indexer_loss": sizes["indexer_loss_coef"] * jnp.sum(out["kl"])}
    if out["selection"] is not None:
        forced["selection"] = out["selection"]
    if routes:
        forced.update(
            router_logits=out["router_logits"],
            moe_lb_loss=sizes["moe_aux_coef"] * jnp.sum(out["balance"]),
            moe_z_loss=sizes["moe_z_coef"] * jnp.sum(out["z"]),
        )
    return x @ head, forced


def loss_and_logits(params, batch, sizes, q_block=1024):
    """Free-running: the reference's own selection (and routing). Mean
    next-token cross-entropy (no other term) and the logits."""
    logits, _ = forward(params, batch["tokens"], sizes, q_block)
    return _mean_ce(logits, batch["targets"]), logits


def loss_and_logits_selected(params, batch, sizes, q_block, choices):
    """Teacher-forced: every query attends to the keys
    ``choices["attn_selected"]`` names (and every token goes to the
    experts ``choices["moe_choices"]`` names). Mean cross-entropy,
    logits, and ``forced``: the selection's statistics, the router
    logits where it routes, and the objective's other terms."""
    logits, forced = forward(params, batch["tokens"], sizes, q_block, choices)
    return _mean_ce(logits, batch["targets"]), logits, forced
