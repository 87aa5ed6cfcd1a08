"""A plain reference for a decoder whose attention selects BLOCKS of
keys, kept with the tests: InfLLM-v2's block-sparse attention as
MiniCPM4 and MiniCPM-SALA's ``minicpm4`` layers run it
(``sparse_config``; openbmb/MiniCPM-SALA ``config.json``), in the dense
family's block (RMSNorm, grouped-query attention, SwiGLU). It is what
``tests/test_selected.py`` and the chip rehearsal of the ``selected``
comparison in UNITS (PERF.md section 4, PR 56) were judged against; no
committed configuration names it. A configuration for such a model
brings a reference of its own in ``references/`` to this contract.

For a layer's normed input h, block size b, a query t in block
own = t // b, and KV head g with its n_head / G query heads:

    k~_j    = mean of k_s over s in [stride j, stride j + window)  (a pooled key)
    p_t,h,j = softmax over the pooled keys whose window has ENDED
              (stride j + window - 1 <= t) of q_t,h . k~_j / sqrt(hd)
    P_t,g,j = sum of p_t,h,j over the query heads h of g
    I_t,g,u = max of P_t,g,j over the ended pooled keys that overlap
              block u = [b u, b u + b); 0 where none has ended; -inf
              for u > own (the units the query cannot see)
    F_t     = the units u <= own with u < select_init_blocks or
              u > own - select_local / b          (taken whatever I says)
    S_t,g   = F_t and the min(free, k - |F_t|) free units of largest
              I_t,g,u, ties to the lower u (free-running), or the units
              handed in (teacher-forced)
    o_t,h   = softmax attention of q_t,h over the keys s <= t of the
              blocks in S_t,g(h)

with q and k under a per-head RMSNorm where ``sizes["qk_norm"]``, rope
only where ``sizes["pos"]`` says so (the model's sparse layers have
none), ``o * sigmoid(h W_g)`` before ``W_o`` where
``sizes["attn_gate"]``, both parts' outputs times
``sizes["residual_scale"]``, the embedding times ``sizes["scale_emb"]``
and the last hidden state times ``sizes["logit_scale"]`` (absent: 1).
No parameter of its own scores the blocks and no loss aligns them.

``sizes["select_groups"]`` = G is the selections a layer makes: the KV
heads, or 1 (every query head's p summed into one selection).

Attention, scores and the selection's statistics
(``lib/selected.selection_stats`` with the forced units) run ``q_block``
query rows at a time and are stacked to [L x G, B, S]. Under teacher
forcing the units are the ONLY thing taken from the program.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.selected import selection_stats
from benchmarks.references.decoder_plain import F32, _norm, _rope
from benchmarks.tests.moe_plain import _mean_ce


def _rms(x, p, eps):
    return _norm(x, p, "rmsnorm", eps)


def pooled_keys(k, window, stride):
    """k [B, S, KV, D] -> [B, P, KV, D]: pooled key j the mean of keys
    [stride j, stride j + window); P whole windows."""
    count = (k.shape[1] - window) // stride + 1
    at = stride * np.arange(count)[:, None] + np.arange(window)[None, :]
    return jnp.mean(k[:, at], axis=2)


def overlaps(n_units, n_pooled, block, window, stride):
    """bool [U, P]: pooled key j shares a key with block u."""
    first = stride * np.arange(n_pooled)[None, :]
    start = block * np.arange(n_units)[:, None]
    return (first < start + block) & (first + window > start)


def forced_units(qpos, n_units, sizes):
    """bool [Q, U]: the units query t takes whatever their score, among
    those it sees: the initial blocks and its local window's."""
    block = sizes["select_block"]
    own = (qpos // block)[:, None]
    unit = jnp.arange(n_units)[None, :]
    local = sizes["select_local"] // block
    rule = (unit < sizes["select_init_blocks"]) | (unit > own - local)
    return rule & (unit <= own)


def top_units(scores, forced, k):
    """bool like ``scores`` [..., Q, U] (``-inf`` at the units a query
    cannot see): the forced units it sees and, of the others, the
    min(free, k - forced) of largest score, ties to the lower unit."""
    forced = forced & jnp.isfinite(scores)
    free = jnp.where(forced, -jnp.inf, scores)
    live = jnp.isfinite(free)
    size = jnp.minimum(
        jnp.sum(live, -1), jnp.maximum(k - jnp.sum(forced, -1), 0)
    )
    ranked = jnp.sort(free, axis=-1, descending=True)
    kth = jnp.take_along_axis(
        ranked, jnp.maximum(size, 1)[..., None] - 1, axis=-1
    )
    above, ties = live & (free > kth), live & (free == kth)
    room = size - jnp.sum(above, -1)
    best = above | (ties & (jnp.cumsum(ties, -1) <= room[..., None]))
    return forced | (best & (size > 0)[..., None])


def unit_scores(q, pooled, qpos, sizes, n_units):
    """q [B, Q, H, D] at positions qpos [Q], pooled [B, P, KV, D] ->
    I [B, G, Q, U], ``-inf`` at the units above the query's own."""
    b, _, h, d = q.shape
    block, groups = sizes["select_block"], sizes["select_groups"]
    window, stride = sizes["pool_window"], sizes["pool_stride"]
    n_pooled = pooled.shape[1]
    pooled = jnp.repeat(pooled, h // pooled.shape[2], axis=2)
    ended = (
        stride * jnp.arange(n_pooled) + window - 1
    )[None, :] <= qpos[:, None]
    dots = jnp.einsum("bqhd,bphd->bhqp", q, pooled) * d ** -0.5
    p = jax.nn.softmax(jnp.where(ended, dots, -1e30), axis=-1)
    p = jnp.where(ended, p, 0.0)  # a query no window has ended for: zeros
    p = jnp.sum(p.reshape(b, groups, h // groups, *p.shape[2:]), axis=2)
    over = overlaps(n_units, n_pooled, block, window, stride)
    score = jnp.max(jnp.where(over, p[..., None, :], 0.0), axis=-1)
    seen = jnp.arange(n_units)[None, :] <= (qpos // block)[:, None]
    return jnp.where(seen, score, -jnp.inf)


def _selecting_attention(q, k, v, sizes, q_block, chosen_units):
    """Attention over each query's blocks, q block by q block. Returns
    (out [B, S, H x hd], selection statistics [G, B, S] each or None
    when free-running); ``chosen_units`` bool [G, B, S, U] or None."""
    b, s, h, d = q.shape
    block, groups, topk = (
        sizes["select_block"], sizes["select_groups"], sizes["index_topk"]
    )
    if s % block:
        raise ValueError(f"sequence {s} is no whole number of blocks")
    n_units = s // block
    pooled = pooled_keys(k, sizes["pool_window"], sizes["pool_stride"])
    rep = h // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    q_block = min(q_block, s)
    if s % q_block:
        raise ValueError(f"sequence {s} is not a multiple of {q_block}")
    kpos = jnp.arange(s)[None, :]

    def rows(start):
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, start, q_block, 1)
        qpos = start + jnp.arange(q_block)
        index = unit_scores(take(q), pooled, qpos, sizes, n_units)
        forced = forced_units(qpos, n_units, sizes)
        if chosen_units is None:
            chosen, stats = top_units(index, forced, topk), None
        else:
            chosen = jnp.moveaxis(
                jax.lax.dynamic_slice_in_dim(chosen_units, start, q_block, 2),
                0, 1,
            )
            stats = selection_stats(index, chosen, topk, forced)
        # units to keys, and the causal mask inside the query's own block
        keys = jnp.repeat(chosen, block, axis=-1) & (kpos <= qpos[:, None])
        keys = jnp.repeat(keys, h // groups, axis=1)  # [B, H, Q, S]
        scores = jnp.einsum("bqhd,bkhd->bhqk", take(q), k) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(keys, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v), stats

    out, stats = jax.lax.map(rows, jnp.arange(0, s, q_block))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h * d)
    if stats is not None:  # [blocks, B, G, Q] -> [G, B, S]
        stats = jax.tree.map(
            lambda a: jnp.transpose(a, (2, 1, 0, 3)).reshape(groups, b, s),
            stats,
        )
    return out, stats


def forward(params, tokens, sizes, q_block=512, choices=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, forced) with
    ``forced["selection"]`` the selection's statistics [L x G, B, S]
    under teacher forcing (``choices["attn_selected"]`` bool
    [L x G, B, S, S / b]) and nothing else: the objective has no term
    beside the cross-entropy."""
    b, s = tokens.shape
    nh, d = sizes["n_head"], sizes["d_model"]
    nkv = sizes.get("n_kv_head") or nh
    hd = sizes.get("head_dim") or d // nh
    groups, eps = sizes["select_groups"], sizes["norm_eps"]
    scale = sizes.get("residual_scale", 1.0)
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(F32)
    x = x * sizes.get("scale_emb", 1.0)
    units = (choices or {}).get("attn_selected")
    if units is not None:
        units = units.reshape(-1, groups, *units.shape[1:])

    def layer(x, inp):
        p, chosen = inp
        p = jax.tree.map(lambda w: w.astype(F32), p)
        attn, mlp = p["attn"], p["mlp"]
        h = _rms(x, p["ln1"], eps)
        q = (h @ attn["wq"]).reshape(b, s, nh, hd)
        k = (h @ attn["wk"]).reshape(b, s, nkv, hd)
        v = (h @ attn["wv"]).reshape(b, s, nkv, hd)
        if sizes.get("qk_norm"):
            q, k = _rms(q, attn["q_norm"], eps), _rms(k, attn["k_norm"], eps)
        if sizes.get("pos") == "rope":
            q, k = (_rope(a, sizes["rope_theta"]) for a in (q, k))
        a, stats = _selecting_attention(q, k, v, sizes, q_block, chosen)
        if sizes.get("attn_gate"):
            a = a * jax.nn.sigmoid(h @ attn["wg"])
        x = x + scale * (a @ attn["wo"])
        h = _rms(x, p["ln2"], eps)
        m = (jax.nn.silu(h @ mlp["w_gate"]) * (h @ mlp["w_up"])) \
            @ mlp["w_down"]
        return x + scale * m, stats

    x, stats = jax.lax.scan(layer, x, (params["layers"], units))
    x = _rms(x, jax.tree.map(lambda w: w.astype(F32), params["final_norm"]),
             eps)
    if sizes["tie_embeddings"]:
        head = params["embed"]["tokens"].astype(F32).T
    else:
        head = params["lm_head"]["w"].astype(F32)
    forced = {}
    if stats is not None:  # [L, G, B, S] -> a row a selection
        forced["selection"] = jax.tree.map(
            lambda a: a.reshape(-1, b, s), stats
        )
    return (x * sizes.get("logit_scale", 1.0)) @ head, forced


def loss_and_logits(params, batch, sizes, q_block=512):
    """Free-running: the reference's own selection. Mean next-token
    cross-entropy and the logits."""
    logits, _ = forward(params, batch["tokens"], sizes, q_block)
    return _mean_ce(logits, batch["targets"]), logits


def loss_and_logits_selected(params, batch, sizes, q_block, choices):
    """Teacher-forced: every query attends, KV head by KV head, to the
    blocks ``choices["attn_selected"]`` names. Mean cross-entropy,
    logits, and ``forced["selection"]``."""
    logits, forced = forward(params, batch["tokens"], sizes, q_block, choices)
    return _mean_ce(logits, batch["targets"]), logits, forced
