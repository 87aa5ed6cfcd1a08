"""Plain reference for MiniCPM-SALA (``model_type: minicpm_sala``;
https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json): the
forward pass and the mean next-token loss in straightforward
``jax.numpy`` and float32, written LAYER BY LAYER. No kernel, no chunked
scan, no scan over layers, no remat. The caller runs it under
``jax.default_matmul_precision("highest")``.

The stream, with s = ``scale_depth`` / sqrt(the PUBLISHED depth, 32)
whatever depth is run (``sizes["residual_scale"]``):

    x_0 = scale_emb E[token]                                  (12)
    x <- x + s mixer_i(rms(x; g1_i))
    x <- x + s W_down (silu(h W_gate) * (h W_up)),   h = rms(x; g2_i)
    logits = (rms(x; g_f) dim_model_base / d) W_head   (256 / 4096, untied)

``mixer_types`` names each layer's mixer. With h the layer's normed
input:

``lightning-attn`` (``L``): q, k, v = h W_q, h W_k, h W_v, n_head heads
of d_head each; an RMSNorm over each head's channels of q and of k
(``qk_norm``; one learned scale of d_head for all heads), then rope at
``rope_theta`` on both (``lightning_use_rope``; rotate-half pairs);

    S_t = lambda_h S_{t-1} + k_t^T v_t,   o_t = q_t S_t / sqrt(d_head)
    out = (rms(o) * sigmoid(h W_g)) W_o

computed here in its exact quadratic form, ``o_t = sum_{s<=t}
lambda_h^(t-s) (q_t . k_s) v_s / sqrt(d_head)``, a block of queries at
a time: the program's chunked scan and its kernels are another algorithm
for the same numbers. ``log lambda_h = -2^(-8 h / n_head)``, h = 1 ..
n_head (ASSUMED: ``config.json`` has no key for the decay; Lightning
Attention's fixed slopes, the same in every layer). The output norm is
an RMSNorm over the WHOLE read-out, the n_head x d_head channels of a
token together, with a learned scale a channel (ASSUMED;
``use_output_norm``; Lightning Attention's own form — a statistic over
one head's channels would make a token's output a unit vector however
small its read-out, whose sign at the first token is the sign of
q_0 . k_0), then the gate (``use_output_gate``).

``minicpm4`` (``S``; InfLLM-v2's block-sparse attention, MiniCPM4's
``sparse_config``, which the catalog row omits: ASSUMED, each size under
a name of its own): q = h W_q (n_head x d_head), k, v = h W_k, h W_v
(n_kv_head x d_head), the per-head RMSNorm on q and k, NO rope
(``attn_use_rope`` false). For block size b, a query t in block own =
t // b, and KV head g with its n_head / n_kv_head query heads:

    k~_j    = mean of k_s over s in [stride j, stride j + window)
    p_t,h,j = softmax over the pooled keys whose window has ENDED
              (stride j + window - 1 <= t) of q_t,h . k~_j / sqrt(d_head)
    P_t,g,j = sum of p_t,h,j over the query heads h of g
    I_t,g,u = max of P_t,g,j over the ended pooled keys that overlap
              block u; 0 where none has ended; -inf for u > own
    F_t     = the units u <= own with u < select_init_blocks or
              u > own - select_local / b     (taken whatever I says)
    S_t,g   = F_t and the min(free, k - |F_t|) free units of largest
              I_t,g,u, ties to the lower u (free-running), or the units
              handed in (teacher-forced)
    o_t,h   = softmax attention of q_t,h over the keys s <= t of the
              blocks in S_t,g(h)
    out     = (o * sigmoid(h W_g)) W_o        (``attn_use_output_gate``)

A sequence of at most ``select_dense_len`` tokens attends to every
earlier key. Nothing of the selection is differentiated or has a
parameter. The rule is ``tests/block_plain.py``'s (PR 56, rehearsed on
the chip), written out again here so that the reference stands alone.

Under teacher forcing the units are the ONLY thing taken from the
program (``choices["attn_selected"]`` bool [S layers x n_kv_head, B, S,
S / b], layer-major and group-minor); ``forced`` carries the
selection's statistics at this reference's own block scores with the
forced units named (``lib/selected.selection_stats``) and two scalars
under the names of the program's step metrics, which
``routed.objective_checks`` holds to ``ROUTER_LOSS_TOL``.
``sparse_attn_out_ms``: the mean square of the sparse attention's
output o before gate and ``W_o``, mean over the S layers: an attention
that ignores its selection moves it by tens of percent where the
logits, a hundredth of whose stream the one sparse layer is, hardly
move. ``lightning_fast_out_ms``: the mean square of the recurrence's
read-out o, before its norm, over the quarter of the heads that forget
fastest (the first n_head // 4, at least one), mean over the L layers:
normed whole those heads hold about a hundredth of the read-out's
energy, so the logits pass a running log-decay kept in bf16, whose sum
passes 200 inside a chunk there; this does not (PERF.md section 6).

It reads the program's parameter tree by name (``layers`` holding the
parts kind by kind: ``sparse``, ``lightning``, ``mlp``, each part with
its own norm ``ln``; a kind's parts may lie in several stacks end to
end: ``_part``) and the configuration file's ``sizes``.

``required_terms``: the matrices kind by kind; the recurrence's state
update and read-out as ``nemotron_h_plain`` enters Mamba-2's, 2 x
(n_head d_head) x d_head multiplied parameters a token and lightning
layer; a sparse layer's pairs by ``mean_span(seq, topk, block)`` and its
pooled scorer by ``lib/flops.py``'s clause (heads x score channels / 2
x mean_span(seq) / stride).
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import flops
from benchmarks.lib.selected import selection_stats
from benchmarks.references.decoder_plain import F32, _attention, _norm, _rope

KINDS = {"S": "sparse", "L": "lightning"}


def _layers(sizes):
    """The mixer's letter of every layer, from ``layer_pattern``."""
    pattern = sizes["layer_pattern"]
    if (
        len(pattern) != 2 * sizes["n_layer"]
        or set(pattern[1::2]) != {"-"} or set(pattern[0::2]) - set(KINDS)
    ):
        raise ValueError(
            f"{pattern!r} is not {sizes['n_layer']} layers of a mixer "
            "(S or L) and an MLP (-) each"
        )
    return pattern[0::2]


def selects(sizes, seq):
    """Whether a sparse layer's queries choose at ``seq`` tokens."""
    return seq > sizes["select_dense_len"]


def required_terms(sizes, seq):
    """The two terms of ``lib/flops.py``'s convention: layers counted
    kind by kind, the vocabulary as sliced."""
    d, hd = sizes["d_model"], sizes["d_head"]
    d_attn = sizes["n_head"] * hd
    mixer = {
        # q, o and the gate; k and v at the KV heads
        "S": 3 * d * d_attn + 2 * d * sizes["n_kv_head"] * hd,
        # q, k, v, gate, o; the state's update and its read-out
        "L": 5 * d * d_attn + 2 * d_attn * hd,
    }
    mlp = 3 * d * sizes["d_ff"]
    kinds = _layers(sizes)
    if selects(sizes, seq):
        pairs = d_attn * flops.mean_span(
            seq, topk=sizes["index_topk"], block=sizes["select_block"]
        ) + d_attn / 2 * flops.mean_span(seq) / sizes["pool_stride"]
    else:
        pairs = d_attn * flops.mean_span(seq)
    return {
        "multiplied_params": int(
            sum(mixer[c] + mlp for c in kinds) + d * sizes["vocab_size"]
        ),
        "attention_pair_channels": kinds.count("S") * pairs,
    }


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(F32), tree)


def _rms(x, p, sizes):
    return _norm(x, p, "rmsnorm", sizes["norm_eps"])


def log_decay(n_head):
    """float32 [n_head]: log lambda_h = -2^(-8 h / n_head), h = 1.."""
    return -(2.0 ** (-8.0 * np.arange(1, n_head + 1) / n_head)).astype(
        np.float32
    )


def lightning_attention(q, k, v, decay, q_block):
    """o_t = sum_{s <= t} exp((t - s) decay_h) (q_t . k_s) v_s: q, k, v
    [B, S, H, D], decay [H] (log lambda) -> [B, S, H, D], ``q_block``
    query rows at a time."""
    b, s, h, d = q.shape
    q_block = min(q_block, s)
    if s % q_block:
        raise ValueError(f"sequence {s} is not a multiple of {q_block}")
    kpos = jnp.arange(s)[None, :]

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=1)
        gap = (start + jnp.arange(q_block))[:, None] - kpos  # t - s
        weight = jnp.where(
            gap >= 0,
            jnp.exp(jnp.maximum(gap, 0).astype(F32) * decay[:, None, None]),
            0.0,
        )  # [H, Q, S]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * weight
        return jnp.einsum("bhqk,bkhd->bqhd", scores, v)

    out = jax.lax.map(rows, jnp.arange(0, s, q_block))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def _lightning(h, p, sizes, q_block):
    """h [B, S, d] -> (the lightning mixer's output [B, S, d], the mean
    square of its fastest quarter of heads' read-out before the norm)."""
    b, s, _ = h.shape
    nh, hd = sizes["n_head"], sizes["d_head"]
    q = _rms((h @ p["wq"]).reshape(b, s, nh, hd), p["q_norm"], sizes)
    k = _rms((h @ p["wk"]).reshape(b, s, nh, hd), p["k_norm"], sizes)
    v = (h @ p["wv"]).reshape(b, s, nh, hd)
    q, k = (_rope(a, sizes["rope_theta"]) for a in (q, k))
    o = lightning_attention(
        q * hd ** -0.5, k, v, jnp.asarray(log_decay(nh)), q_block
    )
    fast = jnp.mean(jnp.square(o[:, :, :max(1, nh // 4)]))
    o = _rms(o.reshape(b, s, nh * hd), p["o_norm"], sizes)
    return (o * jax.nn.sigmoid(h @ p["wg"])) @ p["wo"], fast


# ---- the selection of blocks ------------------------------------------------

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
    I [B, KV, Q, U], ``-inf`` at the units above the query's own."""
    b, _, h, d = q.shape
    block, window, stride = (
        sizes["select_block"], sizes["pool_window"], sizes["pool_stride"]
    )
    n_pooled, kv = pooled.shape[1:3]
    pooled = jnp.repeat(pooled, h // kv, axis=2)
    ended = (
        stride * jnp.arange(n_pooled) + window - 1
    )[None, :] <= qpos[:, None]
    dots = jnp.einsum("bqhd,bphd->bhqp", q, pooled) * d ** -0.5
    p = jax.nn.softmax(jnp.where(ended, dots, -1e30), axis=-1)
    p = jnp.where(ended, p, 0.0)  # a query no window has ended for: zeros
    p = jnp.sum(p.reshape(b, kv, h // kv, *p.shape[2:]), axis=2)
    over = overlaps(n_units, n_pooled, block, window, stride)
    score = jnp.max(jnp.where(over, p[..., None, :], 0.0), axis=-1)
    seen = jnp.arange(n_units)[None, :] <= (qpos // block)[:, None]
    return jnp.where(seen, score, -jnp.inf)


def selecting_attention(q, k, v, sizes, q_block, chosen_units):
    """Attention over each query's blocks, q block by q block. Returns
    (out [B, S, H, D], selection statistics [KV, B, S] each, or None
    when free-running); ``chosen_units`` bool [KV, B, S, U] or None."""
    b, s, h, d = q.shape
    block, topk = sizes["select_block"], sizes["index_topk"]
    kv = k.shape[2]
    if s % block:
        raise ValueError(f"sequence {s} is no whole number of blocks")
    n_units = s // block
    pooled = pooled_keys(k, sizes["pool_window"], sizes["pool_stride"])
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
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
        keys = jnp.repeat(keys, h // kv, axis=1)  # [B, H, Q, S]
        scores = jnp.einsum("bqhd,bkhd->bhqk", take(q), k) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(keys, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v), stats

    out, stats = jax.lax.map(rows, jnp.arange(0, s, q_block))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)
    if stats is not None:  # [blocks, B, KV, Q] -> [KV, B, S]
        stats = jax.tree.map(
            lambda a: jnp.transpose(a, (2, 1, 0, 3)).reshape(kv, b, s), stats
        )
    return out, stats


def _sparse(h, p, sizes, q_block, chosen):
    """h [B, S, d] -> (the sparse mixer's output [B, S, d], the
    selection's statistics or None, the mean square of the attention's
    output before gate and ``W_o``)."""
    b, s, _ = h.shape
    nh, nkv, hd = sizes["n_head"], sizes["n_kv_head"], sizes["d_head"]
    q = _rms((h @ p["wq"]).reshape(b, s, nh, hd), p["q_norm"], sizes)
    k = _rms((h @ p["wk"]).reshape(b, s, nkv, hd), p["k_norm"], sizes)
    v = (h @ p["wv"]).reshape(b, s, nkv, hd)
    if selects(sizes, s):
        o, stats = selecting_attention(q, k, v, sizes, q_block, chosen)
    else:
        o, stats = _attention(q, k, v, 0, q_block), None
    o = o.reshape(b, s, nh * hd)
    out = (o * jax.nn.sigmoid(h @ p["wg"])) @ p["wo"]
    return out, stats, jnp.mean(jnp.square(o))


def _part(stacks, name, k):
    """The k-th part of a kind, float32: its stacks ``name``,
    ``name.1``, ... end to end."""
    stack, more = name, 0
    while True:
        n = jax.tree.leaves(stacks[stack])[0].shape[0]
        if k < n:
            return _f32(jax.tree.map(lambda t: t[k], stacks[stack]))
        k, more = k - n, more + 1
        stack = f"{name}.{more}"


def forward(params, tokens, sizes, q_block=512, choices=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, forced):
    ``forced["sparse_attn_out_ms"]`` and
    ``forced["lightning_fast_out_ms"]`` where the model has the kind
    and, under teacher forcing (``choices["attn_selected"]``),
    ``forced["selection"]``, the statistics [S layers x KV, B, S]."""
    b, s = tokens.shape
    kv = sizes["n_kv_head"]
    if sizes["select_groups"] != kv:
        raise ValueError("a sparse layer selects once a KV head")
    stacks = params["layers"]
    scale = sizes["residual_scale"]
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(F32)
    x = x * sizes["scale_emb"]
    units = (choices or {}).get("attn_selected")
    if units is not None:
        units = units.reshape(-1, kv, *units.shape[1:])
    seen = dict.fromkeys(KINDS, 0)
    stats, squares = [], {"S": [], "L": []}
    for i, kind in enumerate(_layers(sizes)):
        mixer = _part(stacks, KINDS[kind], seen[kind])
        h = _rms(x, mixer["ln"], sizes)
        if kind == "S":
            chosen = None if units is None else units[seen[kind]]
            out, stat, square = _sparse(
                h, mixer["attn"], sizes, q_block, chosen
            )
            if stat is not None:
                stats.append(stat)
        else:
            out, square = _lightning(h, mixer["lin"], sizes, q_block)
        squares[kind].append(square)
        seen[kind] += 1
        x = x + scale * out
        part = _part(stacks, "mlp", i)
        h, mlp = _rms(x, part["ln"], sizes), part["mlp"]
        x = x + scale * (
            (jax.nn.silu(h @ mlp["w_gate"]) * (h @ mlp["w_up"]))
            @ mlp["w_down"]
        )
    x = _rms(x, _f32(params["final_norm"]), sizes) * sizes["logit_scale"]
    forced = {
        name: jnp.mean(jnp.stack(squares[kind]))
        for kind, name in (
            ("S", "sparse_attn_out_ms"), ("L", "lightning_fast_out_ms")
        )
        if squares[kind]
    }
    if stats:  # a row a selection: layer-major, group-minor
        forced["selection"] = jax.tree.map(
            lambda *a: jnp.concatenate(a), *stats
        )
    return x @ params["lm_head"]["w"].astype(F32), forced


def _mean_ce(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(logz - tgt)


def loss_and_logits(params, batch, sizes, q_block=512):
    """Free-running: the reference's own selection. Mean next-token
    cross-entropy and the logits."""
    logits, _ = forward(params, batch["tokens"], sizes, q_block)
    return _mean_ce(logits, batch["targets"]), logits


def loss_and_logits_selected(params, batch, sizes, q_block, choices):
    """Teacher-forced: every query attends, KV head by KV head, to the
    blocks ``choices["attn_selected"]`` names. Mean cross-entropy,
    logits, and ``forced``: ``selection`` and the two mean squares."""
    logits, forced = forward(params, batch["tokens"], sizes, q_block, choices)
    return _mean_ce(logits, batch["targets"]), logits, forced
