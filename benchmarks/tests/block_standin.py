"""A stand-in for a program whose attention selects BLOCKS of keys, kept
with the tests and no part of the benchmark: ``tests/block_plain.py``'s
equations as a program on the chip would compute them. bf16 parameters
and hidden states; every matmul on bf16 operands accumulating in
float32; norms, softmax, the gate's sigmoid and the residual scale in
float32; the pooled keys a float32 mean rounded to bf16, the pooled-key
product on bf16 operands with a float32 sum; the softmax over the ended
pooled keys, the sum over a KV head's query heads, the max over a
block's pooled keys (strided slices of the padded row; the reference
masks an overlap matrix) and the top-k (by rank, two argsorts; the
reference sorts the values and cuts at the k-th) in float32; attention
in q blocks under the chosen blocks' keys. It hands over what
``lib/selected.py`` asks of a program that selects by blocks:
``aux["attn_selected"]`` bool [L x G, B, S, S / b], layer-major and
group-minor.

The program proper selects keys, one selection a layer
(``models/decoder.py``), and a ``benchmark`` PR may not edit it: what
the limits of ``selected`` allow a selection by blocks was read from
this file on the chip (``tests/rehearse_selected.py --shape sala``;
PERF.md section 4, PR 56). ``tests/defects.py`` patches the small
functions below, which are looked up when ``forward`` is traced.
"""

import jax
import jax.numpy as jnp

from benchmarks.tests import sparse_standin
from benchmarks.tests.sparse_standin import BF16, F32, _mm, _rms

# MiniCPM-SALA's sparse (``minicpm4``) layer as data: ``config.json`` of
# openbmb/MiniCPM-SALA for the widths, MiniCPM4's ``sparse_config`` for
# the selection (the catalog row omits it and says "block top-64"), one
# eighth of the 73,448-row vocabulary: the widths of the chip rehearsal
SALA_WIDTHS = {
    "n_layer": 1, "d_model": 4096, "n_head": 32, "n_kv_head": 2,
    "head_dim": 128, "qk_norm": True, "attn_gate": True, "d_ff": 16384,
    "vocab_size": 9181, "norm_eps": 1e-6, "tie_embeddings": False,
    "index_topk": 64, "select_block": 64, "select_groups": 2,
    "pool_window": 32, "pool_stride": 16, "select_init_blocks": 1,
    "select_local": 2048,
}
# the model's three multipliers (``scale_emb`` 12 on the embedding,
# ``scale_depth`` 1.4 over sqrt(32 layers) on both parts' outputs,
# ``dim_model_base`` 256 of 4096 before the head), which ISSUE 56's
# shape leaves out: at seeded weights of deviation 0.02 they leave the
# attention about a hundredth of the stream (``--multipliers``; PERF.md
# section 7, PR 56)
SALA_MULTIPLIERS = {
    "scale_emb": 12.0, "residual_scale": 1.4 / 32 ** 0.5,
    "logit_scale": 256 / 4096,
}


def init(key, sizes, std=0.02):
    """Seeded bf16 parameters in the program's layout (per-layer tensors
    stacked on axis 0), made on the device in one call."""
    n, d, ff = sizes["n_layer"], sizes["d_model"], sizes["d_ff"]
    nh, nkv, hd = sizes["n_head"], sizes["n_kv_head"], sizes["head_dim"]
    vocab = sizes["vocab_size"]
    shapes = {
        "embed/tokens": (vocab, d), "lm_head/w": (d, vocab),
        "attn/wq": (n, d, nh * hd), "attn/wk": (n, d, nkv * hd),
        "attn/wv": (n, d, nkv * hd), "attn/wg": (n, d, nh * hd),
        "attn/wo": (n, nh * hd, d),
        "mlp/w_gate": (n, d, ff), "mlp/w_up": (n, d, ff),
        "mlp/w_down": (n, ff, d),
    }

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        return {
            name: (std * jax.random.normal(k, shape, F32)).astype(BF16)
            for k, (name, shape) in zip(keys, sorted(shapes.items()))
        }

    flat = make(key)
    ones = lambda *shape: {"scale": jnp.ones(shape, BF16)}
    group = lambda g: {
        k.split("/")[1]: v for k, v in flat.items() if k.startswith(g + "/")
    }
    return {
        "embed": group("embed"),
        "lm_head": group("lm_head"),
        "final_norm": ones(d),
        "layers": {
            "ln1": ones(n, d), "ln2": ones(n, d),
            "attn": dict(group("attn"), q_norm=ones(n, hd), k_norm=ones(n, hd)),
            "mlp": group("mlp"),
        },
    }


# ---- what the defects patch ------------------------------------------------

def _pool_inputs(q, pooled):
    """The query and the pooled keys as the score product reads them."""
    return q, pooled


def _live_pooled(ended, sizes):
    """bool [Q, P]: the pooled keys a query scores, of those whose
    window has ended."""
    return ended


def _head_sum(p):
    """[B, G, heads of a KV head, Q, P] -> [B, G, Q, P]."""
    return jnp.sum(p, axis=2)


def _block_reduce(p):
    """[..., U, pooled keys that overlap a block] -> [..., U]."""
    return jnp.max(p, axis=-1)


def _forced_units(qpos, n_units, sizes):
    """bool [Q, U]: the initial blocks and the local window's, among the
    units the query sees."""
    block = sizes["select_block"]
    own = (qpos // block)[:, None]
    unit = jnp.arange(n_units)[None, :]
    near = own - unit < sizes["select_local"] // block
    return (near | (unit < sizes["select_init_blocks"])) & (unit <= own)


def _select_units(index, forced, k, qpos):
    """bool [B, G, Q, U]: the forced units and the best of the others,
    k in all. ``index`` is ``-inf`` at the units the query cannot see."""
    forced = forced & jnp.isfinite(index)
    free = jnp.where(forced, -jnp.inf, index)
    order = jnp.argsort(-free, axis=-1, stable=True)  # ties: lower u first
    rank = jnp.argsort(order, axis=-1)
    room = jnp.maximum(k - jnp.sum(forced, -1, keepdims=True), 0)
    return forced | ((rank < room) & jnp.isfinite(free))


def _group_selection(chosen):
    """The layer's selections, [B, G, Q, U], as its KV heads use them
    and as they are handed over."""
    return chosen


def _attended(keys, visible):
    """The keys the attention runs over, given the blocks it hands
    over."""
    return keys


# ----------------------------------------------------------------------------

def _pooled_keys(k, window, stride):
    """bf16 k [B, S, KV, D] -> bf16 [B, P, KV, D]: float32 means of
    ``window`` keys every ``stride``, as sums of strided slices, rounded
    to the keys' own type."""
    count = (k.shape[1] - window) // stride + 1
    wide = k.astype(F32)
    total = sum(
        wide[:, o: o + stride * (count - 1) + 1: stride]
        for o in range(window)
    )
    return (total / window).astype(k.dtype)


def _unit_scores(q, pooled, qpos, sizes, n_units):
    """bf16 q [B, Q, H, D], bf16 pooled [B, P, KV, D] -> float32
    [B, G, Q, U], ``-inf`` above the query's own unit."""
    b, _, h, d = q.shape
    block, groups = sizes["select_block"], sizes["select_groups"]
    window, stride = sizes["pool_window"], sizes["pool_stride"]
    if block % stride or window % stride:
        raise ValueError("pooled keys do not tile the blocks")
    n_pooled, kv = pooled.shape[1], pooled.shape[2]
    ended = (
        stride * jnp.arange(n_pooled) + window - 1
    )[None, :] <= qpos[:, None]
    ended = _live_pooled(ended, sizes)
    q, pooled = _pool_inputs(q, pooled)
    dots = jnp.einsum(
        "bqgrd,bpgd->bgrqp", q.reshape(b, -1, kv, h // kv, d), pooled,
        preferred_element_type=F32,
    ) * d ** -0.5
    p = jax.nn.softmax(jnp.where(ended, dots, -1e30), axis=-1)
    p = jnp.where(ended, p, 0.0).reshape(b, groups, h // groups, *p.shape[3:])
    p = _head_sum(p)
    # pooled keys r u - extra .. r u + r - 1 overlap block u
    r, extra = block // stride, window // stride - 1
    p = jnp.pad(p, [(0, 0)] * 3 + [(extra, r * n_units - n_pooled)])
    over = jnp.stack(
        [p[..., o: o + r * n_units: r] for o in range(r + extra)], axis=-1
    )
    seen = jnp.arange(n_units)[None, :] <= (qpos // block)[:, None]
    return jnp.where(seen, _block_reduce(over), -jnp.inf)


def _layer(x, p, sizes, q_block):
    b, s, d = x.shape
    nh, nkv, hd = sizes["n_head"], sizes["n_kv_head"], sizes["head_dim"]
    block, groups, topk = (
        sizes["select_block"], sizes["select_groups"], sizes["index_topk"]
    )
    eps, scale = sizes["norm_eps"], sizes.get("residual_scale", 1.0)
    n_units = s // block
    attn = p["attn"]
    h = _rms(x, p["ln1"]["scale"], eps)
    q = _rms(_mm(h, attn["wq"]).reshape(b, s, nh, hd),
             attn["q_norm"]["scale"], eps)
    k = _rms(_mm(h, attn["wk"]).reshape(b, s, nkv, hd),
             attn["k_norm"]["scale"], eps)
    v = _mm(h, attn["wv"]).reshape(b, s, nkv, hd)
    pooled = _pooled_keys(k, sizes["pool_window"], sizes["pool_stride"])
    k, v = jnp.repeat(k, nh // nkv, axis=2), jnp.repeat(v, nh // nkv, axis=2)
    kpos = jnp.arange(s)[None, :]

    def rows(start):
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, start, q_block, 1)
        qpos = start + jnp.arange(q_block)
        index = _unit_scores(take(q), pooled, qpos, sizes, n_units)
        forced = _forced_units(qpos, n_units, sizes)
        chosen = _group_selection(_select_units(index, forced, topk, qpos))
        visible = kpos <= qpos[:, None]
        keys = jnp.repeat(chosen, block, axis=-1) & visible
        keys = _attended(jnp.repeat(keys, nh // groups, axis=1), visible)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", take(q), k, preferred_element_type=F32
        ) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(keys, scores, -jnp.inf), axis=-1)
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", probs.astype(BF16), v,
            preferred_element_type=F32,
        ).astype(BF16)
        return out, chosen

    out, chosen = jax.lax.map(rows, jnp.arange(0, s, q_block))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, nh * hd)
    # [blocks, B, G, Q, U] -> [G, B, S, U]
    chosen = jnp.transpose(chosen, (2, 1, 0, 3, 4)).reshape(
        groups, b, s, n_units
    )
    gate = jax.nn.sigmoid(jnp.matmul(h, attn["wg"], preferred_element_type=F32))
    out = (out.astype(F32) * gate).astype(BF16)
    x = (x.astype(F32) + scale * _mm(out, attn["wo"]).astype(F32)).astype(BF16)
    h = _rms(x, p["ln2"]["scale"], eps)
    mlp = p["mlp"]
    up = jnp.matmul(h, mlp["w_up"], preferred_element_type=F32)
    act = jax.nn.silu(jnp.matmul(h, mlp["w_gate"], preferred_element_type=F32))
    m = _mm((act * up).astype(BF16), mlp["w_down"])
    return (x.astype(F32) + scale * m.astype(F32)).astype(BF16), chosen


def forward(params, tokens, sizes, q_block=512):
    """tokens [B, S] -> (logits float32, aux) with ``aux["attn_selected"]``
    bool [L x G, B, S, S / b]."""
    q_block = min(q_block, tokens.shape[1])
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    x = (x.astype(F32) * sizes.get("scale_emb", 1.0)).astype(BF16)

    def layer(x, p):
        return _layer(x, p, sizes, q_block)

    x, chosen = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], sizes["norm_eps"])
    x = (x.astype(F32) * sizes.get("logit_scale", 1.0)).astype(BF16)
    logits = jnp.matmul(x, params["lm_head"]["w"], preferred_element_type=F32)
    return logits, {
        "attn_selected": chosen.reshape(-1, *chosen.shape[2:]),
    }


def judge(reference, params, batch, sizes, q_block, tolerances):
    """``sparse_standin.judge_forward`` with this stand-in in the
    program's place."""
    return sparse_standin.judge_forward(
        forward, reference, params, batch, sizes, q_block, tolerances
    )
