"""Plain reference for the dense decoder family (GPT-2 and
Llama/Mistral blocks): the forward pass and the mean next-token loss in
straightforward ``jax.numpy`` and float32, written from the published
descriptions and independent of the program's model code. No Pallas
kernel, no fused norm, no fused cross-entropy, no remat, no cache.

It reads the program's parameter tree (``decoder.init``'s layout:
per-layer tensors stacked on axis 0) and the configuration file's
``sizes`` group. The caller runs it under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
otherwise runs in bf16 passes.

Block equations (x is the residual stream, [B, S, d]):

    h  = norm1(x);  q, k, v = h Wq, h Wk, h Wv   (heads of head_dim)
    rope on q, k (Mistral: rotate-half pairs i, i + head_dim/2)
    a  = softmax(q k^T / sqrt(head_dim) + mask) v ;  x = x + a Wo
    h  = norm2(x)
    GPT-2:   x = x + gelu_tanh(h W_up) W_down
    Mistral: x = x + (silu(h W_gate) * (h W_up)) W_down
    logits = final_norm(x) W_head   (W_head = embedding^T when tied)

Mask: query i sees key j iff j <= i and (no window or i - j < window);
grouped-query attention repeats each kv head over n_head / n_kv_head
query heads. Attention is computed for ``q_block`` query rows at a
time so that the [heads, q_block, S] scores of a long sequence fit; the
arithmetic is that of the full S x S softmax.

Departures of the PROGRAM from the sources, which this reference
follows so that the two can agree (each is listed under ``assumed`` in
the configuration files): no bias on any projection (GPT-2 has them);
LayerNorm eps 1e-5 and RMSNorm eps 1e-6 are fixed in the program
(Mistral publishes 1e-5) and arrive here as ``sizes["norm_eps"]``.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _norm(x, p, kind, eps):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p["scale"].astype(F32)
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32)
    return out + p["bias"].astype(F32)


def _rope(x, theta):
    # x: [B, S, H, D]; rotate-half: lane i pairs with lane i + D/2
    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window, q_block):
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    q_block = min(q_block, s)
    if s % q_block:
        raise ValueError(f"sequence {s} is not a multiple of {q_block}")
    kpos = jnp.arange(s)[None, :]

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=1)
        qpos = start + jnp.arange(q_block)[:, None]
        mask = kpos <= qpos
        if window:
            mask = mask & (qpos - kpos < window)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(rows, jnp.arange(0, s, q_block))  # [nb, B, qb, H, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h * d)


def forward(params, tokens, sizes, q_block=1024):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32."""
    b, s = tokens.shape
    nh = sizes["n_head"]
    nkv = sizes.get("n_kv_head") or nh
    hd = sizes["d_model"] // nh
    kind, eps = sizes["norm"], sizes["norm_eps"]
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(F32)
    if sizes["pos"] == "learned":
        x = x + params["pos_embed"]["table"][:s].astype(F32)[None]

    def layer(x, p):
        p = jax.tree.map(lambda w: w.astype(F32), p)
        h = _norm(x, p["ln1"], kind, eps)
        q = (h @ p["attn"]["wq"]).reshape(b, s, nh, hd)
        k = (h @ p["attn"]["wk"]).reshape(b, s, nkv, hd)
        v = (h @ p["attn"]["wv"]).reshape(b, s, nkv, hd)
        if sizes["pos"] == "rope":
            q, k = _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])
        a = _attention(q, k, v, sizes.get("attn_window", 0), q_block)
        x = x + a @ p["attn"]["wo"]
        h = _norm(x, p["ln2"], kind, eps)
        if sizes["act"] == "swiglu":
            m = jax.nn.silu(h @ p["mlp"]["w_gate"]) * (h @ p["mlp"]["w_up"])
        else:
            m = jax.nn.gelu(h @ p["mlp"]["w_up"], approximate=True)
        return x + m @ p["mlp"]["w_down"], None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _norm(x, jax.tree.map(lambda w: w.astype(F32), params["final_norm"]),
              kind, eps)
    if sizes["tie_embeddings"]:
        head = params["embed"]["tokens"].astype(F32).T
    else:
        head = params["lm_head"]["w"].astype(F32)
    return x @ head


def loss_and_logits(params, batch, sizes, q_block=1024):
    """Mean next-token cross-entropy over every position of
    ``batch["tokens"]`` against ``batch["targets"]``, and the logits."""
    logits = forward(params, batch["tokens"], sizes, q_block)
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, batch["targets"][..., None], -1)[..., 0]
    return jnp.mean(logz - tgt), logits
