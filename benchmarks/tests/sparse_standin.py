"""A stand-in for a program whose attention selects its keys, kept with
the tests and no part of the benchmark: ``tests/sparse_plain.py``'s
equations as a program on the chip would compute them. bf16 parameters
and hidden states; every matmul on bf16 operands accumulating in
float32; norms, rope, softmax and the indexer's weighting in float32;
the index product over bf16 qI and kI with a float32 sum, the top-k in
float32 by rank (two argsorts; the reference sorts the values and cuts
at the k-th); attention in q blocks under the selection's mask. It
hands over what ``lib/selected.py`` asks of a program:
``aux["attn_selected"]`` bool [L, B, S, S], and the step metric
``indexer_loss``.

The program proper has no indexer to tap (``models/decoder.py``), and a
``benchmark`` PR may not edit it: the limits of ``selected`` were set on
this file's readings on the chip (``tests/rehearse_selected.py``; PERF.md
section 4, PR 36), provisionally. ``tests/defects.py`` patches the small
functions below, which are looked up when ``forward`` is traced.
"""

import jax
import jax.numpy as jnp

from benchmarks.lib import selected

BF16, F32 = jnp.bfloat16, jnp.float32

# Keye-VL-2.0-30B-A3B's language tower (``config.json``: ``text_config``
# and ``sa_config``), as data, with a dense SwiGLU MLP in place of its
# experts (the intermediate size of eight experts of 768) and one
# eighth of the vocabulary: the widths of the chip rehearsal
KEYE_WIDTHS = {
    "n_layer": 1, "d_model": 2048, "n_head": 32, "n_kv_head": 4,
    "head_dim": 128, "qk_norm": True, "d_ff": 6144, "vocab_size": 18992,
    "norm_eps": 1e-6, "rope_theta": 1e7, "tie_embeddings": False,
    "attn_window": 0, "index_n_heads": 16, "index_head_dim": 64,
    "index_topk": 2048, "indexer_loss_coef": 1.0,
}


def init(key, sizes, std=0.02):
    """Seeded bf16 parameters in the program's layout (per-layer tensors
    stacked on axis 0), made on the device in one call."""
    n, d, ff = sizes["n_layer"], sizes["d_model"], sizes["d_ff"]
    nh, nkv, hd = sizes["n_head"], sizes["n_kv_head"], sizes["head_dim"]
    nj, nc, vocab = (
        sizes["index_n_heads"], sizes["index_head_dim"], sizes["vocab_size"]
    )
    shapes = {
        "embed/tokens": (vocab, d), "lm_head/w": (d, vocab),
        "attn/wq": (n, d, nh * hd), "attn/wk": (n, d, nkv * hd),
        "attn/wv": (n, d, nkv * hd), "attn/wo": (n, nh * hd, d),
        "indexer/wq": (n, d, nj * nc), "indexer/wk": (n, d, nc),
        "indexer/w": (n, d, nj),
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
            "indexer": dict(group("indexer"), k_norm=ones(n, nc)),
            "mlp": group("mlp"),
        },
    }


def _mm(x, w):
    """bf16 operands, float32 accumulation, a bf16 result."""
    return jnp.matmul(x, w, preferred_element_type=F32).astype(BF16)


def _rms(x, scale, eps):
    x = x.astype(F32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return (x * scale.astype(F32)).astype(BF16)


def _rope(x, theta):
    # [B, S, H, D] bf16 -> bf16, rotated in float32; rotate-half pairs
    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x = x.astype(F32)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1
    ).astype(BF16)


# ---- what the defects patch ------------------------------------------------

def _index_inputs(qi, ki):
    """The indexer's query and key as the score product reads them."""
    return qi, ki


_index_act = jax.nn.relu


def _head_weights(w):
    return w


def _select(index, k, qpos):
    """bool [B, Q, S]: each query's min(t + 1, k) best visible keys.
    ``index`` is ``-inf`` at the invisible ones."""
    order = jnp.argsort(-index, axis=-1, stable=True)  # ties: lower s first
    rank = jnp.argsort(order, axis=-1)
    return (rank < k) & jnp.isfinite(index)


def _attended(chosen, visible):
    """The keys the attention runs over, given the selection it hands
    over."""
    return chosen


def _reported_indexer_loss(value):
    return value


# ----------------------------------------------------------------------------

def _layer(x, p, sizes, q_block):
    b, s, d = x.shape
    nh, nkv, hd = sizes["n_head"], sizes["n_kv_head"], sizes["head_dim"]
    nj, nc = sizes["index_n_heads"], sizes["index_head_dim"]
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]
    topk = sizes["index_topk"]
    attn, idx = p["attn"], p["indexer"]
    h = _rms(x, p["ln1"]["scale"], eps)
    q = _mm(h, attn["wq"]).reshape(b, s, nh, hd)
    k = _mm(h, attn["wk"]).reshape(b, s, nkv, hd)
    v = _mm(h, attn["wv"]).reshape(b, s, nkv, hd)
    q = _rope(_rms(q, attn["q_norm"]["scale"], eps), theta)
    k = _rope(_rms(k, attn["k_norm"]["scale"], eps), theta)
    k, v = jnp.repeat(k, nh // nkv, axis=2), jnp.repeat(v, nh // nkv, axis=2)
    # the indexer reads the layer's input detached: it is trained by its
    # own term alone
    hi = jax.lax.stop_gradient(h)
    qi = _rope(_mm(hi, idx["wq"]).reshape(b, s, nj, nc), theta)
    ki = _rope(
        _rms(_mm(hi, idx["wk"]), idx["k_norm"]["scale"], eps)[:, :, None], theta
    )[:, :, 0]
    qi, ki = _index_inputs(qi, ki)
    w = _head_weights(
        jnp.matmul(hi, idx["w"], preferred_element_type=F32)
        * (nj * nc) ** -0.5
    )
    kpos = jnp.arange(s)[None, :]

    def rows(start):
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, start, q_block, 1)
        qpos = start + jnp.arange(q_block)[:, None]
        visible = (kpos <= qpos)[None]
        dots = _index_act(jnp.einsum(
            "bqjc,bsc->bjqs", take(qi), ki, preferred_element_type=F32
        ))
        # weighted in float32, elementwise: no second pass over the MXU
        weight = jnp.moveaxis(take(w), 1, 2)[..., None]  # [B, J, Q, 1]
        index = jnp.where(visible, jnp.sum(dots * weight, axis=1), -jnp.inf)
        chosen = _select(index, topk, qpos)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", take(q), k, preferred_element_type=F32
        ) * hd ** -0.5
        attended = _attended(chosen, visible)
        probs = jax.nn.softmax(
            jnp.where(attended[:, None], scores, -jnp.inf), axis=-1
        )
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", probs.astype(BF16), v,
            preferred_element_type=F32,
        ).astype(BF16)
        target = jax.lax.stop_gradient(jnp.mean(probs, axis=1))
        log_i = jax.nn.log_softmax(
            jnp.where(attended, index, -jnp.inf), axis=-1
        )
        live = target > 0
        kl = jnp.sum(jnp.where(
            live,
            target * (jnp.log(jnp.where(live, target, 1.0))
                      - jnp.where(live, log_i, 0.0)),
            0.0,
        ), axis=-1)
        return out, kl, chosen

    out, kl, chosen = jax.lax.map(rows, jnp.arange(0, s, q_block))
    join = lambda a: jnp.moveaxis(a, 0, 1).reshape(b, s, *a.shape[3:])
    x = x + _mm(join(out).reshape(b, s, nh * hd), attn["wo"])
    h = _rms(x, p["ln2"]["scale"], eps)
    mlp = p["mlp"]
    gate = jnp.matmul(h, mlp["w_gate"], preferred_element_type=F32)
    up = jnp.matmul(h, mlp["w_up"], preferred_element_type=F32)
    x = x + _mm((jax.nn.silu(gate) * up).astype(BF16), mlp["w_down"])
    return x, (jnp.mean(join(kl)), join(chosen))


def forward(params, tokens, sizes, q_block=512):
    """tokens [B, S] -> (logits float32, aux) with ``aux["attn_selected"]``
    bool [L, B, S, S] and ``aux["indexer_loss"]``."""
    q_block = min(q_block, tokens.shape[1])
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)

    def layer(x, p):
        return _layer(x, p, sizes, q_block)

    x, (kl, chosen) = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], sizes["norm_eps"])
    logits = jnp.matmul(x, params["lm_head"]["w"], preferred_element_type=F32)
    loss = _reported_indexer_loss(sizes["indexer_loss_coef"] * jnp.sum(kl))
    return logits, {"attn_selected": chosen, "indexer_loss": loss}


def judge(reference, params, batch, sizes, q_block, tolerances):
    """``judge_forward`` with this stand-in in the program's place."""
    return judge_forward(
        forward, reference, params, batch, sizes, q_block, tolerances
    )


def judge_forward(forward, reference, params, batch, sizes, q_block,
                  tolerances):
    """What the runner does for a ``selected`` configuration
    (``runners/train.py::_check_outputs``), with a stand-in's ``forward``
    in the program's place: (checks as ``(name, ok, value, limit)``, the
    ``BENCH reference`` record with the free-running errors in it)."""
    from benchmarks.lib import routed

    @jax.jit
    def program(params, batch):
        logits, aux = forward(params, batch["tokens"], sizes, q_block)
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, batch["targets"][..., None], -1)
        ce = jnp.mean(logz - tgt[..., 0])
        return logits, aux, ce

    @jax.jit
    def against_free(params, batch, logits):
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_logits = reference.loss_and_logits(
                params, batch, sizes, q_block
            )
        return (ref_loss, *routed.logit_errors(logits, ref_logits))

    logits, aux, ce = program(params, batch)
    losses = {"loss": float(ce), "ce_loss": float(ce)}
    if "indexer_loss" in aux:  # a stand-in with an indexer to align
        losses["indexer_loss"] = float(aux["indexer_loss"])
    ref_loss, logit_err, logit_rms = (
        float(x) for x in against_free(params, batch, logits)
    )
    loss_err = abs(losses["ce_loss"] - ref_loss) / abs(ref_loss)
    results, record = selected.compare(
        reference, params, batch, sizes, q_block, logits,
        {"attn_selected": aux["attn_selected"]}, losses, tolerances,
    )
    results.append((
        "loss_vs_free_reference", loss_err <= selected.FREE_LOSS_TOL,
        loss_err, selected.FREE_LOSS_TOL,
    ))
    record.update(
        ref_loss=ref_loss, logit_err=logit_err, logit_rms=logit_rms,
        loss_err=loss_err, program_losses=losses,
    )
    return results, record
