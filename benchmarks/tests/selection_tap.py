"""A selecting attention on the program, for the tests, from a program
that has none.

``lib/selected.py`` takes the selection from the program's own
``decoder.forward(..., return_aux=True)[1]["attn_selected"]``. The
program has no indexer (``models/decoder.py``) and a ``benchmark`` PR
may not edit it, so the tests plant one from outside: ``install``
replaces ``decoder._attention_block`` by a block that scores every
visible key with an indexer TIED to the attention's own weights (no new
parameter: its query, key and head-weight matrices are the first
columns of ``wq``, ``wk`` and ``wv``), selects ``index_topk`` of them as
the stand-in does (``sparse_standin._select``) and attends under that
mask in plain ``jax.numpy``; ``logits_and_choices`` runs the forward
once more with an ordered host callback that carries each layer's mask
out, beside ``choices_tap``'s expert ids where the model routes.
``reference`` is ``sparse_plain`` reading the same tied indexer. Same
forward in the train step, in the forward-only losses and in the tap.
Not part of the benchmark: ``run.py`` refuses a ``selected``
configuration on a program without the key.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.tests import choices_tap, sparse_plain, sparse_standin

# the tied indexer's sizes, in the configuration file's vocabulary
INDEX = {"index_n_heads": 8, "index_head_dim": 16, "index_topk": 16}
_emit = None  # set while ``logits_and_choices`` traces its forward


def tied_indexer(attn):
    """The indexer's matrices cut from the attention's ([..., d, n])."""
    nj, nc = INDEX["index_n_heads"], INDEX["index_head_dim"]
    return {
        "wq": attn["wq"][..., : nj * nc], "wk": attn["wk"][..., :nc],
        "w": attn["wv"][..., :nj],
        "k_norm": {"scale": jnp.ones(attn["wk"].shape[:-2] + (nc,))},
    }


def install(patch):
    """Make every attention layer of the program a selecting one."""
    from dlrover_tpu.models import decoder

    f32 = jnp.float32

    def block(x, layer, cfg, mesh, positions, attn_fn, fp8=None, rope=None):
        b, s, _ = x.shape
        nh, hd = cfg.n_head, cfg.head_dim
        nj, nc, topk = (
            INDEX["index_n_heads"], INDEX["index_head_dim"],
            INDEX["index_topk"],
        )
        q, k, v = decoder._project_qkv(
            x, layer, cfg, positions, fp8=fp8, rope=rope
        )
        rep = nh // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        idx = tied_indexer(layer["attn"])
        hi = jax.lax.stop_gradient(x)
        qi = sparse_standin._rope(
            (hi @ idx["wq"].astype(x.dtype)).reshape(b, s, nj, nc),
            cfg.rope_theta,
        )
        ki = sparse_standin._rope(
            sparse_standin._rms(
                hi @ idx["wk"].astype(x.dtype), idx["k_norm"]["scale"], 1e-6
            )[:, :, None].astype(x.dtype), cfg.rope_theta,
        )[:, :, 0]
        w = (hi @ idx["w"].astype(x.dtype)).astype(f32) * (nj * nc) ** -0.5
        dots = jax.nn.relu(jnp.einsum(
            "bqjc,bsc->bjqs", qi.astype(f32), ki.astype(f32)
        ))
        qpos = jnp.arange(s)[:, None]
        visible = (jnp.arange(s)[None, :] <= qpos)[None]
        index = jnp.where(
            visible, jnp.sum(dots * jnp.moveaxis(w, 1, 2)[..., None], 1),
            -jnp.inf,
        )
        chosen = sparse_standin._select(index, topk, qpos)
        if _emit is not None:
            _emit(chosen)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q.astype(f32), k.astype(f32)
        ) * hd ** -0.5
        probs = jax.nn.softmax(
            jnp.where(chosen[:, None], scores, -jnp.inf), axis=-1
        )
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(x.dtype), v)
        return out.reshape(b, s, nh * hd) @ layer["attn"]["wo"].astype(x.dtype)

    patch(decoder, "_attention_block", block)


def logits_and_choices(params, tokens, cfg, sizes=None):
    """In ``selected.program_logits_and_choices``'s place, on a program
    ``install`` was applied to."""
    global _emit
    from dlrover_tpu.models import decoder

    seen = []  # one [B, S, S] per layer, in layer order
    _emit = lambda chosen: jax.debug.callback(
        lambda rows: seen.append(np.asarray(rows)), chosen, ordered=True
    )
    try:
        if cfg.n_experts:
            logits, ids = choices_tap.tapped_logits_and_choices(
                params, tokens, cfg
            )
        else:
            logits = jax.jit(
                lambda p, t: decoder.forward(p, t, cfg)
            )(params, tokens)
            jax.block_until_ready(logits)
            jax.effects_barrier()
    finally:
        _emit = None
    choices = {"attn_selected": jnp.asarray(np.stack(seen))}
    if cfg.n_experts:
        choices["moe_choices"] = ids
    return logits, choices


def _with_indexer(params):
    layers = dict(params["layers"])
    layers["indexer"] = tied_indexer(layers["attn"])
    return dict(params, layers=layers)


def _selected(params, batch, sizes, q_block, choices):
    loss, logits, forced = sparse_plain.loss_and_logits_selected(
        _with_indexer(params), batch, sizes, q_block, choices
    )
    # the tied indexer is trained by nothing: the program reports no
    # ``indexer_loss`` and the reference owes none
    forced.pop("indexer_loss")
    return loss, logits, forced


# ``sparse_plain`` on the program's parameters, as a reference module
reference = types.SimpleNamespace(
    loss_and_logits=lambda params, batch, sizes, q_block: (
        sparse_plain.loss_and_logits(
            _with_indexer(params), batch, sizes, q_block
        )
    ),
    loss_and_logits_selected=_selected,
)
