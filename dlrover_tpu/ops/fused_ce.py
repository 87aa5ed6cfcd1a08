"""Fused lm-head + softmax cross-entropy: never materializes [N, V] logits.

The reference computes full logits then a separate CE (Megatron-style
vocab-parallel CE in atorch keeps the whole [B*S, vocab] tensor alive:
reference atorch/atorch/modules/distributed_modules/cross_entropy.py).
On TPU the f32 logits block for b8*s1024*v32000 is ~1 GiB of HBM that
the standard path writes in forward, re-reads for logsumexp / gather /
argmax, and re-materializes as softmax in backward — several GiB of
pure bandwidth plus ~2 GiB of peak memory.

This op chunks the vocab axis and keeps online max / log-sum-exp
statistics (the same trick as ops/pallas_attention.py, applied at the
XLA level where the chunk matmuls already hit the MXU): peak memory is
one [B, S, block_v] block, and backward recomputes each chunk's logits
instead of loading them. The extra recompute is one [N,D]x[D,V] matmul
pass; the savings are the logits round-trips and ~2 GiB of HBM, which
in turn buys a cheaper remat policy for the trunk.

Implemented as plain XLA (lax.scan over vocab chunks) rather than a
Pallas kernel: the hot op is a large matmul XLA already tiles onto the
MXU perfectly; a hand kernel could only lose.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dlrover_tpu.common import device

_NEG_INF = float("-inf")


def _num_chunks(v: int, block_v: int) -> int:
    return max(1, math.ceil(v / block_v))


def _pad_w(w: jax.Array, block_v: int) -> jax.Array:
    v = w.shape[1]
    nc = _num_chunks(v, block_v)
    pad = nc * block_v - v
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
    return w


def _mm_f32(subscripts, a, b):
    """Matmul with f32 accumulation/output from (possibly) bf16 operands.

    On TPU: bf16 operands + preferred_element_type=f32 is the native
    MXU contract. On CPU (the test platform): XLA's thunk runtime
    cannot execute a BF16xBF16=F32 dot when remat name-barriers stop it
    fusing the converts, so upcast the operands explicitly — the
    fallback path's extra precision is free there.
    """
    if device.on_cpu():
        return jnp.einsum(
            subscripts, a.astype(jnp.float32), b.astype(jnp.float32)
        )
    return jnp.einsum(
        subscripts, a, b, preferred_element_type=jnp.float32
    )


def _vary_like(carry, *operands):
    """A fresh scan carry, marked varying over the manual mesh axes its
    operands vary over. Inside a shard_map region (the ZeRO step's
    dp-manual update) constants are unvarying while everything computed
    from the per-rank batch varies, and scan requires the carry's input
    and output types to agree."""
    axes = frozenset().union(*(jax.typeof(o).vma for o in operands))
    if not axes:
        return carry
    return jax.tree.map(
        lambda a: lax.pcast(a, tuple(sorted(axes)), to="varying"), carry
    )


def _chunk_logits(x, w_pad, start, block_v, v, scale):
    """One [B, S, block_v] f32 logits chunk; out-of-vocab lanes -> -inf."""
    w_c = lax.dynamic_slice_in_dim(w_pad, start, block_v, axis=1)
    logits = _mm_f32("bsd,dv->bsv", x, w_c.astype(x.dtype))
    if scale != 1.0:
        logits = logits * jnp.float32(scale)
    valid = (start + jnp.arange(block_v)) < v
    logits = jnp.where(valid[None, None, :], logits, _NEG_INF)
    return logits, w_c


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_linear_ce(x, w, targets, scale=1.0, block_v=4096):
    """logz/target-logit/argmax of ``scale * (x @ w)`` without the logits.

    Args:
      x: [B, S, D] hidden states (any float dtype; matmuls run in this
        dtype with f32 accumulation, matching the unfused einsum path).
      w: [D, V] head weight (pass ``embed.T`` for tied embeddings — the
        transpose stays outside this op so its cotangent flows back).
      targets: [B, S] int32 target ids in [0, V).
      scale: static logit multiplier (muP readout).
      block_v: static vocab chunk width (MXU-friendly multiple of 128).

    Returns:
      (logz [B,S] f32, tgt_logit [B,S] f32, argmax [B,S] int32).
      NLL = logz - tgt_logit; z-loss reads logz; accuracy reads argmax.
      Differentiable w.r.t. x and w.
    """
    out, _ = _fused_fwd(x, w, targets, scale, block_v)
    return out


def _fused_fwd(x, w, targets, scale, block_v):
    b, s, _ = x.shape
    v = w.shape[1]
    nc = _num_chunks(v, block_v)
    w_pad = _pad_w(w, block_v)

    init = (
        jnp.full((b, s), _NEG_INF, jnp.float32),
        jnp.zeros((b, s), jnp.float32),
        jnp.zeros((b, s), jnp.float32),
        jnp.full((b, s), _NEG_INF, jnp.float32),
        jnp.zeros((b, s), jnp.int32),
    )
    init = _vary_like(init, x, w, targets)

    def step(carry, i):
        m, se, tgt, av, ai = carry
        start = i * block_v
        logits, _ = _chunk_logits(x, w_pad, start, block_v, v, scale)
        cm = logits.max(-1)
        m_new = jnp.maximum(m, cm)
        se = se * jnp.exp(m - m_new) + jnp.exp(
            logits - m_new[..., None]
        ).sum(-1)
        rel = targets - start
        inb = (rel >= 0) & (rel < block_v)
        got = jnp.take_along_axis(
            logits, jnp.clip(rel, 0, block_v - 1)[..., None], axis=-1
        )[..., 0]
        tgt = jnp.where(inb, got, tgt)
        ci = logits.argmax(-1).astype(jnp.int32)
        upd = cm > av
        av = jnp.where(upd, cm, av)
        ai = jnp.where(upd, start + ci, ai)
        return (m_new, se, tgt, av, ai), None

    (m, se, tgt, _, ai), _ = lax.scan(
        step, init, jnp.arange(nc), unroll=False
    )
    logz = m + jnp.log(se)
    out = (logz, tgt, ai)
    return out, (x, w, targets, logz)


def _fused_bwd(scale, block_v, res, cots):
    x, w, targets, logz = res
    g_logz, g_tgt, _ = cots  # argmax cotangent is float0/zero: ignored
    v = w.shape[1]
    d = w.shape[0]
    nc = _num_chunks(v, block_v)
    w_pad = _pad_w(w, block_v)
    g_logz = g_logz.astype(jnp.float32)
    g_tgt = g_tgt.astype(jnp.float32)

    def step(carry, i):
        dx, dwp = carry
        start = i * block_v
        logits, w_c = _chunk_logits(x, w_pad, start, block_v, v, scale)
        # p has exact zeros on padded lanes: exp(-inf - logz) == 0
        p = jnp.exp(logits - logz[..., None])
        dlog = g_logz[..., None] * p
        rel = targets - start
        onehot = jnp.arange(block_v)[None, None, :] == rel[..., None]
        dlog = dlog + jnp.where(onehot, g_tgt[..., None], 0.0)
        dlog_c = dlog.astype(x.dtype)  # MXU dtype, matches fwd matmuls
        dx = dx + jnp.float32(scale) * _mm_f32(
            "bsv,dv->bsd", dlog_c, w_c.astype(x.dtype)
        )
        dw_c = jnp.float32(scale) * _mm_f32("bsd,bsv->dv", x, dlog_c)
        dwp = lax.dynamic_update_slice_in_dim(dwp, dw_c, start, axis=1)
        return (dx, dwp), None

    init = (
        jnp.zeros(x.shape, jnp.float32),
        jnp.zeros((d, nc * block_v), jnp.float32),
    )
    init = _vary_like(init, x, w, targets, logz, g_logz, g_tgt)
    (dx, dwp), _ = lax.scan(step, init, jnp.arange(nc))
    d_targets = np.zeros(targets.shape, dtype=jax.dtypes.float0)
    return dx.astype(x.dtype), dwp[:, :v].astype(w.dtype), d_targets


fused_linear_ce.defvjp(_fused_fwd, _fused_bwd)
