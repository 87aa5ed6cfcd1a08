"""Multi-head attention ops.

TPU counterpart of the reference's flash-attention integrations
(atorch modules/transformer/layers.py:538 FlashMHA wrappers; tfplus
flash_attn C++/CUDA glue). Here the op surface is one function,
``mha(q, k, v, causal=...)``:

- ``mha_reference`` — plain jnp einsum softmax attention (always available;
  XLA already fuses it well on small/medium sequences).
- ``flash_attention`` — Pallas TPU kernel (ops/pallas_attention.py), used
  automatically on TPU backends for long sequences.

All inputs are ``[batch, seq, heads, head_dim]``; GQA is expressed by
passing k/v with fewer heads (they are repeated on the fly).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.common import device


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(
        k[:, :, :, None, :], (b, s, h, n_rep, d)
    ).reshape(b, s, h * n_rep, d)


def mha_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    softmax_scale: Optional[float] = None,
    prefix_len: Optional[jax.Array] = None,
    window: int = 0,
    selected: Optional[jax.Array] = None,
    return_lse: bool = False,
):
    """Plain attention. q:[B,S,H,D], k/v:[B,S,Hkv,D] → [B,S,H,D].

    ``prefix_len`` [B] int32 (causal only): GLM-style prefix-LM — keys at
    positions < prefix_len[b] are visible to every query (bidirectional
    prefix), the rest follow the causal mask. ``window`` (causal only):
    Mistral-style sliding window — each query sees the last ``window``
    positions only. ``selected`` [B, Sq, Sk] (bool, or an integer array
    that is nonzero at the chosen keys): each query attends to the keys
    it names that the mask above also lets it see, the same for every
    head (or ``[B, G, Sq, Sk]``: a selection for each of G groups of
    H / G consecutive heads); the softmax runs over those alone. ``return_lse`` adds the
    log-sum-exp of each row's scores, float32 [B, H, Sq].
    """
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    if hkv != h:
        k = _repeat_kv(k, h // hkv)
        v = _repeat_kv(v, h // hkv)
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    if device.on_cpu():
        # explicit f32 upcast rather than preferred_element_type:
        # XLA:CPU's thunk runtime cannot execute a BF16xBF16=F32 dot
        # when a `name` barrier (remat checkpoint tags upstream) keeps
        # it from fusing the converts in; on CPU the extra precision is
        # free. TPU keeps bf16 operands + f32 accumulate — the native
        # MXU contract (this path serves prefill/generation there).
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk",
            q.astype(jnp.float32),
            k.astype(jnp.float32),
        )
    else:
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        )
    logits = logits * scale
    if causal:
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        mask = q_pos >= k_pos - (sk - sq)
        if window:
            if window < 0:
                raise ValueError(f"window must be >= 0, got {window}")
            if prefix_len is not None:
                raise ValueError(
                    "window and prefix_len are mutually exclusive"
                )
            mask = mask & ((k_pos - (sk - sq)) > q_pos - window)
        if prefix_len is not None:
            pmask = (
                mask[None]
                | (k_pos[None] < prefix_len[:, None, None])
            )  # [B, Sq, Sk]
            logits = jnp.where(pmask[:, None], logits, -1e30)
        else:
            logits = jnp.where(mask[None, None], logits, -1e30)
    elif prefix_len is not None:
        raise ValueError("prefix_len requires causal=True")
    elif window:
        raise ValueError("window requires causal=True")
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        logits = jnp.where(seg_mask[:, None, :sq, :sk], logits, -1e30)
    if selected is not None:
        chosen = selected != 0
        if chosen.ndim == 3:
            chosen = chosen[:, None]
        else:  # [B, G, Sq, Sk]: a selection a group of heads
            chosen = jnp.repeat(chosen, h // chosen.shape[1], axis=1)
        logits = jnp.where(chosen, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    if return_lse:
        return out, jax.nn.logsumexp(logits, axis=-1)
    return out


@functools.partial(
    jax.jit, static_argnames=("causal", "softmax_scale", "impl")
)
def mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    softmax_scale: Optional[float] = None,
    impl: str = "auto",
) -> jax.Array:
    """Dispatching attention entry point.

    ``impl``: "auto" picks the Pallas flash kernel on TPU for seq >= 1024,
    plain jnp otherwise. "reference" / "flash" force a path.
    """
    use_flash = False
    if impl == "flash":
        use_flash = True
    elif impl == "auto":
        use_flash = (
            device.on_tpu() and q.shape[1] >= 1024 and segment_ids is None
        )
    if use_flash:
        from dlrover_tpu.ops.pallas_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal, softmax_scale=softmax_scale
        )
    return mha_reference(
        q,
        k,
        v,
        causal=causal,
        segment_ids=segment_ids,
        softmax_scale=softmax_scale,
    )
