"""Sequence/context parallelism: Ulysses all-to-all and ring attention.

Reference: atorch's Ulysses-like SequenceParallelOptimization
(auto/opt_lib/sequence_parallel_optimization.py:9-103) — attention becomes
head-parallel, everything else sequence-parallel, via explicit all-to-all
process groups. **The reference has no ring/blockwise context parallelism
at all** (SURVEY.md §5) — ring attention here exceeds it.

TPU-native:
- Ulysses: ``jax.lax.all_to_all`` over the ``sp`` mesh axis inside
  ``shard_map`` — seq-sharded activations become head-sharded for exact
  attention, then return. All-to-alls ride ICI.
- Ring: k/v blocks rotate around the sp axis with ``ppermute`` while each
  device accumulates online-softmax partial attention for its local q
  block — O(S/sp) memory, exact causal attention for any sequence length.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.common import device

from dlrover_tpu.ops.attention import _repeat_kv, mha_reference

NEG_INF = -1e30


def _match_heads(q, k, v):
    """GQA: repeat k/v heads up to q's head count."""
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = _repeat_kv(k, rep)
        v = _repeat_kv(v, rep)
    return k, v


# ---------------------------------------------------------------------------
# Ulysses (all-to-all) sequence parallelism
# ---------------------------------------------------------------------------


def ulysses_attention(
    q: jax.Array,  # [B, S, H, D] — S sharded over sp outside shard_map
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    axis: str = "sp",
    attn_fn=None,
    prefix_len: Optional[jax.Array] = None,  # [B] int32 prefix-LM
    window: int = 0,  # sliding window (causal only)
) -> jax.Array:
    """Exact attention with seq-sharded inputs/outputs.

    Inside: all-to-all turns [B, S/sp, H, D] into [B, S, H/sp, D]
    (full sequence, sharded heads), runs normal attention, and reverses.
    ``prefix_len`` (GLM prefix-LM) and ``window`` (sliding window) pass
    straight through: the inner attention sees the full sequence with
    its true global positions, so the mask rules are unchanged.
    """
    if prefix_len is not None and not causal:
        raise ValueError("prefix_len requires causal=True")
    if window:
        if window < 0:
            raise ValueError(f"window must be >= 0, got {window}")
        if not causal:
            raise ValueError("window requires causal=True")
    attn_fn = attn_fn or functools.partial(mha_reference, causal=causal)

    def _call_attn(q, k, v, prefix=None):
        # forward the mask args only when set, so custom attn_fns that
        # don't take them keep working; a set window/prefix reaches EVERY
        # attn_fn (never silently dropped for custom ones)
        kw = {}
        if prefix is not None:
            kw["prefix_len"] = prefix
        if window:
            kw["window"] = window
        return attn_fn(q, k, v, **kw)

    sp = mesh.shape[axis]
    if sp == 1:
        return _call_attn(q, k, v, prefix_len)

    def local(q, k, v, prefix=None):
        # both inner impls (mha_reference and the flash kernel) handle GQA
        # natively, so expand kv heads ONLY when sp can't split them — the
        # expanded all-to-all would move groups× more bytes over ICI.
        # Decided HERE from the tp-LOCAL head count (k may arrive with its
        # head axis already sharded over tp; the global count would
        # misjudge divisibility).
        if k.shape[2] % sp != 0:
            k, v = _match_heads(q, k, v)

        # [B, S/sp, H, D] → [B, S, H/sp, D]
        def scatter_heads(x):
            return jax.lax.all_to_all(
                x, axis, split_axis=2, concat_axis=1, tiled=True
            )

        def gather_seq(x):
            return jax.lax.all_to_all(
                x, axis, split_axis=1, concat_axis=2, tiled=True
            )

        qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
        out = _call_attn(qh, kh, vh, prefix)
        return gather_seq(out)

    # batch stays sharded over (dp, fsdp) and heads over tp — declaring
    # either replicated would all-gather it and duplicate attention work
    spec = P(("dp", "fsdp"), axis, _head_axis(mesh, q, k), None)
    args = (q, k, v)
    in_specs = (spec, spec, spec)
    if prefix_len is not None:
        args = args + (prefix_len,)
        in_specs = in_specs + (P(("dp", "fsdp")),)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=spec,
        check_vma=False,
    )(*args)


def _head_axis(mesh: Mesh, q, k) -> Optional[str]:
    """Keep heads tp-sharded inside sp shard_maps when the mesh has tp.

    Only when tp divides BOTH q heads and kv heads: contiguous head blocks
    then align across shards, so the per-shard GQA repeat in
    ``_match_heads`` maps each q head to its correct kv group."""
    tp = mesh.shape.get("tp", 1)
    if tp > 1 and q.shape[2] % tp == 0 and k.shape[2] % tp == 0:
        return "tp"
    return None


# ---------------------------------------------------------------------------
# Ring attention (blockwise context parallelism over ppermute)
# ---------------------------------------------------------------------------


def _block_attend(q, k, v, scale, q_offset, k_offset, causal,
                  prefix=None, window=0):
    """Partial attention of local q against one k/v block.

    ``q_offset``/``k_offset`` are the blocks' global positions; ``prefix``
    [B] (global prefix-LM lengths) makes keys before it visible to all;
    ``window`` limits each query to the last ``window`` global positions.
    Returns (unnormalised out [B,Sq,H,D], row max m [B,H,Sq], row sum l).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_pos = k_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        allowed = (q_pos >= k_pos)[None, None]  # [1,1,Sq,Sk]
        if window:
            allowed = allowed & (q_pos - k_pos < window)[None, None]
        if prefix is not None:
            allowed = allowed | (
                k_pos[None, None] < prefix[:, None, None, None]
            )
        s = jnp.where(allowed, s, NEG_INF)
    m = jnp.max(s, axis=-1)  # [B,H,Sq]
    p = jnp.exp(s - m[..., None])
    # fully-masked rows: zero contribution, not NaN
    p = jnp.where((m == NEG_INF)[..., None], 0.0, p)
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return out.astype(jnp.float32), m, l


def _block_softmax_jnp(q, k, v, scale, q_offset, k_offset, causal,
                       prefix=None, window=0):
    """Normalized partial attention of local q vs one k/v block.

    Returns (out [B,Sq,H,D] f32 normalized within the block,
    lse [B,H,Sq] f32; fully-masked rows: out 0, lse NEG_INF)."""
    out_raw, m, l = _block_attend(
        q, k, v, scale, q_offset, k_offset, causal, prefix=prefix,
        window=window,
    )
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = out_raw / l_safe.transpose(0, 2, 1)[..., None]
    lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))
    return out, lse


def _block_softmax_flash(q, k, v, scale, q_offset, k_offset, causal,
                         bq, bk, prefix=None, window=0):
    """Same contract via the Pallas flash kernel (O(block) memory inside).

    Ring blocks are equal-sized, so vs the local q block a k/v block is
    exactly one of: fully before (dense), diagonal (causal), fully after
    (empty). The relation is traced (the source rotates), so lax.switch
    picks the kernel variant.

    With a prefix-LM ``prefix``, blocks at/after the diagonal run the
    causal kernel with a block-local prefix: globally, keys < prefix[b]
    are visible to every query, which inside this k block means the first
    ``prefix - k_offset`` keys (clamped) — the kernel's own block-skip
    keeps fully-dark blocks cheap. Before-diagonal blocks are already
    fully visible (dense) either way.
    """
    from dlrover_tpu.ops.pallas_attention import flash_attention_with_lse

    b, sq, h, d = q.shape

    def dense(q, k, v):
        out, lse = flash_attention_with_lse(
            q, k, v, None, None, False, scale, bq, bk
        )
        return out.astype(jnp.float32), lse

    def diagonal(q, k, v):
        # the kernel masks by block-LOCAL positions (iota from 0), and a
        # diagonal block has q and k at the same global offset — plain
        # causal masking is correct; the prefix part is folded in below
        # when present
        out, lse = flash_attention_with_lse(
            q, k, v, None, None, True, scale, bq, bk
        )
        return out.astype(jnp.float32), lse

    def empty(q, k, v):
        return (
            jnp.zeros((b, sq, h, d), jnp.float32),
            jnp.full((b, h, sq), NEG_INF, jnp.float32),
        )

    if not causal:
        return dense(q, k, v)
    if window:
        # sliding window over the ring: classify the k block by its
        # distance behind the local q block. Fully-lit before-blocks run
        # dense, the diagonal runs the kernel's own causal+window mask
        # (offsets align block-locally), boundary blocks the window only
        # partially covers run the kernel with GLOBAL offsets in SMEM —
        # its run gate compute-skips the tiles outside the window band —
        # and fully-dark blocks stay empty.
        sq_local = q.shape[1]
        sk_local = k.shape[1]
        dist = q_offset - k_offset

        def diag_cw(q, k, v):
            out, lse = flash_attention_with_lse(
                q, k, v, None, None, True, scale, bq, bk, window
            )
            return out.astype(jnp.float32), lse

        def win_partial(q, k, v):
            offs = jnp.stack(
                [jnp.int32(q_offset), jnp.int32(k_offset)]
            )
            out, lse = flash_attention_with_lse(
                q, k, v, None, offs, True, scale, bq, bk, window
            )
            return out.astype(jnp.float32), lse

        case = jnp.where(
            k_offset > q_offset,
            3,  # after the diagonal: empty
            jnp.where(
                k_offset == q_offset,
                1,  # diagonal: causal + block-local window
                jnp.where(
                    dist - (sk_local - 1) >= window,
                    3,  # every pair at/behind the window edge: empty
                    jnp.where(
                        dist + sq_local - 1 < window,
                        0,  # every pair inside the window: dense
                        2,  # window boundary crosses this block
                    ),
                ),
            ),
        )
        return jax.lax.switch(
            case, (dense, diag_cw, win_partial, empty), q, k, v
        )
    if prefix is not None:
        # block-local prefix: how many of THIS k block's keys fall inside
        # the global bidirectional prefix
        local_pref = jnp.clip(prefix - k_offset, 0, k.shape[1]).astype(
            jnp.int32
        )

        def causal_prefix(q, k, v):
            # diagonal block: block-local causal mask (both offsets
            # align) + the block-local slice of the prefix
            out, lse = flash_attention_with_lse(
                q, k, v, local_pref, None, True, scale, bq, bk
            )
            return out.astype(jnp.float32), lse

        def prefix_only(q, k, v):
            # after-block the prefix reaches into: causally nothing is
            # visible, only keys inside the prefix. Run the kernel with
            # a hugely negative global q offset — it kills the causal
            # term for every pair, leaving exactly the prefix mask; the
            # run gate still visits prefix-lit k tiles (k_start < pref)
            offs = jnp.stack(
                [-(jnp.int32(1) << 30), jnp.int32(0)]
            )
            out, lse = flash_attention_with_lse(
                q, k, v, local_pref, offs, True, scale, bq, bk
            )
            return out.astype(jnp.float32), lse

        # after-blocks no prefix reaches stay EMPTY — without this branch
        # every after-block would visit the kernel for all-dark tiles
        reach = jnp.max(local_pref) > 0
        case = jnp.where(
            k_offset < q_offset,
            0,
            jnp.where(
                k_offset == q_offset, 1, jnp.where(reach, 2, 3)
            ),
        )
        return jax.lax.switch(
            case, (dense, causal_prefix, prefix_only, empty), q, k, v
        )
    case = jnp.where(k_offset == q_offset, 1, jnp.where(k_offset < q_offset, 0, 2))
    return jax.lax.switch(case, (dense, diagonal, empty), q, k, v)


def ring_attention(
    q: jax.Array,  # [B, S, H, D] — S sharded over sp outside shard_map
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    axis: str = "sp",
    softmax_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    prefix_len: Optional[jax.Array] = None,  # [B] int32 prefix-LM
    window: int = 0,  # sliding window (causal only)
) -> jax.Array:
    """Exact attention over the full (sharded) sequence via a k/v ring.

    Each of the sp devices holds one contiguous sequence block; k/v rotate
    around the ring (ppermute over ICI) for sp steps while the local q
    merges per-block softmax results ((out, lse) logaddexp combination).
    On TPU the per-block attention is the Pallas flash kernel, so forward
    memory is O(kernel block) — not O(local_block²) — per step. The scan
    body is rematerialized, so backward avoids the O(S²/sp) score
    tensors; note the scan carries (rotating k/v + accumulator) are still
    saved per step, so backward holds O(S) k/v per device — the usual
    ring-attention bound. Communication overlaps the next block's
    compute under XLA's scheduler.
    """
    if prefix_len is not None and not causal:
        raise ValueError("prefix_len requires causal=True")
    if window:
        if window < 0:
            raise ValueError(f"window must be >= 0, got {window}")
        if not causal:
            raise ValueError("window requires causal=True")
        if prefix_len is not None:
            raise ValueError("window and prefix_len are mutually exclusive")
    sp = mesh.shape[axis]
    scale = (
        softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
    )
    if sp == 1:
        return mha_reference(
            q, k, v, causal=causal, softmax_scale=scale,
            prefix_len=prefix_len, window=window,
        )

    def local(rank, q, k, v, prefix=None):
        from dlrover_tpu.ops import pallas_attention as pa

        # sp rank from an sp-sharded iota input, not lax.axis_index:
        # partial-manual shard_map on jax 0.4.x lowers axis_index to a
        # PartitionId the SPMD partitioner rejects
        idx = rank[0]
        b, sq, h, d = q.shape
        q_offset = idx * sq

        bq = pa._fit_block(sq, block_q)
        bk = pa._fit_block(k.shape[1], block_k)
        use_flash = (
            pa.pltpu is not None and device.on_tpu() and bq and bk
        )
        if not use_flash:
            # the jnp block path needs matched heads; the flash kernel
            # handles GQA natively — keeping k/v at hkv heads there means
            # every ppermute rotation moves groups× fewer bytes over ICI
            k, v = _match_heads(q, k, v)

        perm = [(i, (i + 1) % sp) for i in range(sp)]

        def body(carry, _):
            k_blk, v_blk, src, acc, lse_run = carry
            k_offset = src * sq
            if use_flash:
                out_blk, lse_blk = _block_softmax_flash(
                    q, k_blk, v_blk, scale, q_offset, k_offset, causal,
                    bq, bk, prefix=prefix, window=window,
                )
            else:
                out_blk, lse_blk = _block_softmax_jnp(
                    q, k_blk, v_blk, scale, q_offset, k_offset, causal,
                    prefix=prefix, window=window,
                )
            # merge two normalized partials: logaddexp on lse, rescale outs
            lse_new = jnp.logaddexp(lse_run, lse_blk)
            alpha_run = jnp.where(
                lse_run <= NEG_INF, 0.0, jnp.exp(lse_run - lse_new)
            )
            alpha_blk = jnp.where(
                lse_blk <= NEG_INF, 0.0, jnp.exp(lse_blk - lse_new)
            )
            acc = (
                acc * alpha_run.transpose(0, 2, 1)[..., None]
                + out_blk * alpha_blk.transpose(0, 2, 1)[..., None]
            )
            # rotate k/v to the next device on the ring
            k_next = jax.lax.ppermute(k_blk, axis, perm)
            v_next = jax.lax.ppermute(v_blk, axis, perm)
            src_next = jax.lax.rem(src - 1 + sp, sp)
            return (k_next, v_next, src_next, acc, lse_new), None

        acc0 = jnp.zeros((b, sq, h, d), jnp.float32)
        lse0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
        (_, _, _, acc, _), _ = jax.lax.scan(
            jax.checkpoint(body),  # O(S/sp) backward memory per step
            (k, v, idx, acc0, lse0),
            None,
            length=sp,
        )
        return acc.astype(q.dtype)

    # batch stays sharded over (dp, fsdp), heads over tp; seq rides the ring
    spec = P(("dp", "fsdp"), axis, _head_axis(mesh, q, k), None)
    args = (jnp.arange(sp, dtype=jnp.int32), q, k, v)
    in_specs = (P(axis), spec, spec, spec)
    if prefix_len is not None:
        args = args + (prefix_len,)
        in_specs = in_specs + (P(("dp", "fsdp")),)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=spec,
        check_vma=False,
    )(*args)
