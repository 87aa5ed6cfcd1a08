"""Fused paged attention over the serving tier's block-table KV pools.

The serving engine (PR 11) stored KV state in fixed-size pages but
computed attention by materializing the full per-slot contiguous cache
every step — a ``kv_cache.gather`` producing an `[L, B, S_max, Hkv, D]`
copy per decode token, O(entire working set) HBM traffic, plus a full
bf16 dequant copy in int8 mode. This module is the paged decode path
that never builds that tensor:

- ``paged_attention`` — the dispatching op. On TPU (or in Pallas
  interpret mode) it runs a fused kernel whose grid walks each slot's
  block table one physical page at a time: K/V pages load straight from
  the layer-leading pools, int8 payloads dequantize **in-register**
  against their per-block f32 scales (bf16 pools load verbatim), and
  pages fold together with flash-style online softmax (running max/sum,
  f32 accumulation, the same ``-1e30`` masking as the dense cached
  attention). Per step it touches only the pages a slot actually holds.
- ``paged_attention_reference`` — the pure-jnp fallback with the same
  signature. It gathers ONLY the pages named by the block table (sliced
  to ``max_pages`` when the host knows how many are held) and then
  replicates ``decoder._cached_attention`` / ``_chunk_cached_attention``
  op for op, so in bf16 mode its output is **bitwise** equal to the
  dense gather path — the parity oracle for both the kernel and the
  engine's ``paged=True`` mode. Even as a fallback it beats the old
  full-pool gather: traffic scales with pages held, not table width.
- ``write_page_rows`` — the per-layer encode-on-write twin of
  ``kv_cache.write_rows`` (same phys/offset math, same trash-page
  routing) so the decoder's layer scan can commit each new token's K/V
  row straight into its page cell.

Both variants honor GQA (``kv_heads < n_head``) and sliding-window
masking (``window``), and the interpret-mode hook
(``DLROVER_TPU_PALLAS_INTERPRET``) makes the whole kernel CPU-testable,
following ``pallas_attention.py``/``pallas_norm.py``. Availability is
surfaced through the ``KernelCapabilities`` table
(``accelerate/device_context.py``) as ``paged_attention``.
"""

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # pltpu only resolves on TPU builds of jaxlib
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

from dlrover_tpu.ops import quant
from dlrover_tpu.ops.attention import _repeat_kv
from dlrover_tpu.common import device

NEG_INF = -1e30

# test hook: run every kernel in pallas interpret mode (CPU-executable).
INTERPRET = os.environ.get(
    "DLROVER_TPU_PALLAS_INTERPRET", ""
).lower() in ("1", "true", "yes")


def kernels_available(interpret=None) -> bool:
    """True when the fused paged kernel would actually run (real TPU or
    interpret mode) — what ``KernelCapabilities.paged_attention`` keys
    off. Everywhere else ``paged_attention`` silently runs the jnp
    reference, which is still a paged (pages-held-only) gather."""
    interpret = INTERPRET if interpret is None else interpret
    return pltpu is not None and (device.on_tpu() or interpret)


# ---------------------------------------------------------------------------
# Page-level helpers shared by the reference, the kernel and the decoder
# ---------------------------------------------------------------------------


def _pool_info(pools, kv_heads):
    """(mode, page_size, kv_heads, head_dim) from a per-layer pool dict.

    bf16 pools carry the head split in their shape; int8 pools store
    flat quant blocks, so ``kv_heads`` must come from the caller."""
    if "k" in pools:
        _, ps, hkv, d = pools["k"].shape
        return "bf16", ps, hkv, d
    if kv_heads is None:
        raise ValueError(
            "int8 pools store flat quant blocks; pass kv_heads= so the "
            "row can be split back into heads"
        )
    _, ps, nb, blk = pools["k_q"].shape
    row = nb * blk
    if row % kv_heads:
        raise ValueError(f"row of {row} elems not divisible by "
                         f"kv_heads={kv_heads}")
    return "int8", ps, kv_heads, row // kv_heads


def gather_pages(pools, block_tables, *, kv_heads=None, max_pages=None,
                 dtype=None):
    """K/V for ONLY the pages the block table names.

    Per-layer pools (bf16 ``{"k","v"}`` `[n_pages, ps, Hkv, D]`, int8
    ``{"k_q","k_scale","v_q","v_scale"}``) → ``(k, v)`` each
    `[B, W·ps, Hkv, D]`, where ``W`` is ``max_pages`` (host-known pages
    held) or the full table width. Unassigned entries (-1) clamp onto
    the trash page — finite garbage the caller masks by position.
    int8 payloads dequantize to ``dtype`` (the model compute dtype),
    matching ``kv_cache.gather``'s output values exactly.
    """
    tables = block_tables if max_pages is None else block_tables[:, :max_pages]
    t = jnp.maximum(tables, 0)
    mode, ps, hkv, d = _pool_info(pools, kv_heads)
    b, w = t.shape
    if mode == "bf16":
        k, v = pools["k"][t], pools["v"][t]
    else:
        dt = jnp.dtype(dtype) if dtype is not None else jnp.bfloat16
        k = quant.kv_decode_rows(pools["k_q"][t], pools["k_scale"][t], dt)
        v = quant.kv_decode_rows(pools["v_q"][t], pools["v_scale"][t], dt)
    shape = (b, w * ps, hkv, d)
    return k.reshape(shape), v.reshape(shape)


def write_page_rows(pools, block_tables, positions, valid, k_rows, v_rows):
    """Commit token K/V rows straight into their page cells (per-layer).

    The decoder-scan twin of ``kv_cache.write_rows``: same
    phys = table[position // ps] / offset = position % ps math, same
    trash-page routing for invalid lanes, encode-on-write for int8 —
    but over ONE layer's pool slice so the layer scan can carry pools
    as xs. ``positions``/``valid`` are `[B, C]`; rows `[B, C, Hkv, D]`.
    """
    mode, ps, _, _ = _pool_info(pools, k_rows.shape[2])
    page_idx = positions // ps
    offs = positions % ps
    phys = jnp.take_along_axis(block_tables, page_idx, axis=1)
    phys = jnp.where(valid, jnp.maximum(phys, 0), 0)  # 0 == TRASH_PAGE
    offs = jnp.where(valid, offs, 0)
    if mode == "bf16":
        dt = pools["k"].dtype
        return {
            "k": pools["k"].at[phys, offs].set(k_rows.astype(dt)),
            "v": pools["v"].at[phys, offs].set(v_rows.astype(dt)),
        }
    blk = pools["k_q"].shape[-1]
    b, c, hkv, d = k_rows.shape
    kq, ks = quant.kv_encode_rows(k_rows.reshape(b, c, hkv * d), blk)
    vq, vs = quant.kv_encode_rows(v_rows.reshape(b, c, hkv * d), blk)
    return {
        "k_q": pools["k_q"].at[phys, offs].set(kq),
        "k_scale": pools["k_scale"].at[phys, offs].set(ks),
        "v_q": pools["v_q"].at[phys, offs].set(vq),
        "v_scale": pools["v_scale"].at[phys, offs].set(vs),
    }


# ---------------------------------------------------------------------------
# Pure-jnp reference (the parity oracle, and the CPU fast path)
# ---------------------------------------------------------------------------


def paged_attention_reference(
    q,                  # [B, C, H, D] (decode: C == 1)
    pools,              # per-LAYER pool slices (bf16 or int8 keys)
    block_tables,       # [B, max_pages] int32, -1 = unassigned
    positions,          # decode: [B] (or scalar); chunk/verify: [B, C]
    *,
    scale,
    window: int = 0,
    kv_heads=None,
    max_pages=None,
    variant: str = "decode",
    extra_k=None,       # verify: in-flight chunk K rows [B, C, Hkv, D]
    extra_v=None,
):
    """Paged attention via a pages-held-only gather + the dense cached
    attention, op for op.

    ``variant`` selects which dense reference to replicate — decode and
    chunk differ in precision placement (decode keeps probs f32 through
    the PV einsum; chunk casts probs to q.dtype first, mirroring
    ``mha_reference``) and must not be mixed or bf16 bitwise parity
    breaks. Output `[B, C, H, D]` in q.dtype. Masked/garbage pages
    (trash, beyond a slot's length) contribute exact zeros through the
    f32 softmax, so slicing the walk to ``max_pages`` held pages is
    invisible to the math — the same argument as the engine's dense
    parity pin.

    ``variant="verify"`` is the speculative-decoding verify step: the C
    queries are the draft chunk, whose K/V rows (``extra_k``/``extra_v``,
    at positions ``positions`` themselves) are IN-FLIGHT — appended as
    extra keys after the committed pages instead of written to the
    pools, so rejected draft rows never touch page storage. Per query
    it runs the DECODE variant's math (grouped heads, probs f32 through
    PV): committed keys mask at ``kpos < positions[:, 0]`` (pool cells
    at chunk positions may hold a previous tenant's stale rows) and
    in-flight key i serves query j iff i <= j. The nonzero softmax
    lanes are the same values in the same order as sequential
    write-then-attend decode steps, so bf16 verify logits are bitwise
    equal to the spec-off decode path (pinned by the serving tests).
    """
    b, c, h, d = q.shape
    k, v = gather_pages(pools, block_tables, kv_heads=kv_heads,
                        max_pages=max_pages, dtype=q.dtype)
    s_len = k.shape[1]
    hkv = k.shape[2]
    kpos = jnp.arange(s_len)
    if variant == "verify":
        if extra_k is None or extra_v is None:
            raise ValueError("verify variant needs extra_k/extra_v rows")
        positions = jnp.asarray(positions)
        if positions.ndim != 2:
            raise ValueError("verify variant needs per-query positions "
                             "[B, C]")
        start = positions[:, 0]
        groups = h // hkv
        qg = q.reshape(b, c, hkv, groups, d)
        kf = jnp.concatenate(
            [k.astype(jnp.float32), extra_k.astype(jnp.float32)], axis=1
        )
        vf = jnp.concatenate(
            [v.astype(jnp.float32), extra_v.astype(jnp.float32)], axis=1
        )
        # key positions: committed rows at their cell index, in-flight
        # rows at the chunk positions
        key_pos = jnp.concatenate(
            [jnp.broadcast_to(kpos, (b, s_len)), positions], axis=1
        )
        committed = jnp.concatenate(
            [jnp.ones((b, s_len), bool), jnp.zeros((b, c), bool)], axis=1
        )
        mask = key_pos[:, None, :] <= positions[:, :, None]
        mask = mask & (~committed | (key_pos < start[:, None]))[:, None, :]
        if window:
            mask = mask & (
                key_pos[:, None, :] > positions[:, :, None] - window
            )
        s = jnp.einsum(
            "bckgd,bskd->bckgs", qg.astype(jnp.float32), kf
        ) * scale
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bckgs,bskd->bckgd", p, vf)
        return out.reshape(b, c, h, d).astype(q.dtype)
    if variant == "decode":
        if c != 1:
            raise ValueError("decode variant takes a single query (C=1)")
        groups = h // hkv
        qg = q.reshape(b, hkv, groups, d)
        s = jnp.einsum(
            "bkgd,bskd->bkgs",
            qg.astype(jnp.float32),
            k.astype(jnp.float32),
        ) * scale
        pos = jnp.asarray(positions)
        if pos.ndim == 0:
            mask = kpos <= pos
            if window:
                mask = mask & (kpos > pos - window)
            s = jnp.where(mask[None, None, None, :], s, NEG_INF)
        else:
            mask = kpos[None, :] <= pos[:, None]
            if window:
                mask = mask & (kpos[None, :] > pos[:, None] - window)
            s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
        return out.reshape(b, 1, h, d).astype(q.dtype)
    if variant != "chunk":
        raise ValueError(f"variant must be decode|chunk, got {variant!r}")
    if jnp.asarray(positions).ndim != 2:
        raise ValueError("chunk variant needs per-query positions [B, C]")
    if hkv != h:
        k = _repeat_kv(k, h // hkv)
        v = _repeat_kv(v, h // hkv)
    if device.on_cpu():
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
    mask = kpos[None, None, :] <= positions[:, :, None]
    if window:
        mask = mask & (kpos[None, None, :] > positions[:, :, None] - window)
    logits = jnp.where(mask[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------------------
# Fused kernel: one grid program per (slot, physical page)
# ---------------------------------------------------------------------------


def _paged_kernel(
    # scalar prefetch (SMEM)
    tab_ref,            # [B, W] int32 block tables
    span_ref,           # [B, 2] int32 (first, last) query position
    # VMEM blocks
    rowpos_ref,         # [1, n_q, 1] int32 query position of each row
    q_ref,              # [1, C, H, D]
    *refs,
    page_size,
    scale,
    window,
    hkv,
    groups,
    n_q,
    int8,
    out_dtype,
    verify=False,
):
    """Fold one physical page into every query row of one slot.

    Grid is (B, W): program (b, j) loads the page ``tab[b, j]`` names
    (clamped to the trash page when unassigned — its garbage is masked
    below), dequantizes int8 payloads in-register, and advances the
    flash-style running (max, sum, acc) state per kv head. The page
    walk is the ONLY K/V traffic: nothing the width of the block table
    is ever materialized.

    ``verify=True`` is the speculative-decoding verify step: the grid
    grows one extra column (B, W+1) whose last program folds the
    IN-FLIGHT draft-chunk K/V block (an extra VMEM operand, never
    resident in the pools) instead of a page; committed pages mask at
    ``kpos < start`` so stale rows at chunk positions are invisible,
    and in-flight key i serves query row j iff i <= j (causal within
    the chunk).
    """
    if verify:
        if int8:
            (kq_ref, ks_ref, vq_ref, vs_ref, ink_ref, inv_ref, inpos_ref,
             o_ref, m_scr, l_scr, acc_scr) = refs
        else:
            (k_ref, v_ref, ink_ref, inv_ref, inpos_ref,
             o_ref, m_scr, l_scr, acc_scr) = refs
    elif int8:
        kq_ref, ks_ref, vq_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    c = n_q // groups
    d = q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # query positions for this slot, one per (c, g) row. A VMEM column,
    # not a vector stacked from SMEM scalars: Mosaic refuses to
    # concatenate more than a tile's worth of scalars, which is every
    # prefill chunk of a realistic width (C >= 256)
    pos_rows = rowpos_ref[0]  # [n_q, 1] int32
    min_pos = span_ref[b, 0]
    max_pos = span_ref[b, 1]

    def _fold_block(k, v, allowed):
        """Advance the running (max, sum, acc) state by one key block
        ``k``/``v`` [rows, hkv, d] under mask ``allowed`` [n_q, rows]."""
        for kh in range(hkv):
            # row order: q is [C, H, D] with H = hkv*groups kv-major, so
            # kv head kh owns columns [kh*groups, (kh+1)*groups) of H
            # for every chunk row c → gather those into [c*groups, d].
            # ``allowed`` is (c, g)-major too (masks depend only on the
            # chunk row), so it serves every head unchanged.
            q_h = q_ref[0, :, kh * groups:(kh + 1) * groups, :]
            q_h = q_h.reshape(c * groups, d).astype(jnp.float32)
            k_h = k[:, kh, :].astype(jnp.float32)  # [rows, d]
            v_h = v[:, kh, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q_h, k_h,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [c·g, rows]
            s = jnp.where(allowed, s, NEG_INF)
            m_prev = m_scr[kh][:, :1]
            l_prev = l_scr[kh][:, :1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            # zero masked probs explicitly: an all-masked page would
            # otherwise contribute exp(NEG_INF - NEG_INF) = 1 per lane
            p = jnp.where(allowed, jnp.exp(s - m_new), 0.0)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p, v_h,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_scr[kh] = acc_scr[kh] * alpha + pv
            m_scr[kh] = jnp.broadcast_to(m_new, m_scr[kh].shape)
            l_scr[kh] = jnp.broadcast_to(l_new, l_scr[kh].shape)

    # the last grid column of a verify walk is the in-flight block, not
    # a page — clamp the table read so it never indexes out of bounds
    tab_w = tab_ref.shape[1]
    jt = jnp.minimum(j, tab_w - 1)
    page_ok = jnp.logical_and(tab_ref[b, jt] >= 0, j * page_size <= max_pos)
    if verify:
        # committed pages only hold usable rows BELOW the chunk start
        # (cells at chunk positions may be a previous tenant's stale
        # rows); the in-flight column handles the rest
        page_ok = jnp.logical_and(page_ok, j * page_size < min_pos)
        page_ok = jnp.logical_and(page_ok, j < nj - 1)
    if window:
        # page overlaps [min_pos - window + 1, max_pos]
        page_ok = jnp.logical_and(
            page_ok, (j + 1) * page_size - 1 > min_pos - window
        )

    @pl.when(page_ok)
    def _fold():
        if int8:
            # in-register dequant against the per-head f32 scales (the
            # scale block IS the head, so no cross-lane reshape);
            # round-trip through the compute dtype so values match what
            # kv_decode_rows hands the reference path
            ks = ks_ref[0]  # [ps, hkv] f32
            vs = vs_ref[0]
            k = (kq_ref[0].astype(jnp.float32) * ks[..., None])
            v = (vq_ref[0].astype(jnp.float32) * vs[..., None])
            k = k.astype(out_dtype)  # [ps, hkv, d]
            v = v.astype(out_dtype)
        else:
            k = k_ref[0]  # [ps, hkv, d]
            v = v_ref[0]
        kpos = (
            jax.lax.broadcasted_iota(jnp.int32, (n_q, page_size), 1)
            + j * page_size
        )
        allowed = kpos <= pos_rows
        if verify:
            allowed = jnp.logical_and(allowed, kpos < min_pos)
        if window:
            allowed = jnp.logical_and(allowed, kpos > pos_rows - window)
        _fold_block(k, v, allowed)

    if verify:

        @pl.when(j == nj - 1)
        def _fold_inflight():
            kpos_in = inpos_ref[0]  # [1, C] — the chunk positions
            allowed = kpos_in <= pos_rows  # [n_q, C]
            if window:
                allowed = jnp.logical_and(
                    allowed, kpos_in > pos_rows - window
                )
            _fold_block(ink_ref[0], inv_ref[0], allowed)

    @pl.when(j == nj - 1)
    def _finish():
        for kh in range(hkv):
            l = l_scr[kh][:, :1]
            l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → 0 out
            out = (acc_scr[kh] / l).reshape(c, groups, d)
            o_ref[0, :, kh * groups:(kh + 1) * groups, :] = out.astype(
                o_ref.dtype
            )


# Mosaic's scoped-VMEM default on a v5e; decode and verify stay inside it
_SCOPED_VMEM_DEFAULT = 16 * 1024 * 1024


def _vmem_limit(c, h, hkv, n_q, d, itemsize, verify):
    """``vmem_limit_bytes`` for one call: None (the compiler's default)
    while the call's blocks and scratch fit it, else their bytes plus
    half again for the compiler's own temporaries. The q/out blocks and
    the per-head (m, l, acc) scratch grow with C·H·D: compiled for a
    v5e at 25 heads x 64 in bf16, C=256 fits the default, C=512 needs
    32-40 MiB (this asks for 53) and C=1024 80-96 MiB (106). A chip
    that cannot give what is asked refuses at compile time, in words."""

    def pad(n, m):
        return -(-n // m) * m

    lanes = pad(d, 128)
    rows = 32 // itemsize  # sublanes a tile holds: 8 of f32, 16 of bf16
    scratch = hkv * pad(n_q, 8) * (128 + 128 + lanes) * 4
    blocks = 2 * c * pad(h, rows) * lanes * itemsize  # q and out
    if verify:
        blocks += 2 * c * pad(hkv, rows) * lanes * itemsize
    blocks += pad(n_q, 8) * 128 * 4  # row positions
    need = int(1.5 * (scratch + 2 * blocks))  # blocks are double-buffered
    return need if need > _SCOPED_VMEM_DEFAULT else None


def _paged_call(q, pools, tables, positions, *, scale, window, kv_heads,
                variant, interpret, extra_k=None, extra_v=None):
    mode, ps, hkv, d = _pool_info(pools, kv_heads)
    b, c, h, _ = q.shape
    groups = h // hkv
    w = tables.shape[1]
    n_q = c * groups
    verify = variant == "verify"

    kernel = functools.partial(
        _paged_kernel,
        page_size=ps,
        scale=scale,
        window=window,
        hkv=hkv,
        groups=groups,
        n_q=n_q,
        int8=(mode == "int8"),
        out_dtype=q.dtype,
        verify=verify,
    )

    # a verify walk has one extra grid column (the in-flight block) —
    # clamp the table read in every index map so it stays in bounds
    jw = w - 1
    q_spec = pl.BlockSpec((1, c, h, d), lambda i, j, tab, pos: (i, 0, 0, 0))
    # (c, g)-major row positions as a VMEM column, and the slot's
    # (first, last) position as the only per-query SMEM scalars
    row_pos = jnp.repeat(positions, groups, axis=1)[..., None]
    rowpos_spec = pl.BlockSpec((1, n_q, 1), lambda i, j, tab, pos: (i, 0, 0))
    span = jnp.stack([positions[:, 0], positions[:, -1]], axis=1)
    if mode == "bf16":
        pool_args = (pools["k"], pools["v"])
        pool_specs = [
            pl.BlockSpec(
                (1, ps, hkv, d),
                lambda i, j, tab, pos: (
                    jnp.maximum(tab[i, jnp.minimum(j, jw)], 0), 0, 0, 0
                ),
            )
            for _ in range(2)
        ]
    else:
        nb, blk = pools["k_q"].shape[-2:]
        if (nb, blk) != (hkv, d):
            raise ValueError(
                f"int8 pools with {nb} scale blocks of {blk} per row: the "
                f"fused kernel needs one block per kv head ({hkv} x {d})"
            )
        pool_args = (pools["k_q"], pools["k_scale"],
                     pools["v_q"], pools["v_scale"])
        qspec = pl.BlockSpec(
            (1, ps, nb, blk),
            lambda i, j, tab, pos: (
                jnp.maximum(tab[i, jnp.minimum(j, jw)], 0), 0, 0, 0
            ),
        )
        sspec = pl.BlockSpec(
            (1, ps, nb),
            lambda i, j, tab, pos: (
                jnp.maximum(tab[i, jnp.minimum(j, jw)], 0), 0, 0
            ),
        )
        pool_specs = [qspec, sspec, qspec, sspec]

    extra_args = ()
    extra_specs = []
    if verify:
        if extra_k is None or extra_v is None:
            raise ValueError("verify variant needs extra_k/extra_v rows")
        extra_args = (extra_k, extra_v, positions[:, None, :])
        extra_specs = [
            pl.BlockSpec((1, c, hkv, d),
                         lambda i, j, tab, pos: (i, 0, 0, 0))
            for _ in range(2)
        ] + [pl.BlockSpec((1, 1, c), lambda i, j, tab, pos: (i, 0, 0))]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, w + 1) if verify else (b, w),
        in_specs=[rowpos_spec, q_spec] + pool_specs + extra_specs,
        out_specs=pl.BlockSpec((1, c, h, d),
                               lambda i, j, tab, pos: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, n_q, 128), jnp.float32),  # running max
            pltpu.VMEM((hkv, n_q, 128), jnp.float32),  # running sum
            pltpu.VMEM((hkv, n_q, d), jnp.float32),    # f32 accumulator
        ],
    )
    compiler_params = (
        None
        if interpret
        else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                c, h, hkv, n_q, d, q.dtype.itemsize, verify
            ),
        )
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, h, d), q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
        name="paged_attention",
    )(tables, span, row_pos, q, *pool_args, *extra_args)
    return out


def paged_attention(
    q,
    pools,
    block_tables,
    positions,
    *,
    scale,
    window: int = 0,
    kv_heads=None,
    max_pages=None,
    variant: str = "decode",
    interpret=None,
    extra_k=None,
    extra_v=None,
):
    """Paged attention over block-table KV pools — fused when it can be.

    Dispatch mirrors the other Pallas ops: the kernel runs on real TPUs
    or under interpret mode; everywhere else the jnp reference runs
    (still touching only ``max_pages`` held pages, and carrying the
    bf16 bitwise-parity contract). The kernel accumulates in f32 with
    online softmax, so it matches the reference to float tolerance, not
    bitwise — CPU serving keeps bitwise pins because CPU dispatch IS
    the reference.

    ``variant="verify"`` (speculative decoding) additionally takes the
    draft chunk's in-flight ``extra_k``/``extra_v`` rows [B, C, Hkv, D];
    they are folded as keys WITHOUT ever touching the pools, so a
    rejected draft row leaves no trace in page storage.
    """
    interpret = INTERPRET if interpret is None else interpret
    if pltpu is None or not (device.on_tpu() or interpret):
        return paged_attention_reference(
            q, pools, block_tables, positions, scale=scale, window=window,
            kv_heads=kv_heads, max_pages=max_pages, variant=variant,
            extra_k=extra_k, extra_v=extra_v,
        )
    tables = (
        block_tables if max_pages is None else block_tables[:, :max_pages]
    )
    pos = jnp.asarray(positions, jnp.int32)
    b, c = q.shape[0], q.shape[1]
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (b,))
    if pos.ndim == 1:
        pos = pos[:, None]
    if pos.shape != (b, c):
        raise ValueError(
            f"positions {pos.shape} must broadcast to queries {(b, c)}"
        )
    return _paged_call(
        q, pools, jnp.asarray(tables, jnp.int32), pos, scale=scale,
        window=window, kv_heads=kv_heads, variant=variant,
        interpret=interpret, extra_k=extra_k, extra_v=extra_v,
    )
