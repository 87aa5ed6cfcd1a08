"""Pallas TPU flash attention.

The framework's hot-op showcase (reference analog: the flash-attention
CUDA glue in tfplus/flash_attn and atorch's FlashMHA wrappers,
modules/transformer/layers.py:538 — here it's a native TPU kernel, not a
vendored library binding).

Forward: classic FlashAttention-2 online-softmax over k/v blocks. Grid is
(batch*kv_head_groups, q_blocks, k_blocks) with the k dimension marked
"arbitrary" so the output block is revisited and carried in VMEM scratch
(m/l running stats + f32 accumulator).

Block skipping: a (q, k) block pair above the causal diagonal, or wholly
older than a sliding window reaches, runs nothing (``_block_runs``) and
fetches nothing (its index map re-addresses a live neighbour, which is
not refetched). On the square grid such a pair is still a grid step.
Under a LIVE window (causal, a static window shorter than the sequence,
no prefix, no traced offsets) the inner grid axis is therefore the
**band** (``_inner_grid``): as many steps as the widest query block's
window touches key blocks — 3 at a window of 2,048 keys and tiles of
1,024 on a sequence of 16 blocks, 5 at tiles of 512 of 32 —, step s of
query block i standing for key block ``first(i) + s``
(``_band_k_block``); the dk/dv grid walks the query blocks of a key
block the same way (``_band_q_block``). The kernels take their
``k_start`` / ``q_start`` from the block a step stands for, initialise at
the axis' first step and finish at its last; a block's steps beyond its
own run (the sequence's first blocks have shorter ones) stand for
blocks past the diagonal or the sequence's end and run nothing, so no
accumulator sees a block twice. Without a live window the extent is
every block and the first block 0: the square, one path whose extent
follows the window. Ring attention (traced offsets), a prefix and the
``_sel`` kernels keep the square.

Backward: FlashAttention-2-style pallas kernels via custom_vjp — a dq pass
(k-blocks innermost, dq carried in VMEM scratch) and a dk/dv pass (q-blocks
innermost), both recomputing p from the saved lse; tiles capped by head
width (BWD_BLOCK=512 for head_dim 64, BWD_BLOCK_WIDE=1024 for head_dim
128, BWD_BLOCK_256 = 1024 × 512 for head_dim 256, where 1024 × 1024
does not fit VMEM — all measured on v5e; the backward holds ~4 [bq,bk]
f32 transients at whichever cap applies) and, on the banded grid, by the
window (``_bwd_tiles``: at most a quarter of it — a tile of b rows
executes about W + b keys a query for W useful; the sweep that set the
fraction, and why the forward keeps the caller's tile, is in the comment
above ``WINDOW_TILES``). A jnp-level chunked recompute remains as the
off-TPU / untileable-shape fallback.

The per-row statistics keep one format from the kernel that makes them
to the kernels that read them — f32 tiles ``[B·slabs, S, 8]``, head p of
a slab in lane p of its row's tile (unpacked: ``[B·H, S, 8]``, a slab is
one head) — and XLA makes no pass over them. ``lse`` is the forward
kernel's output as it wrote it: the residual (``flash_lse``) and what
both backward kernels read through the same BlockSpec. ``delta`` = Σ_d dO·out is made
in the dq kernel, once a q block at its first key step, from the dO
block it holds and the matching ``out`` block (f32 products of the
stored values, f32 row sum per head), and is its second output, which
the dk/dv kernel reads. Only a caller that asked for lse
(``flash_attention_with_lse``: ring attention's softmax merge) gets the
``[B, H, S]`` view (``_stat_rows``), and only its lse cotangent comes in
from XLA (``_stat_tiles``) — into the dq kernel, which subtracts it from
delta, so the SAME kernels serve the ring. The price of the format: a
tile array is 128 lanes wide in memory (54 MB a layer at GPT-2 XL for
0.85 MB of numbers; 134 MB for 1 MB at 32 heads x 8192 tokens). So what
a remat policy KEEPS is not the tiles: a caller whose policy lists
``flash_out`` and ``flash_lse`` says so (``lse_rows``; the decoder's
``full`` at long spans, ``decoder.keeps_attention_output``), and
``flash_lse`` then names the ``[B, H, S]`` numbers; the backward rule
pads them into tiles again for its kernels (one read and one write of
the tile array a layer, against running the forward kernel a second
time). Where no policy keeps them the residual is the tile array and the
step is as above.

Both paths support GLM-style prefix-LM masking (per-batch prefix scalar in
SMEM) and GQA (K/V shared across head groups via BlockSpec index maps, no
materialized repeats).

The selection operand (``flash_attention(..., selected=mask)``; a
learned sparse attention, ``models/decoder.py``'s indexer): ``mask`` is
``[B, Sq, Sk]`` int8, nonzero where query t chose key s, ONE mask for
all heads of a sequence (the indexer ranks keys per query, not per
head) — or ``[B, G, Sq, Sk]``, a mask for each of G groups of H / G
consecutive heads (a block-sparse attention that selects per KV head:
``_sel_rows``; the same kernels, the tile's row picked by the head's
group). The unpacked kernels take it as a further operand, one
``[1, block_q, block_k]`` tile a grid step through the index map
``(g // H, i, k block)``, and a pair counts where the causal rule admits
it AND the tile names it; their traced names end in ``_sel``
(``flash_fwd_sel``, ``flash_bwd_dq_sel``, ``flash_bwd_dkv_sel``). What
is skipped is what the causal run gate skips: blocks above the diagonal
are neither fetched nor computed. A block below it runs DENSE under the
mask, whether the tile names one key or all, so a call executes the
causal half of the pairs and is credited the selected ones (at 8192
tokens and top-2048: 4,096.5 against 1,792.125 a query). The softmax
statistics — the running maximum, the sum and the ``lse`` written for
the backward — range over the selected keys alone: a row whose first
blocks name none of its keys carries only masked scores there, which
the first real score's rescaling wipes to exactly zero. ``lse`` is
returned ``[B, H, S]`` and detached (the indexer's alignment term reads
it). The tile is read again for each of the H heads (int8: 64 MiB a
sequence of 8192, so 2 GB a call at 32 heads, under the MXU time).

Narrow-head packing (``head_pack``): heads narrower than the 128-lane
quantum (gpt2's head_dim=64) share a **slab**: 128 columns —
``128 // head_dim`` heads side by side — of the projection's own
``[B, S, H·D]`` array, of which ``[B, S, H, D]`` is a free view. The packed
kernels run on grid (batch, slab, q block, k block) and take the
``[block, 128]`` tile at column block ``slab`` as it lies, and write the
output, dq, dk and dv the same way: the BlockSpec index map does what a
transpose to ``[B, H, S, D]`` would, so nothing is padded, transposed or
sliced in memory between the projection matmuls and the kernels. Inside a
program the heads are kept apart by zeroing one operand outside head p's
lanes (a 32-bit AND, ``_and_lanes``): ``q @ k_pᵀ`` contracts 128 lanes of
which ``d`` are live — the MXU passes of a ``d``-deep contraction, the
128-lane quantum makes them cost the same — and ``p_p @ v_p`` lands on
head p's lanes of ONE ``[block_q, 128]`` accumulator, so every store is
lane-dense. Softmax statistics, lse and delta stay per head (delta: the
row sum of dO·out over the head's own lanes). What packing
buys is everything around the matmuls: no relayout pass over q, k, v, out
or their cotangents, the causal/prefix/window mask and its iotas computed
once per program and shared by the slab's heads, pack× fewer grid
programs. A head count that does not fill its last slab (gpt2-1.5b's 25 ×
64 = 12½ slabs) is handled in the kernel: the half slab reads past column
H·D, where the block holds nothing specified; those lanes are in no head's
mask and are ANDed to zero in the operands used whole, and the part of an
output block beyond the array is written nowhere. GQA keeps the unpacked
path (every GQA config here runs full-width d=128 heads anyway).
"""

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

try:  # pltpu only resolves on TPU builds of jaxlib
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

from dlrover_tpu.common import device
from dlrover_tpu.observability.tracing import counters, set_counter

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
# cap on the backward recompute chunk: bounds the transient p/dp/ds
# tensors to [B,H,S,1024] f32 regardless of the forward tile choice,
# while leaving seq<=1024 single-chunk (measured fastest on v5e)
BACKWARD_CHUNK = 1024
NEG_INF = -1e30

# test hook: run every kernel in pallas interpret mode (CPU-executable);
# lets composition layers (ring attention) exercise the real kernel path
# on the virtual CPU mesh. Seeded from DLROVER_TPU_PALLAS_INTERPRET so
# a whole test run can flip every kernel module (this one and
# ops/pallas_norm.py) without per-module monkeypatching.
INTERPRET = os.environ.get(
    "DLROVER_TPU_PALLAS_INTERPRET", ""
).lower() in ("1", "true", "yes")

# the backward kernels' tiles, capped separately from the forward's
# (see _bwd_rule)
BWD_BLOCK = 512        # measured best for head_dim 64 (v5e)
BWD_BLOCK_WIDE = 1024  # measured best for head_dim 128 (v5e)
# head_dim > 128 (latent attention expanded): (q rows, k rows). 1024 x
# 1024 needs 17.3 MB of the kernel's 16 MB of VMEM (Mosaic refuses it:
# tests/test_tpu_compile.py). Of those that fit, swept on a v5e at 2 x
# 8192 x 20 heads (PR 34; the two backward kernels, ms): 1024 x 512
# 32.7, 512 x 1024 33.1, 512 x 512 33.4, 1024 x 256 35.7, 256 x 1024
# 36.1, 256 x 512 38.3, 512 x 256 39.2
BWD_BLOCK_256 = (1024, 512)


# Under a live window (``_inner_grid``: the banded grid) a tile of b rows
# executes about W + b keys a query for W useful on a band of W / b + 1
# steps, so a smaller tile saves pairs until the cost of a step takes it
# back. Swept on a v5e with the band (PR 48; ms a call, the layout
# copies around the kernels included; q rows x k rows):
#   1 x 16,384, GQA 32 / 4 x 128, W 2,048 (Trinity-Mini's window layers;
#   the square grid at 1024 x 1024: 8.27 / 11.85 / 12.07)
#     tile         flash_fwd  flash_bwd_dq  flash_bwd_dkv
#     1024 x 1024       7.51          9.72           9.81
#     1024 x 512       13.39          9.41          10.08
#     512 x 1024        8.40         10.03           9.72
#     512 x 512        12.08          8.92           9.10
#     256 x 512        13.56         10.14          12.15
#     k rows 256   21.4-24.7     11.4-13.3      13.5-16.4
#   1 x 8,192, GQA 32 / 8 x 128, W 4,096 (Mistral's; square 4.89 / 6.69 /
#   6.99): 1024 x 1024 4.79 / 6.35 / 6.84, 512 x 512 8.41 / 6.26 / 6.85,
#   1024 x 512 8.72 / 6.16 / 7.21, 512 x 1024 5.43 / 6.63 / 6.77
# The forward has two matmuls a step to the backward's five and pays a
# step's fixed cost (the carried statistics, the accumulator's rescale)
# twice as often at half the k rows: it keeps the caller's tile. The
# backward kernels gain 7-8% at a quarter of the window and lose at an
# eighth, so their tile is at most window / WINDOW_TILES.
WINDOW_TILES = 4


def _gate_is_static(causal, prefix, offsets) -> bool:
    """Whether the run gate's dead blocks are known while tracing, so
    that index maps may clamp them away and a live window's grid may be
    its band: not with a prefix, which can make above-diagonal blocks
    live, nor with traced global offsets (ring attention), where the
    diagonal's grid position is unknown."""
    return bool(causal) and prefix is None and offsets is None


def _live_window(window: int, sk: int) -> bool:
    """A window that hides keys of a sequence of ``sk`` from some query."""
    return bool(window) and 0 < window < sk


def _bwd_caps(head_dim: int):
    """Largest backward tile (q rows, k rows) for a head width. A head
    past one tile of 128 lanes lies in two, whatever it fills of the
    second (192 score channels: 1024 x 1024 asks 17.5 MB of the 16)."""
    if head_dim > LANES:
        return BWD_BLOCK_256
    cap = BWD_BLOCK_WIDE if head_dim >= 128 else BWD_BLOCK
    return cap, cap


def _bwd_tiles(sq, sk, head_dim, block_q, block_k, window=0):
    """The backward kernels' tile (q rows, k rows; None where none
    fits): the forward's, cut to the head width's cap and, where
    ``window`` is live on the banded grid, to a quarter of it (the sweep
    above) — the largest 128-multiple that divides the sequence."""
    cap_q, cap_k = _bwd_caps(head_dim)
    if _live_window(window, sk):
        cap = max(128, window // WINDOW_TILES)
        cap_q, cap_k = min(cap_q, cap), min(cap_k, cap)
    return (
        _fit_block(sq, min(block_q, cap_q)),
        _fit_block(sk, min(block_k, cap_k)),
    )


def _lower(a, b):
    """min(a, b): a Python int of Python ints (the band's extent is
    counted at trace time, where a traced minimum has no value) and a
    traced minimum of grid indices."""
    if isinstance(a, int) and isinstance(b, int):
        return min(a, b)
    return jnp.minimum(a, b)


def _upper(a, b):
    """max(a, b), as ``_lower``."""
    if isinstance(a, int) and isinstance(b, int):
        return max(a, b)
    return jnp.maximum(a, b)


def _last_visible_k_block(i, block_q, block_k):
    """Highest k-block index the causal run gate admits for q block i —
    the DMA-clamp twin of _block_runs: index maps clamp to this so
    gate-skipped blocks are never fetched. Any change to the gate's
    geometry must land here too."""
    return ((i + 1) * block_q - 1) // block_k


def _first_window_k_block(i, block_q, block_k, window):
    """Lowest k-block index a sliding window admits for q block i:
    its oldest row sees back to q_start − window + 1."""
    return _upper(0, (i * block_q - window + 1) // block_k)


def _first_visible_q_block(j, n_q_blocks, block_q, block_k):
    """Lowest q-block index the causal run gate admits for k block j,
    clamped into range (causal with sk > sq can otherwise exceed it)."""
    return _lower((j * block_k) // block_q, n_q_blocks - 1)


def _last_window_q_block(j, n_q_blocks, block_q, block_k, window):
    """Highest q-block index a sliding window admits for k block j: its
    newest key is visible up to k_end + window − 1."""
    return _lower(
        ((j + 1) * block_k - 1 + window - 1) // block_q, n_q_blocks - 1
    )


def _band_k_block(i, step, block_q, block_k, window, band):
    """The inner grid axis of the forward and dq kernels: (the k block
    that step ``step`` of q block i stands for, whether the sequence has
    such a block — None on the square grid, where the step IS the
    block). ``band``: 0 on the square grid, else the sequence's number
    of k blocks; the band then starts at the first block the window
    admits, and what lies past the diagonal the run gate drops like any
    dead block."""
    if not band:
        return step, None
    j = _first_window_k_block(i, block_q, block_k, window) + step
    return j, j < band


def _band_q_block(j, step, block_q, block_k, band):
    """The same for the dk/dv kernels (``band``: the sequence's number
    of q blocks): the band starts at the first q block the diagonal
    admits for k block j, and the run gate drops what the window does
    not reach."""
    if not band:
        return step, None
    i = (j * block_k) // block_q + step
    return i, i < band


def _block_runs(causal, has_prefix, pref, q_start, k_start, block_q,
                block_k=None, window=0, in_band=None):
    """Run-gate shared by all kernels: a (q,k) block pair participates
    unless it lies entirely above the causal diagonal or (with a
    sliding window) entirely below it — and with a prefix-LM prefix,
    k blocks inside the prefix always participate. ``in_band`` (the
    banded grid: ``_band_k_block``): false for a step that stands for
    no block of the sequence, which runs nothing."""
    run = (not causal) or (k_start <= q_start + block_q - 1)
    if causal and window:
        # the OLDEST q row (q_start) sees back to q_start − window + 1;
        # a k block ending before that is outside every row's window
        run = jnp.logical_and(
            run, k_start + block_k - 1 >= q_start - window + 1
        )
    if causal and has_prefix:
        run = jnp.logical_or(run, k_start < pref)
    if in_band is not None:
        run = jnp.logical_and(run, in_band)
    return run


# sentinel distinguishing "compute the mask here" from a precomputed
# mask (which may legitimately be None for non-causal attention)
_MASK_UNSET = object()


def _allowed_mask(q_start, k_start, block_q, block_k, causal, has_prefix,
                  pref, window=0):
    """The [block_q, block_k] visibility mask (None when unmasked) — the
    ONE place the mask rule's geometry lives; every kernel reaches it
    through ``_masked_scores`` so forward and backward cannot drift.
    Packed kernels call it directly ONCE per program and share the
    result across all packed heads (the mask depends only on positions,
    never on the head)."""
    if not causal:
        return None
    q_pos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    allowed = q_pos >= k_pos
    if window:
        # Mistral-style sliding window: each query sees the last
        # `window` positions (itself included)
        allowed = jnp.logical_and(allowed, q_pos - k_pos < window)
    if has_prefix:
        # GLM-style prefix-LM: keys inside the prefix are visible
        # to every query (bidirectional prefix, causal tail)
        allowed = jnp.logical_or(allowed, k_pos < pref)
    return allowed


def _masked_scores(q, k, scale, q_start, k_start, block_q, block_k,
                   causal, has_prefix, pref, window=0,
                   allowed=_MASK_UNSET, sel_ref=None):
    """q @ kᵀ with the causal / prefix-LM / sliding-window mask.
    ``allowed`` short-circuits the mask computation with a precomputed
    ``_allowed_mask`` result (head-packed kernels build it once and
    apply it to every packed head). ``sel_ref`` (the ``_sel`` kernels):
    this block of the selection, [1, block_q, block_k] int8, nonzero at
    the keys a query chose; a pair counts where the mask admits it AND
    the selection names it."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    if allowed is _MASK_UNSET:
        allowed = _allowed_mask(
            q_start, k_start, block_q, block_k, causal, has_prefix,
            pref, window=window,
        )
    if sel_ref is not None:
        # widened before the compare: the mask of a 32-bit select comes
        # from 32-bit lanes
        chosen = sel_ref[0].astype(jnp.int32) != 0
        allowed = (
            chosen if allowed is None else jnp.logical_and(allowed, chosen)
        )
    if allowed is not None:
        s = jnp.where(allowed, s, NEG_INF)
    return s


def _p_and_ds(s, do, v, lse_col, delta_col, scale):
    """Backward-shared softmax recompute: p from the saved lse, then
    ds = p·(dp − delta)·scale."""
    p = jnp.exp(s - lse_col)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta_col) * scale
    return p, ds


def _softmax_step(s, m_prev, l_prev):
    """One head's online-softmax statistics from masked scores ``s`` —
    the math shared verbatim by the unpacked and head-packed forward
    kernels. Returns (m_new [bq,1], l_new [bq,1], alpha [bq,1] — the
    factor the old accumulator shrinks by — and p [bq,bk] f32)."""
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    return m_new, l_new, alpha, p


def _pv(p, v):
    """p @ v in v's dtype with f32 accumulation."""
    return jax.lax.dot_general(
        p.astype(v.dtype),
        v,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _fwd_kernel(
    q_ref,  # [block_q, d]
    k_ref,  # [block_k, d]
    v_ref,  # [block_k, d]
    prefix_ref,  # [B, 1] int32, whole array in SMEM (None w/o prefix)
    offs_ref,  # [1, 2] int32 (q_off, k_off) in SMEM (None w/o offsets)
    sel_ref,  # [1, block_q, block_k] int8 selection (None w/o one)
    o_ref,  # [block_q, d]
    lse_ref,  # [block_q, 8] f32 (8 lanes to satisfy TPU tiling; col 0 used)
    m_scratch,  # [block_q, 128] f32
    l_scratch,  # [block_q, 128] f32
    acc_scratch,  # [block_q, d] f32
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    has_prefix: bool,
    has_offsets: bool = False,
    n_head: int = 1,
    window: int = 0,
    band: int = 0,  # _band_k_block / _band_q_block
):
    qi = pl.program_id(1)
    step = pl.program_id(2)
    n_steps = pl.num_programs(2)
    ki, in_band = _band_k_block(qi, step, block_q, block_k, window, band)
    # grid dim 0 is batch·heads; the scalar prefix is per-batch
    pref = (
        prefix_ref[pl.program_id(0) // n_head, 0] if has_prefix else None
    )

    @pl.when(step == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    # global offsets (ring attention: this call's q/k blocks sit at
    # traced global positions) shift every position the mask rule sees
    q_start = qi * block_q + (offs_ref[0, 0] if has_offsets else 0)
    k_start = ki * block_k + (offs_ref[0, 1] if has_offsets else 0)

    @pl.when(_block_runs(causal, has_prefix, pref, q_start, k_start,
                         block_q, block_k, window, in_band))
    def _body():
        s = _masked_scores(
            q_ref[0], k_ref[0], scale, q_start, k_start,
            block_q, block_k, causal, has_prefix, pref, window=window,
            sel_ref=sel_ref,
        )
        v, m_prev, l_prev = v_ref[0], m_scratch[:, :1], l_scratch[:, :1]
        acc_prev = acc_scratch[:]
        m_new, l_new, alpha, p = _softmax_step(s, m_prev, l_prev)
        acc_scratch[:] = acc_prev * alpha + _pv(p, v)
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)

    @pl.when(step == n_steps - 1)
    def _finish():
        l = l_scratch[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scratch[:] / l).astype(o_ref.dtype)
        # log-sum-exp per row — the backward's only softmax residual
        lse_ref[0] = jnp.broadcast_to(
            m_scratch[:, :1] + jnp.log(l), lse_ref.shape[1:]
        )


LANES = 128  # a slab: the lane width of one vector register / MXU tile
STAT_LANES = 8  # a row-statistics tile: head p of a slab in lane p


def _and_lanes(x, bits):
    """``x`` [rows, 128] with the lanes whose ``bits`` [1, 128] uint32
    are 0 set to zero, as a 32-bit AND on the raw words: exact, NaN in
    a dropped lane does not survive (a multiply by 0 would keep it), and
    for bf16 one word holds two ROWS of the same lane, so the mask needs
    no 16-bit select (the v5e's vector unit has none)."""
    return pltpu.bitcast(pltpu.bitcast(x, jnp.uint32) & bits, x.dtype)


def _slab_lanes(slab, heads, d, pack):
    """Lane masks of one slab — ``pack`` heads of width ``d`` side by
    side in 128 lanes of the projection's ``[B, S, H·D]`` array.
    Returns (per-head uint32 masks [1, 128], mask of the real lanes or
    None). With ``heads % pack != 0`` the last slab reaches
    past column H·D: what the block holds there is unspecified, those
    lanes are in no head's mask, and ``real`` cleans the operands that
    are used whole."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    ones, zero = jnp.uint32(0xFFFFFFFF), jnp.uint32(0)
    real = lane < (heads - slab * pack) * d if heads % pack else None
    head_bits = []
    for p in range(pack):
        keep = jnp.logical_and(lane >= p * d, lane < (p + 1) * d)
        if real is not None:
            keep = jnp.logical_and(keep, real)
        head_bits.append(jnp.where(keep, ones, zero))
    real_bits = None if real is None else jnp.where(real, ones, zero)
    return head_bits, real_bits


def _spread_heads(cols, d, lanes=LANES):
    """Per-head columns (``pack`` arrays [rows, 1]) → [rows, lanes] with
    head p's value on head p's ``d`` lanes (the last head's on the rest).
    ``d=1, lanes=STAT_LANES``: a row-statistics tile."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    out = cols[-1]
    for p in range(len(cols) - 2, -1, -1):
        out = jnp.where(lane < (p + 1) * d, cols[p], out)
    return out


def _delta_cols(do, out, head_bits=(None,)):
    """delta = Σ_d dO·out per row, a [rows, 1] f32 column for each entry
    of ``head_bits``: f32 products of the stored values, summed over the
    lanes that entry keeps (one head of a slab; None: all of them)."""
    prod = do.astype(jnp.float32) * out.astype(jnp.float32)
    return [
        jnp.sum(
            prod if bits is None else _and_lanes(prod, bits),
            axis=1, keepdims=True,
        )
        for bits in head_bits
    ]


def _fwd_kernel_packed(
    q_ref,  # [1, block_q, 128]: one slab of [B, S, H·D]
    k_ref,  # [1, block_k, 128]
    v_ref,  # [1, block_k, 128]
    prefix_ref,  # [B, 1] int32 in SMEM (None w/o prefix)
    offs_ref,  # [1, 2] int32 in SMEM (None w/o offsets)
    o_ref,  # [1, block_q, 128]
    lse_ref,  # [1, block_q, 8] f32: head p in lane p
    m_scratch,  # [pack, block_q, 128] f32
    l_scratch,  # [pack, block_q, 128] f32
    acc_scratch,  # [block_q, 128] f32: every head on its own lanes
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    has_prefix: bool,
    has_offsets: bool = False,
    heads: int = 2,  # real heads H (the last slab may hold fewer)
    window: int = 0,
    pack: int = 2,
    band: int = 0,
):
    """Head-packed forward on grid (batch, slab, q block, k block): the
    ``pack`` heads of a slab share one program and one mask. Head p's
    scores are ``q @ k_pᵀ`` with ``k_p`` the key tile zeroed outside
    head p's lanes — a 128-deep contraction of which ``d`` lanes are
    live, the MXU passes of a ``d``-deep one — and ``p_p @ v_p`` lands
    on head p's lanes of the one [block_q, 128] accumulator, so the
    output is stored lane-dense. The online softmax is per head, as in
    the unpacked kernel."""
    slab = pl.program_id(1)
    qi = pl.program_id(2)
    step = pl.program_id(3)
    n_steps = pl.num_programs(3)
    ki, in_band = _band_k_block(qi, step, block_q, block_k, window, band)
    d = LANES // pack
    pref = prefix_ref[pl.program_id(0), 0] if has_prefix else None

    @pl.when(step == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    q_start = qi * block_q + (offs_ref[0, 0] if has_offsets else 0)
    k_start = ki * block_k + (offs_ref[0, 1] if has_offsets else 0)

    @pl.when(_block_runs(causal, has_prefix, pref, q_start, k_start,
                         block_q, block_k, window, in_band))
    def _body():
        allowed = _allowed_mask(
            q_start, k_start, block_q, block_k, causal, has_prefix,
            pref, window=window,
        )
        head_bits, real_bits = _slab_lanes(slab, heads, d, pack)
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        if real_bits is not None:
            q = _and_lanes(q, real_bits)
        alphas, pv = [], None
        for p in range(pack):
            s = _masked_scores(
                q, _and_lanes(k, head_bits[p]), scale, q_start, k_start,
                block_q, block_k, causal, has_prefix, pref,
                window=window, allowed=allowed,
            )
            m_new, l_new, alpha, pr = _softmax_step(
                s, m_scratch[p, :, :1], l_scratch[p, :, :1]
            )
            pv_p = _pv(pr, _and_lanes(v, head_bits[p]))
            m_scratch[p] = jnp.broadcast_to(m_new, m_scratch.shape[1:])
            l_scratch[p] = jnp.broadcast_to(l_new, l_scratch.shape[1:])
            alphas.append(alpha)
            pv = pv_p if pv is None else pv + pv_p
        acc_scratch[:] = acc_scratch[:] * _spread_heads(alphas, d) + pv

    @pl.when(step == n_steps - 1)
    def _finish():
        ls, lses = [], []
        for p in range(pack):
            l = l_scratch[p, :, :1]
            l = jnp.where(l == 0.0, 1.0, l)
            lses.append(m_scratch[p, :, :1] + jnp.log(l))
            ls.append(l)
        lse_ref[0] = _spread_heads(lses, 1, STAT_LANES)
        o_ref[0] = (acc_scratch[:] / _spread_heads(ls, d)).astype(
            o_ref.dtype
        )


def _insert_none_args(kernel, idxs):
    """Adapter for optional SMEM args: the kernel signatures always have
    prefix_ref/offs_ref slots (at positional indices ``idxs``, sorted),
    but pallas passes inputs positionally — splice Nones in for the
    absent ones."""

    def call(*refs):
        refs = list(refs)
        for idx in idxs:
            refs.insert(idx, None)
        return kernel(*refs)

    return call


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, out_ref,
    lse_ref,  # [1, block_q, 8] f32, as the forward wrote it
    glse_ref,  # the lse cotangent in the same tiles (None: ring only)
    prefix_ref, offs_ref,
    sel_ref,  # [1, block_q, block_k] int8 selection (None w/o one)
    dq_ref,
    delta_ref,  # [1, block_q, 8] f32: written here, read by the dkv pass
    acc_scratch,  # [block_q, d] f32
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    has_prefix: bool,
    has_offsets: bool = False,
    n_head: int = 1,
    window: int = 0,
    band: int = 0,  # _band_k_block / _band_q_block
):
    """dq = Σ_k ds @ K with ds = p·(dp − delta)·scale, p recomputed from
    the saved lse — FlashAttention-2 backward, k-blocks innermost so dq
    stays resident in VMEM scratch. ``delta`` = Σ_d dO·out (less the lse
    cotangent) is made here, once a q block at its first key step, from
    the dO block the pass holds anyway, and leaves as a second output in
    lse's tile format."""
    qi = pl.program_id(1)
    step = pl.program_id(2)
    n_steps = pl.num_programs(2)
    ki, in_band = _band_k_block(qi, step, block_q, block_k, window, band)
    pref = (
        prefix_ref[pl.program_id(0) // n_head, 0] if has_prefix else None
    )

    @pl.when(step == 0)
    def _init():
        acc_scratch[:] = jnp.zeros_like(acc_scratch)
        (delta,) = _delta_cols(do_ref[0], out_ref[0])
        if glse_ref is not None:
            # total ds = p·(dp − delta + g_lse): subtract here once
            delta = delta - glse_ref[0][:, :1]
        delta_ref[0] = jnp.broadcast_to(delta, delta_ref.shape[1:])

    q_start = qi * block_q + (offs_ref[0, 0] if has_offsets else 0)
    k_start = ki * block_k + (offs_ref[0, 1] if has_offsets else 0)

    @pl.when(_block_runs(causal, has_prefix, pref, q_start, k_start,
                         block_q, block_k, window, in_band))
    def _body():
        k = k_ref[0]
        s = _masked_scores(
            q_ref[0], k, scale, q_start, k_start,
            block_q, block_k, causal, has_prefix, pref, window=window,
            sel_ref=sel_ref,
        )
        _, ds = _p_and_ds(
            s, do_ref[0], v_ref[0],
            lse_ref[0][:, :1], delta_ref[0][:, :1], scale,
        )
        acc_scratch[:] = acc_scratch[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(step == n_steps - 1)
    def _finish():
        dq_ref[0] = acc_scratch[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, prefix_ref,
    offs_ref,
    sel_ref,  # [1, block_q, block_k] int8 selection (None w/o one)
    dk_ref, dv_ref,
    dk_scratch,  # [block_k, d] f32
    dv_scratch,  # [block_k, d] f32
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    has_prefix: bool,
    has_offsets: bool = False,
    n_head: int = 1,
    window: int = 0,
    band: int = 0,  # _band_k_block / _band_q_block
):
    """dk/dv accumulated per k-block with q-blocks innermost:
    dv = Σ_q pᵀ @ dO, dk = Σ_q dsᵀ @ Q."""
    ki = pl.program_id(1)
    step = pl.program_id(2)
    n_steps = pl.num_programs(2)
    qi, in_band = _band_q_block(ki, step, block_q, block_k, band)
    pref = (
        prefix_ref[pl.program_id(0) // n_head, 0] if has_prefix else None
    )

    @pl.when(step == 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    q_start = qi * block_q + (offs_ref[0, 0] if has_offsets else 0)
    k_start = ki * block_k + (offs_ref[0, 1] if has_offsets else 0)

    @pl.when(_block_runs(causal, has_prefix, pref, q_start, k_start,
                         block_q, block_k, window, in_band))
    def _body():
        q = q_ref[0]
        do = do_ref[0]
        s = _masked_scores(
            q, k_ref[0], scale, q_start, k_start,
            block_q, block_k, causal, has_prefix, pref, window=window,
            sel_ref=sel_ref,
        )
        p, ds = _p_and_ds(
            s, do, v_ref[0],
            lse_ref[0][:, :1], delta_ref[0][:, :1], scale,
        )
        dv_scratch[:] = dv_scratch[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_scratch[:] = dk_scratch[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(step == n_steps - 1)
    def _finish():
        dk_ref[0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[:].astype(dv_ref.dtype)


def _bwd_dq_kernel_packed(
    q_ref, k_ref, v_ref, do_ref, out_ref,  # [1, block, 128] slabs
    lse_ref,  # [1, block_q, 8] f32, as the forward wrote it
    glse_ref,  # the lse cotangent in the same tiles (None: ring only)
    prefix_ref, offs_ref,
    dq_ref,  # [1, block_q, 128]
    delta_ref,  # [1, block_q, 8] f32: written here, head p in lane p
    acc_scratch,  # [block_q, 128] f32
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    has_prefix: bool,
    has_offsets: bool = False,
    heads: int = 2,
    window: int = 0,
    pack: int = 2,
    band: int = 0,
):
    """Head-packed dq pass on grid (batch, slab, q block, k block): the
    recomputed-p backward per head under ONE shared mask, heads kept
    apart by zeroing k and v outside head p's lanes (see
    _fwd_kernel_packed); ``ds_p @ k_p`` is then nonzero on head p's
    lanes only and the heads' dq sum into one lane-dense tile. Each
    head's ``delta`` is the row sum of dO·out over its own lanes (see
    _bwd_dq_kernel); lanes past H·D are in no head's sum."""
    slab = pl.program_id(1)
    qi = pl.program_id(2)
    step = pl.program_id(3)
    n_steps = pl.num_programs(3)
    ki, in_band = _band_k_block(qi, step, block_q, block_k, window, band)
    d = LANES // pack
    pref = prefix_ref[pl.program_id(0), 0] if has_prefix else None

    @pl.when(step == 0)
    def _init():
        acc_scratch[:] = jnp.zeros_like(acc_scratch)
        head_bits, _ = _slab_lanes(slab, heads, d, pack)
        deltas = _delta_cols(do_ref[0], out_ref[0], head_bits)
        if glse_ref is not None:
            deltas = [
                delta - glse_ref[0][:, p:p + 1]
                for p, delta in enumerate(deltas)
            ]
        delta_ref[0] = _spread_heads(deltas, 1, STAT_LANES)

    q_start = qi * block_q + (offs_ref[0, 0] if has_offsets else 0)
    k_start = ki * block_k + (offs_ref[0, 1] if has_offsets else 0)

    @pl.when(_block_runs(causal, has_prefix, pref, q_start, k_start,
                         block_q, block_k, window, in_band))
    def _body():
        allowed = _allowed_mask(
            q_start, k_start, block_q, block_k, causal, has_prefix,
            pref, window=window,
        )
        head_bits, real_bits = _slab_lanes(slab, heads, d, pack)
        q, do, k, v = q_ref[0], do_ref[0], k_ref[0], v_ref[0]
        if real_bits is not None:
            q, do = _and_lanes(q, real_bits), _and_lanes(do, real_bits)
        dq = None
        for p in range(pack):
            k_p = _and_lanes(k, head_bits[p])
            s = _masked_scores(
                q, k_p, scale, q_start, k_start,
                block_q, block_k, causal, has_prefix, pref,
                window=window, allowed=allowed,
            )
            _, ds = _p_and_ds(
                s, do, _and_lanes(v, head_bits[p]),
                lse_ref[0][:, p:p + 1], delta_ref[0][:, p:p + 1], scale,
            )
            dq_p = jax.lax.dot_general(
                ds.astype(k.dtype), k_p, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dq = dq_p if dq is None else dq + dq_p
        acc_scratch[:] = acc_scratch[:] + dq

    @pl.when(step == n_steps - 1)
    def _finish():
        dq_ref[0] = acc_scratch[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel_packed(
    q_ref, k_ref, v_ref, do_ref,  # [1, block, 128] slabs of [B, S, H·D]
    lse_ref, delta_ref,  # [1, block_q, 8] f32: head p in lane p
    prefix_ref, offs_ref,
    dk_ref, dv_ref,  # [1, block_k, 128]
    dk_scratch,  # [block_k, 128] f32
    dv_scratch,  # [block_k, 128] f32
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    has_prefix: bool,
    has_offsets: bool = False,
    heads: int = 2,
    window: int = 0,
    pack: int = 2,
    band: int = 0,
):
    """Head-packed dk/dv pass on grid (batch, slab, k block, q block),
    q-blocks innermost: here q and dO are the operands zeroed outside
    head p's lanes, so ``pᵀ @ dO_p`` and ``dsᵀ @ q_p`` land on head
    p's lanes of the dv and dk tiles."""
    slab = pl.program_id(1)
    ki = pl.program_id(2)
    step = pl.program_id(3)
    n_steps = pl.num_programs(3)
    qi, in_band = _band_q_block(ki, step, block_q, block_k, band)
    d = LANES // pack
    pref = prefix_ref[pl.program_id(0), 0] if has_prefix else None

    @pl.when(step == 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    q_start = qi * block_q + (offs_ref[0, 0] if has_offsets else 0)
    k_start = ki * block_k + (offs_ref[0, 1] if has_offsets else 0)

    @pl.when(_block_runs(causal, has_prefix, pref, q_start, k_start,
                         block_q, block_k, window, in_band))
    def _body():
        allowed = _allowed_mask(
            q_start, k_start, block_q, block_k, causal, has_prefix,
            pref, window=window,
        )
        head_bits, real_bits = _slab_lanes(slab, heads, d, pack)
        q, do, k, v = q_ref[0], do_ref[0], k_ref[0], v_ref[0]
        if real_bits is not None:
            k, v = _and_lanes(k, real_bits), _and_lanes(v, real_bits)
        dk = dv = None
        for p in range(pack):
            q_p = _and_lanes(q, head_bits[p])
            do_p = _and_lanes(do, head_bits[p])
            s = _masked_scores(
                q_p, k, scale, q_start, k_start,
                block_q, block_k, causal, has_prefix, pref,
                window=window, allowed=allowed,
            )
            pr, ds = _p_and_ds(
                s, do_p, v,
                lse_ref[0][:, p:p + 1], delta_ref[0][:, p:p + 1], scale,
            )
            dv_p = jax.lax.dot_general(
                pr.astype(do.dtype), do_p, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dk_p = jax.lax.dot_general(
                ds.astype(q.dtype), q_p, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dv = dv_p if dv is None else dv + dv_p
            dk = dk_p if dk is None else dk + dk_p
        dv_scratch[:] = dv_scratch[:] + dv
        dk_scratch[:] = dk_scratch[:] + dk

    @pl.when(step == n_steps - 1)
    def _finish():
        dk_ref[0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[:].astype(dv_ref.dtype)


def _sel_rows(selected, h):
    """(the selection as ``[rows, Sq, Sk]``, row(g) of grid program g =
    batch x H + head): ``[B, Sq, Sk]``, one mask for the H heads of a
    sequence, as it is, row ``g // H``; ``[B, G, Sq, Sk]``, a mask for
    each of G groups of H / G consecutive heads (a KV head's query
    heads), batch-major."""
    if selected is None or selected.ndim == 3:
        return selected, lambda g: g // h
    b, groups, sq, sk = selected.shape
    per = h // groups
    return (
        selected.reshape(b * groups, sq, sk),
        lambda g: (g // h) * groups + (g % h) // per,
    )


def _optional_smem(kernel, prefix, offsets, batch, at, selected=None,
                   sel_spec=None):
    """The optional operands every kernel takes: in SMEM the per-batch
    prefix-LM lengths and the (q, k) global offsets, and (the unpacked
    kernels) the selection ``selected`` [B, Sq, Sk] int8 through
    ``sel_spec``. Returns (arrays, specs, kernel) with None spliced into
    the kernel's ``prefix_ref`` / ``offs_ref`` / ``sel_ref`` slots
    (positions ``at``, ``at + 1``, ``at + 2``; ``sel_spec`` None: the
    kernel has no third slot) for whichever is absent."""
    arrays, none_idxs = [], []
    if prefix is not None:
        # the whole [B,1] scalar table lives in SMEM; the kernel indexes
        # its batch row from the grid (Mosaic rejects sub-8 sublane
        # blocking, so no per-step BlockSpec windowing here)
        arrays.append(prefix.astype(jnp.int32).reshape(batch, 1))
    else:
        none_idxs.append(at)
    if offsets is not None:
        arrays.append(offsets.astype(jnp.int32).reshape(1, 2))
    else:
        none_idxs.append(at + 1)
    specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] * len(arrays)
    if selected is not None:
        arrays.append(selected)
        specs.append(sel_spec)
    elif sel_spec is not None:
        none_idxs.append(at + 2)
    if none_idxs:
        kernel = _insert_none_args(kernel, none_idxs)
    return arrays, specs, kernel


def _k_run_blocks(i, block_q, block_k, n_k_blocks, window):
    """How many k blocks the causal run gate admits for q block i: the
    run from the first block a live window admits (block 0 with none) to
    the diagonal or the sequence's end. The band's extent is the longest
    of these, ``forward_keys`` their mean."""
    first = 0
    if _live_window(window, n_k_blocks * block_k):
        first = _first_window_k_block(i, block_q, block_k, window)
    last = _lower(_last_visible_k_block(i, block_q, block_k), n_k_blocks - 1)
    return last - first + 1


def forward_keys(sq, sk, block_q, block_k, causal=True, window=0) -> float:
    """The mean number of keys a query's FORWARD kernel multiplies —
    what ``flash_fwd*`` executes, not what the mask lets through: at the
    tile ``flash_attention`` fits to the caller's ``block_q`` /
    ``block_k``, every k block of each q block's run (``_k_run_blocks``:
    what the run gate admits, and the banded grid walks) times the key
    tile, averaged over the q blocks; every key without the causal mask.
    A selection of keys runs every causal block (``window`` 0); a
    prefix adds the blocks it makes live above the diagonal, known only
    at run time and not counted. 0.0 where no tile fits the sequence:
    the kernels do not run."""
    bq, bk = _fit_block(sq, block_q), _fit_block(sk, block_k)
    if bq is None or bk is None:
        return 0.0
    if not causal:
        return float(sk)
    n_q, n_k = sq // bq, sk // bk
    blocks = sum(_k_run_blocks(i, bq, bk, n_k, window) for i in range(n_q))
    return blocks * bk / n_q


def _inner_grid(causal_clamp, block_q, block_k, n_q_blocks, n_k_blocks,
                window):
    """The kernels' inner grid axis: ``(band, (steps, k_block), (steps,
    q_block))`` — whether the axis is the band, then its extent and
    index map for the forward and dq grids (``k_block(i, step)``: the k
    block that step of q block i fetches) and for the dk/dv grid
    (``q_block(j, step)``).

    The square: a step is a block, ``nk`` and ``nq`` of them, and under
    ``causal_clamp`` the run gate's dead blocks are clamped onto a live
    neighbour — a compute-skipped block still costs its DMA under a
    naive index map, while re-addressing the SAME block is not
    refetched (``causal_clamp``: ``_gate_is_static``; else identity).

    The band (the clamp and a live window): the blocks a window admits for a
    q block are a run that starts at ``_first_window_k_block`` and ends
    at the diagonal, so the axis walks that run and no more — as many
    steps as the widest q block's run (3 at a window of 2,048 keys and
    tiles of 1,024, 5 at tiles of 512), step s of q block i standing
    for block first(i) + s (``_band_k_block``). A q block with a
    shorter run (the sequence's first ones) re-addresses its last block
    for the steps left over, and the run gate, which sees the block the
    step stands for and not the one fetched, keeps the accumulators from
    seeing a block twice. The dk/dv grid likewise from
    ``_first_visible_q_block`` to ``_last_window_q_block``."""
    band = causal_clamp and _live_window(window, n_k_blocks * block_k)
    k_steps, q_steps = n_k_blocks, n_q_blocks
    if band:
        k_steps = max(
            _k_run_blocks(i, block_q, block_k, n_k_blocks, window)
            for i in range(n_q_blocks)
        )
        q_steps = max(
            _last_window_q_block(j, n_q_blocks, block_q, block_k, window)
            - _first_visible_q_block(j, n_q_blocks, block_q, block_k) + 1
            for j in range(n_k_blocks)
        )

    def k_block(i, step):
        if not causal_clamp:
            return step
        last = _last_visible_k_block(i, block_q, block_k)
        if band:
            step, _ = _band_k_block(
                i, step, block_q, block_k, window, n_k_blocks
            )
            last = _lower(last, n_k_blocks - 1)
        return _lower(step, last)

    def q_block(j, step):
        if not causal_clamp:
            return step
        if band:
            step, _ = _band_q_block(j, step, block_q, block_k, n_q_blocks)
            return _lower(
                step,
                _last_window_q_block(
                    j, n_q_blocks, block_q, block_k, window
                ),
            )
        return _upper(
            step, _first_visible_q_block(j, n_q_blocks, block_q, block_k)
        )

    return band, (k_steps, k_block), (q_steps, q_block)


def _slab_view(x):
    """``[B, S, H, D]`` as the projection's own ``[B, S, H·D]`` (free)."""
    b, s, h, d = x.shape
    return x.reshape(b, s, h * d)


def _slab_count(h, hkv, d, pack):
    """128-lane slabs across ``H·D`` columns; the last may not be full."""
    assert h == hkv and pack * d == LANES, (
        "head packing needs MHA heads that fill a 128-lane slab"
    )
    return -(-h // pack)


def _grid_params(interpret, n_grid):
    """Every grid here carries its accumulator over the last dimension
    and nothing over the others."""
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (n_grid - 1) + ("arbitrary",),
    )


def _stat_rows(tiles, b, h, pack):
    """``[B, H, S]`` from per-row statistics in the kernels' tiles,
    ``[B·slabs, S, 8]`` with head p of a slab in lane p (unpacked: a slab
    is one head): for a caller that asked for lse, and the jnp fallback."""
    s, pack = tiles.shape[1], max(int(pack), 1)
    heads = tiles[..., :pack].reshape(b, -1, s, pack).transpose(0, 1, 3, 2)
    return heads.reshape(b, -1, s)[:, :h]


def _stat_tiles(rows, pack):
    """``[B, H, S]`` statistics in the kernels' float32 tiles,
    ``[B·slabs, S, 8]`` (heads past H: zeros): ``_stat_rows``' inverse.
    The way in for the ring path's lse cotangent and for an ``lse`` kept
    as numbers (``lse_rows``)."""
    b, h, s = rows.shape
    pack = max(int(pack), 1)
    n_slabs = -(-h // pack)
    rows = jnp.pad(rows, ((0, 0), (0, n_slabs * pack - h), (0, 0)))
    heads = rows.astype(jnp.float32).reshape(b * n_slabs, pack, s)
    return jnp.pad(
        heads.transpose(0, 2, 1), ((0, 0), (0, 0), (0, STAT_LANES - pack))
    )


def _pallas_backward(q, k, v, out, lse, g, causal, scale,
                     block_q, block_k, prefix=None,
                     interpret: Optional[bool] = None,
                     g_lse=None, window: int = 0, offsets=None,
                     head_pack: int = 1, selected=None):
    """FA2-style pallas backward: returns (dq, dk, dv, delta).

    All [B,S,H,D] layouts like the forward; GQA dk/dv are group-summed
    back to the kv head count. ``lse`` is the forward kernel's own tile
    array (``_flash_fwd``), read through the BlockSpec it was written
    with; ``delta`` = Σ_d dO·out leaves the dq kernel in the same tiles
    and the dk/dv kernel reads it there (returned for the tests), so no
    XLA operation passes over the row statistics. ``g_lse`` [B,H,S]
    (ring attention's lse cotangent) folds into delta in the dq kernel
    — ∂lse/∂s_j = p_j, so it enters ds as an additive term.

    ``head_pack`` > 1 runs the head-packed kernels on 128-lane slabs of
    the ``[B, S, H·D]`` view (MHA with ``head_pack · D == 128`` only).
    ``selected`` [B, Sq, Sk] int8 (``_flash_fwd``; unpacked only): the
    ``_sel`` kernels, which recompute p over the selected keys.
    """
    interpret = INTERPRET if interpret is None else interpret
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    groups = h // hkv
    pack = max(int(head_pack), 1)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    assert selected is None or pack == 1
    nq, nk = sq // block_q, sk // block_k

    common = dict(
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        has_prefix=prefix is not None,
        has_offsets=offsets is not None,
        window=window,
    )
    band, (k_steps, k_block), (q_steps, q_block) = _inner_grid(
        _gate_is_static(causal, prefix, offsets),
        block_q, block_k, nq, nk, window,
    )
    # the kernels on the banded grid count their blocks from the band's
    # first one and need the sequence's block count to stop at its end
    dq_band, dkv_band = nk * band, nq * band
    g = g.astype(q.dtype)
    selected, sel_row = _sel_rows(selected, h)
    glse = () if g_lse is None else (_stat_tiles(g_lse, pack),)
    stat_struct = _out_struct(lse.shape, lse.dtype, q)

    def dq_kernel(kernel, sel_spec=None):
        """(optional arrays, their specs, kernel) of a dq pass, whose
        slots 6-8 (lse cotangent, prefix, offsets) are all optional, as
        is the unpacked kernel's selection after them."""
        extra, extra_specs, kernel = _optional_smem(
            kernel, prefix, offsets, b, at=7, selected=selected,
            sel_spec=sel_spec,
        )
        if g_lse is None:
            kernel = _insert_none_args(kernel, [6])
        return extra, extra_specs, kernel

    if pack > 1:
        # the kernels read q, k, v, dO, out and write dq, dk, dv as
        # column slabs of the projections' own [B, S, H·D] arrays: the
        # index map does what a transpose to [B, H, S, D] did. MHA only,
        # so no GQA index sharing or group-sum.
        n_slabs = _slab_count(h, hkv, d, pack)
        qs, ks, vs, dos, outs = (_slab_view(x) for x in (q, k, v, g, out))
        common_p = dict(common, heads=h, pack=pack)

        def spec(rows, block):  # block index (batch, slab, step i, step j)
            return pl.BlockSpec(
                (1, rows, LANES), lambda b_, s_, i, j: (b_, block(i, j), s_)
            )

        def row8_spec(block):
            return pl.BlockSpec(
                (1, block_q, 8),
                lambda b_, s_, i, j: (b_ * n_slabs + s_, block(i, j), 0),
            )

        def slab_struct(s_len, dtype):
            return _out_struct((b, s_len, h * d), dtype, q)

        first = lambda i, j: i  # noqa: E731 — this grid step's own block
        extra, extra_specs, kernel = dq_kernel(
            functools.partial(_bwd_dq_kernel_packed, **common_p, band=dq_band)
        )
        dq, delta = pl.pallas_call(
            kernel,
            grid=(b, n_slabs, nq, k_steps),
            in_specs=[spec(block_q, first), spec(block_k, k_block),
                      spec(block_k, k_block), spec(block_q, first),
                      spec(block_q, first),
                      *[row8_spec(first)] * (1 + len(glse)), *extra_specs],
            out_specs=[spec(block_q, first), row8_spec(first)],
            out_shape=[slab_struct(sq, q.dtype), stat_struct],
            scratch_shapes=[pltpu.VMEM((block_q, LANES), jnp.float32)],
            compiler_params=_grid_params(interpret, 4),
            interpret=interpret,
            name="flash_bwd_dq_packed",
        )(qs, ks, vs, dos, outs, lse, *glse, *extra)

        extra, extra_specs, kernel = _optional_smem(
            functools.partial(
                _bwd_dkv_kernel_packed, **common_p, band=dkv_band
            ),
            prefix, offsets, b, at=6,
        )
        dk, dv = pl.pallas_call(
            kernel,
            grid=(b, n_slabs, nk, q_steps),
            in_specs=[spec(block_q, q_block), spec(block_k, first),
                      spec(block_k, first), spec(block_q, q_block),
                      row8_spec(q_block), row8_spec(q_block),
                      *extra_specs],
            out_specs=[spec(block_k, first), spec(block_k, first)],
            out_shape=[slab_struct(sk, k.dtype), slab_struct(sk, v.dtype)],
            scratch_shapes=[
                pltpu.VMEM((block_k, LANES), jnp.float32),
                pltpu.VMEM((block_k, LANES), jnp.float32),
            ],
            compiler_params=_grid_params(interpret, 4),
            interpret=interpret,
            name="flash_bwd_dkv_packed",
        )(qs, ks, vs, dos, lse, delta, *extra)
        return (
            dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            delta,
        )

    qt, dot, outt = (
        x.transpose(0, 2, 1, 3).reshape(b * h, sq, d) for x in (q, g, out)
    )
    # K/V stay at hkv heads; the BlockSpec index_map shares them across
    # the head group (no jnp.repeat HBM copies). dk/dv are still written
    # per q-head and group-summed after — a transient the accumulate-in-
    # VMEM alternative would trade for an 'arbitrary' grid dim.
    kt = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    # grid-dim-0 entries per batch: the prefix SMEM row index is
    # program_id(0) // n_head
    common["n_head"] = h

    # dq grid (g, q-block i, k-block j)
    q_spec = pl.BlockSpec((1, block_q, d), lambda g_, i, j: (g_, i, 0))
    row8_spec = pl.BlockSpec((1, block_q, 8), lambda g_, i, j: (g_, i, 0))

    def k_idx(g_, i, j):
        j = k_block(i, j)
        return (g_ // groups, j, 0)

    k_spec = pl.BlockSpec((1, block_k, d), k_idx)
    sel = "" if selected is None else "_sel"
    extra, extra_specs, kernel = dq_kernel(
        functools.partial(_bwd_dq_kernel, **common, band=dq_band),
        pl.BlockSpec(
            (1, block_q, block_k),
            lambda g_, i, j: (sel_row(g_), i, k_block(i, j)),
        ),
    )
    dq, delta = pl.pallas_call(
        kernel,
        grid=(b * h, nq, k_steps),
        in_specs=[q_spec, k_spec, k_spec, q_spec, q_spec,
                  *[row8_spec] * (1 + len(glse)), *extra_specs],
        out_specs=[q_spec, row8_spec],
        out_shape=[_out_struct((b * h, sq, d), q.dtype, q), stat_struct],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_grid_params(interpret, 3),
        interpret=interpret,
        name="flash_bwd_dq" + sel,
    )(qt, kt, vt, dot, outt, lse, *glse, *extra)

    # dkv grid swaps the roles: k-blocks outer, q-blocks inner
    qkv_spec = pl.BlockSpec(
        (1, block_q, d), lambda g_, j, i: (g_, q_block(j, i), 0)
    )
    row8_spec2 = pl.BlockSpec(
        (1, block_q, 8), lambda g_, j, i: (g_, q_block(j, i), 0)
    )
    kv_in_spec = pl.BlockSpec(
        (1, block_k, d), lambda g_, j, i: (g_ // groups, j, 0)
    )
    kv_spec = pl.BlockSpec((1, block_k, d), lambda g_, j, i: (g_, j, 0))
    extra, extra_specs, kernel = _optional_smem(
        functools.partial(_bwd_dkv_kernel, **common, band=dkv_band),
        prefix, offsets, b,
        at=6, selected=selected,
        sel_spec=pl.BlockSpec(
            (1, block_q, block_k),
            lambda g_, j, i: (sel_row(g_), q_block(j, i), j),
        ),
    )
    dk, dv = pl.pallas_call(
        kernel,
        grid=(b * h, nk, q_steps),
        in_specs=[qkv_spec, kv_in_spec, kv_in_spec, qkv_spec, row8_spec2,
                  row8_spec2, *extra_specs],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            _out_struct((b * h, sk, d), k.dtype, q),
            _out_struct((b * h, sk, d), v.dtype, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_grid_params(interpret, 3),
        interpret=interpret,
        name="flash_bwd_dkv" + sel,
    )(qt, kt, vt, dot, lse, delta, *extra)

    dq = dq.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    dk = dk.reshape(b, hkv, groups, sk, d).sum(axis=2)
    dv = dv.reshape(b, hkv, groups, sk, d).sum(axis=2)
    return (
        dq.astype(q.dtype),
        dk.transpose(0, 2, 1, 3).astype(k.dtype),
        dv.transpose(0, 2, 1, 3).astype(v.dtype),
        delta,
    )


def _flash_fwd(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    interpret: Optional[bool] = None,
    prefix: Optional[jax.Array] = None,  # [B] int32 prefix-LM lengths
    window: int = 0,  # sliding window (causal only; 0 = unlimited)
    offsets: Optional[jax.Array] = None,  # [2] int32 global (q_off, k_off)
    head_pack: int = 1,  # heads per 128-lane slab (MHA, pack · D == 128)
    selected: Optional[jax.Array] = None,  # [B, (G,) Sq, Sk] int8 (unpacked)
):
    """(out [B, S, H, D], lse f32 as the kernel wrote it: 8-lane tiles
    ``[B·slabs, S, 8]``, head p of a slab in lane p (unpacked: ``[B·H, S,
    8]``) — what the backward kernels read; ``_stat_rows`` gives the
    ``[B, H, S]`` view)."""
    interpret = INTERPRET if interpret is None else interpret
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    assert h % hkv == 0
    groups = h // hkv
    pack = max(int(head_pack), 1)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (
        "sequence must be padded to the block size"
    )
    assert selected is None or pack == 1, "the selection runs unpacked"
    nq, nk = sq // block_q, sk // block_k
    sel_spec = None
    selected, sel_row = _sel_rows(selected, h)
    common = dict(
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        has_prefix=prefix is not None,
        has_offsets=offsets is not None,
        window=window,
    )
    band, (k_steps, k_block), _ = _inner_grid(
        _gate_is_static(causal, prefix, offsets),
        block_q, block_k, nq, nk, window,
    )
    common["band"] = nk * band

    if pack > 1:
        # [B, S, H, D] is a free view of the projection's [B, S, H·D]:
        # grid program (batch, slab, i, j) takes the 128 columns of slab
        # — ``pack`` heads side by side — as they lie, and writes the
        # output the same way; nothing is transposed, padded or sliced
        # in memory. With H % pack != 0 the last slab is half outside
        # the array: read unspecified (the kernel zeroes those lanes),
        # written nowhere.
        n_slabs = _slab_count(h, hkv, d, pack)
        kernel = functools.partial(
            _fwd_kernel_packed, **common, heads=h, pack=pack
        )
        inputs = tuple(_slab_view(x) for x in (q, k, v))
        grid = (b, n_slabs, nq, k_steps)
        q_spec = pl.BlockSpec(
            (1, block_q, LANES), lambda b_, s_, i, j: (b_, i, s_)
        )
        kv_spec = pl.BlockSpec(
            (1, block_k, LANES),
            lambda b_, s_, i, j: (b_, k_block(i, j), s_),
        )
        in_specs = [q_spec, kv_spec, kv_spec]
        out_specs = [
            q_spec,
            pl.BlockSpec(
                (1, block_q, 8),
                lambda b_, s_, i, j: (b_ * n_slabs + s_, i, 0),
            ),
        ]
        out_shape = [
            _out_struct((b, sq, h * d), q.dtype, q),
            _out_struct((b * n_slabs, sq, 8), jnp.float32, q),
        ]
        scratch_shapes = [
            pltpu.VMEM((pack, block_q, 128), jnp.float32),
            pltpu.VMEM((pack, block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ]
    else:
        # layout: [B·H, S, D] so the matmul dims are the minor two. K/V
        # stay at hkv heads — GQA sharing happens in the BlockSpec
        # index_map (g // groups), never as a materialized jnp.repeat
        # in HBM.
        kernel = functools.partial(_fwd_kernel, **common, n_head=h)
        inputs = (
            q.transpose(0, 2, 1, 3).reshape(b * h, sq, d),
            k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d),
            v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d),
        )
        grid = (b * h, nq, k_steps)
        kv_spec = pl.BlockSpec(
            (1, block_k, d),
            lambda g, i, j: (g // groups, k_block(i, j), 0),
        )
        in_specs = [
            pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
            kv_spec,
            kv_spec,
        ]
        out_specs = [
            pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_q, 8), lambda g, i, j: (g, i, 0)),
        ]
        out_shape = [
            _out_struct((b * h, sq, d), q.dtype, q),
            _out_struct((b * h, sq, 8), jnp.float32, q),
        ]
        scratch_shapes = [
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ]
        # one [block_q, block_k] tile of the selection a grid step, the
        # same for the h heads of a sequence (or of a group of them)
        sel_spec = pl.BlockSpec(
            (1, block_q, block_k),
            lambda g, i, j: (sel_row(g), i, k_block(i, j)),
        )

    extra, extra_specs, kernel = _optional_smem(
        kernel, prefix, offsets, b, at=3, selected=selected,
        sel_spec=sel_spec,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[*in_specs, *extra_specs],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=_grid_params(interpret, len(grid)),
        interpret=interpret,
        name=(
            "flash_fwd_packed" if pack > 1
            else "flash_fwd" if selected is None else "flash_fwd_sel"
        ),
    )(*inputs, *extra)
    if pack > 1:
        out = out.reshape(b, sq, h, d)
    else:
        out = out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return out, lse


def _bwd_chunk(sk: int, block_k: int) -> int:
    """Largest chunk ≤ min(block_k, BACKWARD_CHUNK) that divides sk —
    the memory cap must never violate the sk % chunk == 0 invariant
    (e.g. block_k=1280 with sk=2560 must not cap to 1024)."""
    cap = max(1, min(block_k, BACKWARD_CHUNK, sk))
    for c in range(cap, 0, -1):
        if sk % c == 0:
            return c
    return 1


def _chunked_backward(q, k, v, out, lse, g, causal, scale, chunk,
                      g_lse=None, prefix=None, window=0, offsets=None):
    """True O(S·chunk) flash backward from saved (out, lse).

    ``g_lse`` [B,H,S]: optional cotangent of the lse output (ring
    attention's softmax-merge differentiates through lse). Since
    ∂lse/∂s_j = p_j, it enters ds as an additive per-row term.

    Recomputes p = exp(s − lse) one key-chunk at a time (lax.scan), never
    materialising the [S, S] attention matrix — the memory property the
    reference's CUDA flash-attention backward has and a plain vjp through
    a softmax attention lacks. GQA: kv heads are expanded for the compute
    and group-summed for dk/dv.

    Layout: [B, H, S, D] throughout; f32 accumulation.
    """
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    groups = h // hkv
    # GQA layout [B, Hkv, G, S, D]: K/V stay at hkv heads — expanding them
    # by jnp.repeat would multiply KV memory by `groups` for the whole
    # sequence, exactly the footprint flash attention exists to avoid
    qt = (
        q.transpose(0, 2, 1, 3)
        .reshape(b, hkv, groups, sq, d)
        .astype(jnp.float32)
    )
    gt = (
        g.transpose(0, 2, 1, 3)
        .reshape(b, hkv, groups, sq, d)
        .astype(jnp.float32)
    )
    ot = (
        out.transpose(0, 2, 1, 3)
        .reshape(b, hkv, groups, sq, d)
        .astype(jnp.float32)
    )
    kt = k.transpose(0, 2, 1, 3).astype(jnp.float32)   # [B,Hkv,Sk,D]
    vt = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    lse_g = lse.reshape(b, hkv, groups, sq)
    delta = jnp.sum(gt * ot, axis=-1)                  # [B,Hkv,G,Sq]
    if g_lse is not None:
        # fold the lse cotangent into the per-row correction: total
        # ds = p·(dp − delta + g_lse)
        delta = delta - g_lse.reshape(b, hkv, groups, sq).astype(
            jnp.float32
        )

    chunk = min(chunk, sk)
    n_chunks = sk // chunk
    assert sk % chunk == 0
    k_chunks = kt.reshape(b, hkv, n_chunks, chunk, d)
    v_chunks = vt.reshape(b, hkv, n_chunks, chunk, d)
    q_pos = jnp.arange(sq)
    if offsets is not None:
        q_pos = q_pos + offsets.reshape(-1)[0]

    def body(dq_acc, idx):
        kc = k_chunks[:, :, idx]                       # [B,Hkv,C,D]
        vc = v_chunks[:, :, idx]
        s = jnp.einsum("bkgqd,bkcd->bkgqc", qt, kc) * scale
        if causal:
            k_pos = idx * chunk + jnp.arange(chunk)
            if offsets is not None:
                k_pos = k_pos + offsets.reshape(-1)[1]
            mask = q_pos[:, None] >= k_pos[None, :]
            if window:
                mask = mask & (
                    q_pos[:, None] - k_pos[None, :] < window
                )
            if prefix is not None:
                # bidirectional prefix: [B,1,1,Q,C] per-batch mask
                pmask = (
                    mask[None]
                    | (k_pos[None, None, :] < prefix[:, None, None])
                )
                s = jnp.where(pmask[:, None, None], s, NEG_INF)
            else:
                s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lse_g[..., None])              # [B,Hkv,G,Q,C]
        dv_c = jnp.einsum("bkgqc,bkgqd->bkcd", p, gt)
        dp = jnp.einsum("bkgqd,bkcd->bkgqc", gt, vc)
        ds = p * (dp - delta[..., None]) * scale
        dk_c = jnp.einsum("bkgqc,bkgqd->bkcd", ds, qt)
        dq_acc = dq_acc + jnp.einsum("bkgqc,bkcd->bkgqd", ds, kc)
        return dq_acc, (dk_c, dv_c)

    dq, (dk_chunks, dv_chunks) = jax.lax.scan(
        body, jnp.zeros_like(qt), jnp.arange(n_chunks)
    )
    # scan stacks on axis 0: [n_chunks, B, Hkv, C, D] → [B, Hkv, Sk, D]
    dk = dk_chunks.transpose(1, 2, 0, 3, 4).reshape(b, hkv, sk, d)
    dv = dv_chunks.transpose(1, 2, 0, 3, 4).reshape(b, hkv, sk, d)
    dq = dq.reshape(b, h, sq, d)
    return (
        dq.transpose(0, 2, 1, 3).astype(q.dtype),
        dk.transpose(0, 2, 1, 3).astype(k.dtype),
        dv.transpose(0, 2, 1, 3).astype(v.dtype),
    )


def _name_residuals(out, lse, q, head_pack, lse_rows):
    """The forward kernel's two results under the names a remat policy
    lists to keep them and not run the kernel again (the decoder's
    ``full`` at long spans): (out, lse as the
    backward rule's residual). ``flash_lse`` names the statistics as
    NUMBERS, ``[B, H, S]`` float32, where the caller says a policy
    keeps them (``lse_rows``): 1/128 of the tile array, which the
    backward rule builds again (``_residual_tiles``). Where nothing
    keeps them the residual is the tile array as the kernel wrote it
    and XLA makes no pass over the statistics."""
    if lse_rows:
        lse = _stat_rows(lse, q.shape[0], q.shape[2], head_pack)
    return checkpoint_name(out, "flash_out"), checkpoint_name(lse, "flash_lse")


def _residual_rows(lse, q, head_pack, lse_rows):
    """``[B, H, S]`` from ``_name_residuals``' lse: for a caller that
    asked for lse, and the jnp fallback."""
    if lse_rows:
        return lse
    return _stat_rows(lse, q.shape[0], q.shape[2], head_pack)


def _residual_tiles(lse, head_pack, lse_rows):
    """The tile array the backward kernels read, from the same."""
    return _stat_tiles(lse, head_pack) if lse_rows else lse


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11)
)
def _flash_attention(q, k, v, prefix, offsets, causal, scale, block_q,
                     block_k, window=0, head_pack=1, lse_rows=False):
    out, _ = _flash_fwd(
        q, k, v, causal, scale, block_q, block_k, prefix=prefix,
        window=window, offsets=offsets, head_pack=head_pack,
    )
    return out


def _fwd_rule(q, k, v, prefix, offsets, causal, scale, block_q, block_k,
              window=0, head_pack=1, lse_rows=False):
    out, lse = _flash_fwd(
        q, k, v, causal, scale, block_q, block_k, prefix=prefix,
        window=window, offsets=offsets, head_pack=head_pack,
    )
    out, lse = _name_residuals(out, lse, q, head_pack, lse_rows)
    return out, (q, k, v, prefix, offsets, out, lse)


def _bwd_rule(causal, scale, block_q, block_k, window, head_pack, lse_rows,
              residuals, g):
    # same dispatch as the lse-carrying variant, with no lse cotangent
    return _bwd_rule_lse(
        causal, scale, block_q, block_k, window, head_pack, lse_rows,
        residuals, (g, None),
    )


_flash_attention.defvjp(_fwd_rule, _bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def flash_attention_with_lse(q, k, v, prefix, offsets, causal, scale,
                             block_q, block_k, window=0, head_pack=1,
                             lse_rows=False):
    """Flash attention returning (out, lse) with BOTH differentiable —
    the primitive ring attention composes (the lse feeds the cross-block
    softmax merge, so its gradient is load-bearing). ``prefix`` [B] int32
    adds the prefix-LM bidirectional-prefix mask (causal only).
    ``offsets`` [2] int32 (q_off, k_off) shifts the mask rule to global
    positions — ring attention passes the blocks' traced ring offsets so
    window-boundary and prefix-reach blocks run this kernel too.
    ``lse_rows``: ``_name_residuals``."""
    out, lse = _flash_fwd(
        q, k, v, causal, scale, block_q, block_k, prefix=prefix,
        window=window, offsets=offsets, head_pack=head_pack,
    )
    return out, _stat_rows(lse, q.shape[0], q.shape[2], head_pack)


def _fwd_rule_lse(q, k, v, prefix, offsets, causal, scale, block_q,
                  block_k, window=0, head_pack=1, lse_rows=False):
    out, lse = _flash_fwd(
        q, k, v, causal, scale, block_q, block_k, prefix=prefix,
        window=window, offsets=offsets, head_pack=head_pack,
    )
    out, lse = _name_residuals(out, lse, q, head_pack, lse_rows)
    # where no policy keeps them the [B, H, S] view is this caller's
    # alone: the residual stays in the kernels' tiles
    rows = _residual_rows(lse, q, head_pack, lse_rows)
    return (out, rows), (q, k, v, prefix, offsets, out, lse)


def _bwd_rule_lse(causal, scale, block_q, block_k, window, head_pack,
                  lse_rows, residuals, cot):
    """The ONE backward dispatch (plain _bwd_rule delegates here with a
    None lse cotangent): FA2 pallas kernels on TPU/interpret with tiles
    capped per head width (BWD_BLOCK / BWD_BLOCK_WIDE — ~4 [bq,bk] f32
    transients per grid step at the applied cap); jnp chunked recompute
    off-TPU or when the sequence doesn't tile to a lane-aligned block.
    The lse residual is ``_name_residuals``': the kernels take the tile
    array, the fallback the [B, H, S] numbers."""
    q, k, v, prefix, offsets, out, lse = residuals
    g_out, g_lse = cot
    # wider heads keep the MXU busier per tile, so bigger tiles win; a
    # window's tile only where the grid is its band
    bq, bk = _bwd_tiles(
        q.shape[1], k.shape[1], q.shape[-1], block_q, block_k,
        window if _gate_is_static(causal, prefix, offsets) else 0,
    )
    in_kernel = (
        pltpu is not None
        and (device.on_tpu() or INTERPRET)
        and bq is not None
        and bk is not None
    )
    set_counter("attn.delta_in_kernel", int(in_kernel))
    if in_kernel:
        dq, dk, dv, _ = _pallas_backward(
            q, k, v, out, _residual_tiles(lse, head_pack, lse_rows), g_out,
            causal, scale, bq, bk,
            prefix=prefix, g_lse=g_lse, window=window, offsets=offsets,
            head_pack=head_pack,
        )
    else:
        dq, dk, dv = _chunked_backward(
            q, k, v, out,
            _residual_rows(lse, q, head_pack, lse_rows), g_out,
            causal, scale,
            chunk=_bwd_chunk(k.shape[1], block_k),
            g_lse=g_lse,
            prefix=prefix,
            window=window,
            offsets=offsets,
        )
    dprefix = (
        None
        if prefix is None
        else np.zeros(prefix.shape, dtype=jax.dtypes.float0)
    )
    doffsets = (
        None
        if offsets is None
        else np.zeros(offsets.shape, dtype=jax.dtypes.float0)
    )
    return dq, dk, dv, dprefix, doffsets


flash_attention_with_lse.defvjp(_fwd_rule_lse, _bwd_rule_lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_attention_selected(q, k, v, selected, scale, block_q, block_k,
                              lse_rows=False):
    """Causal attention over each query's SELECTION of keys
    (``selected`` [B, Sq, Sk] int8, nonzero at the chosen keys, one mask
    for all heads of a sequence) through the unpacked ``_sel`` kernels:
    (out, lse [B, H, S]). The softmax statistics range over the selected
    keys alone. ``lse`` leaves DETACHED — it is there for the indexer's
    alignment term, whose target carries no gradient — so the backward
    takes no cotangent for it."""
    out, lse = _flash_fwd(
        q, k, v, True, scale, block_q, block_k, selected=selected
    )
    return out, _stat_rows(lse, q.shape[0], q.shape[2], 1)


def _fwd_rule_selected(q, k, v, selected, scale, block_q, block_k,
                       lse_rows=False):
    out, lse = _flash_fwd(
        q, k, v, True, scale, block_q, block_k, selected=selected
    )
    out, lse = _name_residuals(out, lse, q, 1, lse_rows)
    rows = _residual_rows(lse, q, 1, lse_rows)
    return (out, rows), (q, k, v, selected, out, lse)


def _bwd_rule_selected(scale, block_q, block_k, lse_rows, residuals, cot):
    q, k, v, selected, out, lse = residuals
    g_out, _ = cot  # lse is detached
    dq, dk, dv, _ = _pallas_backward(
        q, k, v, out, _residual_tiles(lse, 1, lse_rows), g_out, True, scale,
        *_bwd_tiles(q.shape[1], k.shape[1], q.shape[-1], block_q, block_k),
        selected=selected,
    )
    return dq, dk, dv, np.zeros(selected.shape, dtype=jax.dtypes.float0)


_flash_attention_selected.defvjp(_fwd_rule_selected, _bwd_rule_selected)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    prefix_len: Optional[jax.Array] = None,  # [B] int32: prefix-LM
    window: int = 0,  # sliding window (causal only; 0 = unlimited)
    head_pack: int = 0,  # 0 = auto (128 // D heads a slab), 1 = unpacked
    selected: Optional[jax.Array] = None,  # [B, (G,) Sq, Sk] bool or int8
    lse_rows: bool = False,  # a remat policy of the caller keeps flash_lse
):
    """Flash attention; falls back to the jnp path off-TPU.

    q: [B, S, H, D]; k/v: [B, S, Hkv, D] (GQA via fewer kv heads).
    ``prefix_len`` (causal only) makes keys at positions < prefix_len[b]
    visible to every query — GLM-style bidirectional-prefix attention.
    ``window`` (causal only) limits each query to the last ``window``
    positions — Mistral-style sliding-window attention.
    ``head_pack`` (module docstring, "narrow-head packing"): 0 runs
    heads with 16 <= D < 128 dividing 128, in an MHA layout, on 128-lane
    slabs of ``128 // D`` heads; 1 keeps them on the unpacked kernels. A slab
    is the lane width, so the pack is never another number. GQA always
    runs unpacked — packing would replicate kv DMA per group and the
    kernels keep the simple grid//groups indexing.
    ``selected`` (module docstring, "the selection operand"; causal, no
    prefix, no window): each query attends to the keys it names, the
    same for every head (``[B, G, Sq, Sk]``: for every head of a group),
    and the result is ``(out, lse [B, H, S])`` with
    ``lse`` float32 and detached.
    ``lse_rows``: the caller runs this under a remat policy that keeps
    ``flash_out`` and ``flash_lse``, so the residual of that name is the
    statistics as numbers and not the kernels' tile array
    (``_name_residuals``); the values and gradients are the same.
    """
    scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
    sq, sk = q.shape[1], k.shape[1]
    h, hkv, d = q.shape[2], k.shape[2], q.shape[-1]
    bq = _fit_block(sq, block_q)
    bk = _fit_block(sk, block_k)
    if prefix_len is not None and not causal:
        raise ValueError("prefix_len requires causal=True")
    if window:
        if window < 0:
            raise ValueError(f"window must be >= 0, got {window}")
        if not causal:
            raise ValueError("window requires causal=True")
        if prefix_len is not None:
            raise ValueError("window and prefix_len are mutually exclusive")
    if head_pack < 0:
        raise ValueError(f"head_pack must be >= 0, got {head_pack}")
    if selected is not None:
        if not causal or prefix_len is not None or window:
            raise ValueError(
                "a selection of keys runs under the plain causal mask"
            )
        return _selected_attention(
            q, k, v, selected, scale, bq, bk, lse_rows
        )
    if pltpu is None or not (device.on_tpu() or INTERPRET) or bq is None or bk is None:
        # off-TPU (incl. GPU — this is a Mosaic-TPU kernel), or seq not
        # tileable to a lane-aligned block: plain jnp, never a trace-time
        # crash
        from dlrover_tpu.ops.attention import mha_reference

        return mha_reference(
            q, k, v, causal=causal, softmax_scale=scale,
            prefix_len=prefix_len, window=window,
        )
    # a slab is 128 lanes of the projection's array, so the pack is
    # never a choice: 128 // D heads — at most STAT_LANES, each has its
    # lane of a statistics tile — or the unpacked kernels
    pack = 1
    if (
        head_pack != 1 and h == hkv
        and LANES // STAT_LANES <= d < LANES and LANES % d == 0
    ):
        pack = LANES // d
    set_counter("attn.heads_per_slab", pack)
    band, (k_steps, _), _ = _inner_grid(
        _gate_is_static(causal, prefix_len, None),
        bq, bk, sq // bq, sk // bk, window,
    )
    if band or not counters().get("attn.window_tile"):
        # (a step of two kinds of layer keeps its window layers' reading)
        set_counter("attn.band_blocks", k_steps)
        set_counter(
            "attn.window_tile",
            _bwd_tiles(sq, sk, d, bq, bk, window)[1] if band else 0,
        )
    return _flash_attention(
        q, k, v, prefix_len, None, causal, scale, bq, bk, window, pack,
        lse_rows,
    )


def _selected_attention(q, k, v, selected, scale, bq, bk, lse_rows):
    """``flash_attention``'s path under a selection: the ``_sel`` kernels
    where the kernels run at all, else the jnp reference with the same
    mask. (out, lse [B, H, S] float32, detached)."""
    if pltpu is None or not (device.on_tpu() or INTERPRET) or (
        bq is None or bk is None
    ):
        from dlrover_tpu.ops.attention import mha_reference

        out, lse = mha_reference(
            q, k, v, causal=True, softmax_scale=scale, selected=selected,
            return_lse=True,
        )
        return out, jax.lax.stop_gradient(lse)
    set_counter("attn.heads_per_slab", 1)
    set_counter("attn.delta_in_kernel", 1)
    return _flash_attention_selected(
        q, k, v, selected.astype(jnp.int8), scale, bq, bk, lse_rows
    )


def _out_struct(shape, dtype, like):
    """``out_shape`` entry for a ``pallas_call`` whose result varies over
    the same manual mesh axes as the operand ``like``: inside a
    ``shard_map`` that checks replication (the ZeRO step's dp-manual
    region) a kernel's outputs must say so; at top level the set is
    empty."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _fit_block(s: int, prefer: int):
    """Largest 128-multiple block ≤ prefer that divides the sequence."""
    for b in (prefer, 1024, 512, 256, 128):
        if b <= prefer and b <= s and s % b == 0 and b % 128 == 0:
            return b
    return None
