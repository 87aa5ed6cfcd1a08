"""A selecting attention's alignment term as one Pallas TPU kernel a
layer (``models/decoder.py::_alignment_kl``: the KL from the attention's
head-mean probabilities ``p`` to the softmax of the index scores over
each query's selected keys, with its derivative for the indexer's
operands).

For a (query block, key block) tile the J index heads' products
``qI_tj . kI_s`` (bf16 operands, float32 sums) are made on the MXU, the
ReLU, the head weights ``w_tj`` and the head sum ``I_ts`` on the vector
unit in float32, and nothing of a tile leaves VMEM but what it adds to
the results. Grid ``(batch, q block, sweep, k block)``, all but the
batch sequential:

* sweep 0 makes, for the q block's row of tiles, ``I`` and the target
  ``p`` — the attention's side: ``exp(q.k * scale - lse)`` from the
  attention's own operands and the ``lse`` its kernel wrote, summed
  over the heads a kv group at a time, over the head count, zero off
  the selection — keeps both in VMEM (``[n_k, block_q, block_k]``
  float32 each: 8 MB at 256 x 8192) and takes the row statistics over
  the SELECTED keys: the maximum, the sum of exponentials, ``sum_s p``;
* sweep 1 reads both back, makes ``log softmax I``, the KL value
  ``sum p (log p - log softmax I)`` (0 log 0 = 0) and ``dI = softmax(I)
  sum_s p - p`` on the selection, then makes the heads' products AGAIN
  (a tile's float32 products never outlive a head's turn) for the three
  derivatives: ``d_w[t, j] = sum_s dI relu(qI_tj . kI_s)`` summed in
  float32 on the vector unit; ``g = dI w_tj 1[qI_tj . kI_s > 0]`` cast
  to the operands' dtype (the jnp rule's compiled dots take their
  float32 left operand at the MXU's default precision, one bf16 pass:
  the cast is that rounding, made once) and ``d_qI[t, j] = sum_s g
  kI_s``, ``d_kI[s] = sum_{t, j} g qI_tj`` on the MXU, summed in
  float32; ``d_kI`` over every query block in ONE float32 block that
  stays in VMEM for a whole sequence and is cast by the caller.

Causal structure is the chunks': a query block of chunk ``[start, end)``
visits the key blocks under ``end``; those past it are neither fetched
(their index maps clamp to the last one visited) nor computed.

Layout. ``qI`` comes as the projection's own ``[B, S, J*C]`` (a free
view of ``[B, S, J, C]``). Heads narrower than the 128 lanes share a
slab of ``pack = 128 // C`` heads; a head is kept apart by zeroing the
slab outside its lanes, once a query block, into ``[J, block_q, W]``
scratch (``W`` = a slab's width), and the keys come with their C
channels repeated ``pack`` times side by side (``[B, S, W]``), so that
``masked slab @ keys^T`` is head j's product over a 128-deep
contraction of which C are live: the MXU passes of a C-deep one. The
same repeated keys make ``g @ keys`` land head j's ``d_qI`` on its own
lanes (and a copy beside it, which is not read), and ``g^T @ masked
slab`` lands head j's part of ``d_kI`` on lanes ``(j % pack) * C``:
``d_kI`` leaves as ``[B, S, W]`` float32 and the caller adds the
``pack`` lane groups. The attention's ``q`` and ``k`` come head-major
(``[B, H, S, D]``: a head is a leading index), its ``lse`` as ``[B,
Hkv, S, H / Hkv]`` (a kv group's heads in the lanes of a query's row).

The head loops are unrolled: one head's vector work runs beside
another's products (on a v5e at Keye-VL-2.0's shapes the index side
took 4.5 ms a layer as a rolled loop, 3.2 unrolled; CHANGES.md, PR 40).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # pltpu only resolves on TPU builds of jaxlib
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

from dlrover_tpu.common import device
from dlrover_tpu.ops import pallas_attention

LANES = pallas_attention.LANES
STAT_LANES = pallas_attention.STAT_LANES
NEG_INF = pallas_attention.NEG_INF
# (query rows, key rows) of a tile: the largest of these that divides
# the indexer's chunk. On a v5e at Keye-VL-2.0's shapes (chunks of 512;
# ms a layer, PR 40): 256 x 512 6.10, 512 x 512 6.02 at twice the VMEM
# and twice the seconds to compile, 128 x 512 6.66, 256 x 256 7.12
BLOCK_Q = (256, 128)
BLOCK_K = (512, 256, 128)
VMEM_LIMIT = 64 * 1024 * 1024


def tiles(s: int, chunk: int, heads: int, channels: int):
    """(block_q, block_k) for a sequence of ``s`` scored by chunks of
    ``chunk`` with ``heads`` index heads of ``channels``, or None where
    the kernel does not run: off the TPU (and not interpreted), or
    shapes its tiles do not fit — a sequence the chunks do not divide,
    a chunk no tile divides, heads that do not fill their slabs, or a
    query block's two rows of tiles (``I`` and ``p``, float32 over the
    whole sequence) past half the kernel's VMEM."""
    if pltpu is None or not (device.on_tpu() or pallas_attention.INTERPRET):
        return None
    if s % chunk or not (channels == 64 or channels % LANES == 0):
        return None
    if heads % max(1, LANES // channels):
        return None
    bq = next((
        b for b in BLOCK_Q
        if chunk % b == 0 and 2 * 4 * b * s <= VMEM_LIMIT // 2
    ), None)
    bk = next((b for b in BLOCK_K if chunk % b == 0), None)
    return None if bq is None or bk is None else (bq, bk)


def _nt(a, b):
    """a [m, c] @ b[n, c]^T in float32."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _fold(x, width):
    """[rows, n * width] -> [rows, width]: the lane groups added."""
    out = x[:, :width]
    for c in range(1, x.shape[1] // width):
        out = out + x[:, c * width:(c + 1) * width]
    return out


def _align_kernel(
    qi_ref,  # [1, block_q, J*C]: the heads side by side
    ki_ref,  # [1, block_k, W]: a key's channels, ``pack`` times
    w_ref,  # [1, block_q, J] f32
    sel_ref,  # [1, block_q, block_k] int8
    q_ref,  # [1, H, block_q, D]: the attention's queries, head-major
    k_ref,  # [1, Hkv, block_k, D]: its keys
    lse_ref,  # [1, Hkv, block_q, H / Hkv] f32: its lse, a kv group's heads
    kl_ref,  # [1, block_q, 8] f32: a query's KL in every lane
    dqi_ref,  # [1, block_q, J*C]
    dki_ref,  # [1, S, W] f32: resident for the whole sequence
    dw_ref,  # [1, block_q, J] f32
    qm_scr,  # [J, block_q, W]: head j alone on its lanes of its slab
    i_scr,  # [n_k, block_q, block_k] f32: this q block's index scores
    p_scr,  # [n_k, block_q, block_k] f32: and its target
    m_scr, l_scr, psum_scr, kl_scr,  # [block_q, fold] f32 row statistics
    dqi_scr,  # [J, block_q, W] f32
    dw_scr,  # [J, block_q, fold] f32: lane-partial sums
    *,
    block_q: int,
    block_k: int,
    chunk: int,
    heads: int,
    channels: int,
    pack: int,
    scale: float,
):
    i, sweep, kb = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    n_k = pl.num_programs(3)
    width = pack * channels
    fold = m_scr.shape[1]
    end = ((i * block_q) // chunk + 1) * chunk  # the chunk's last key + 1
    runs = kb * block_k < end

    @pl.when((i == 0) & (sweep == 0) & (kb == 0))
    def _new_sequence():
        dki_ref[0] = jnp.zeros_like(dki_ref[0])

    @pl.when((sweep == 0) & (kb == 0))
    def _new_q_block():
        lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, width), 1)
        for j in range(heads):
            slab = qi_ref[0, :, (j // pack) * width:(j // pack + 1) * width]
            if pack > 1:
                slab = jnp.where(
                    lane // channels == j % pack, slab, jnp.zeros_like(slab)
                )
            qm_scr[j] = slab
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        for ref in (l_scr, psum_scr, kl_scr, dqi_scr, dw_scr):
            ref[:] = jnp.zeros_like(ref)

    @pl.when(runs & (sweep == 0))
    def _scores():
        keys, w = ki_ref[0], w_ref[0]
        index = jnp.zeros((block_q, block_k), jnp.float32)
        for j in range(heads):
            index = index + w[:, j:j + 1] * jnp.maximum(
                _nt(qm_scr[j], keys), 0.0
            )
        i_scr[kb] = index
        chosen = sel_ref[0].astype(jnp.int32) != 0

        def kv_group(g, p):
            # the heads that share kv head g: exp(q.k * scale - lse)
            attn_keys, lse = k_ref[0, g], lse_ref[0, g]
            for r in range(lse.shape[1]):
                scores = _nt(q_ref[0, g * lse.shape[1] + r], attn_keys)
                p = p + jnp.exp(scores * scale - lse[:, r:r + 1])
            return p

        p = jax.lax.fori_loop(
            0, k_ref.shape[1], kv_group,
            jnp.zeros((block_q, block_k), jnp.float32),
        )
        p = jnp.where(chosen, p / q_ref.shape[1], 0.0)
        p_scr[kb] = p
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(
            jnp.where(chosen, index, NEG_INF), axis=1, keepdims=True
        ))
        e = jnp.where(chosen, jnp.exp(index - m_new), 0.0)
        l_new = l_scr[:, :1] * jnp.exp(m_prev - m_new) + jnp.sum(
            e, axis=1, keepdims=True
        )
        p_new = psum_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        psum_scr[:] = jnp.broadcast_to(p_new, psum_scr.shape)

    @pl.when(runs & (sweep == 1))
    def _derivatives():
        keys = ki_ref[0]
        chosen = sel_ref[0].astype(jnp.int32) != 0
        p = p_scr[kb]
        shifted = i_scr[kb] - m_scr[:, :1]
        total = l_scr[:, :1]
        live = p > 0
        kl = jnp.where(
            live,
            p * (jnp.log(jnp.where(live, p, 1.0)) - shifted + jnp.log(total)),
            0.0,
        )
        kl_scr[:] = kl_scr[:] + jnp.sum(kl, axis=1, keepdims=True)
        # softmax as exp / sum, not exp(log softmax): an error of log's
        # is one factor for a whole row, and sum_s dI = 0 would not hold
        d_index = jnp.where(
            chosen,
            jnp.exp(shifted) * (psum_scr[:, :1] / total) - p,
            0.0,
        )

        w = w_ref[0]
        d_keys = jnp.zeros((block_k, width), jnp.float32)
        for j in range(heads):
            q = qm_scr[j]
            dots = _nt(q, keys)
            dw_scr[j] = dw_scr[j] + _fold(
                d_index * jnp.maximum(dots, 0.0), fold
            )
            g = jnp.where(
                dots > 0, d_index * w[:, j:j + 1], 0.0
            ).astype(keys.dtype)
            dqi_scr[j] = dqi_scr[j] + jnp.dot(
                g, keys, preferred_element_type=jnp.float32
            )
            d_keys = d_keys + jax.lax.dot_general(
                g, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        rows = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        dki_ref[0, rows, :] = dki_ref[0, rows, :] + d_keys

    @pl.when((sweep == 1) & (kb == n_k - 1))
    def _finish():
        kl_ref[0] = jnp.broadcast_to(kl_scr[:, :1], kl_ref.shape[1:])
        lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, width), 1)
        head_lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, heads), 1)
        d_w = jnp.zeros((block_q, heads), jnp.float32)
        for slab in range(heads // pack):
            d_slab = dqi_scr[slab * pack]
            for r in range(1, pack):
                d_slab = jnp.where(
                    lane // channels == r, dqi_scr[slab * pack + r], d_slab
                )
            dqi_ref[0, :, slab * width:(slab + 1) * width] = d_slab.astype(
                dqi_ref.dtype
            )
        for j in range(heads):
            d_w = jnp.where(
                head_lane == j,
                jnp.sum(dw_scr[j], axis=1, keepdims=True), d_w,
            )
        dw_ref[0] = d_w


def alignment_kl_and_grads(qi, ki, w, selected, q, k, lse, scale, chunk,
                           block_q, block_k):
    """(kl [B, S] float32 a query, d_qi, d_ki, d_w) of the alignment
    term from the indexer's ``qi`` [B, S, J, C], ``ki`` [B, S, C] and
    ``w`` [B, S, J] float32, the selection ``selected`` [B, S, S] int8,
    and the attention's ``q`` [B, S, H, D], ``k`` [B, S, Hkv, D] and
    ``lse`` [B, H, S] float32 over the selection. The derivatives are of
    ``sum(kl)`` and have their operand's shape and dtype."""
    b, s, heads, channels = qi.shape
    n_head, n_kv, head_dim = q.shape[2], k.shape[2], q.shape[3]
    interpret = pallas_attention.INTERPRET
    pack = max(1, min(heads, LANES // channels))
    if heads % pack or s % chunk or chunk % block_q or chunk % block_k:
        raise ValueError(
            f"{heads} index heads of {channels}, chunks of {chunk} in "
            f"{s}: no tiling by {block_q} x {block_k}"
        )
    width = pack * channels
    fold = LANES if block_k % LANES == 0 else block_k
    n_q, n_k = s // block_q, s // block_k

    def last_k(i):
        return ((i * block_q) // chunk + 1) * (chunk // block_k) - 1

    def q_map(bi, i, sweep, kb):
        return bi, i, 0

    def k_map(bi, i, sweep, kb):
        return bi, jnp.minimum(kb, last_k(i)), 0

    def tile_map(bi, i, sweep, kb):
        return bi, i, jnp.minimum(kb, last_k(i))

    def attn_q_map(bi, i, sweep, kb):
        return bi, 0, i, 0

    def attn_k_map(bi, i, sweep, kb):
        # the target is made in the first sweep: the second holds on to
        # the block the first ended with
        return bi, 0, jnp.where(sweep == 0, jnp.minimum(kb, last_k(i)),
                                last_k(i)), 0

    kernel = functools.partial(
        _align_kernel, block_q=block_q, block_k=block_k, chunk=chunk,
        heads=heads, channels=channels, pack=pack, scale=scale,
    )
    f32 = jnp.float32
    stat = pltpu.VMEM((block_q, fold), f32)
    row = pltpu.VMEM((n_k, block_q, block_k), f32)
    kl, d_qi, d_ki, d_w = pl.pallas_call(
        kernel,
        grid=(b, n_q, 2, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, heads * channels), q_map),
            pl.BlockSpec((1, block_k, width), k_map),
            pl.BlockSpec((1, block_q, heads), q_map),
            pl.BlockSpec((1, block_q, block_k), tile_map),
            pl.BlockSpec((1, n_head, block_q, head_dim), attn_q_map),
            pl.BlockSpec((1, n_kv, block_k, head_dim), attn_k_map),
            pl.BlockSpec((1, n_kv, block_q, n_head // n_kv), attn_q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, STAT_LANES), q_map),
            pl.BlockSpec((1, block_q, heads * channels), q_map),
            pl.BlockSpec((1, s, width), lambda bi, i, sweep, kb: (bi, 0, 0)),
            pl.BlockSpec((1, block_q, heads), q_map),
        ],
        out_shape=[
            pallas_attention._out_struct((b, s, STAT_LANES), f32, qi),
            pallas_attention._out_struct(
                (b, s, heads * channels), qi.dtype, qi
            ),
            pallas_attention._out_struct((b, s, width), f32, qi),
            pallas_attention._out_struct((b, s, heads), f32, qi),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, block_q, width), qi.dtype),
            row, row, stat, stat, stat, stat,
            pltpu.VMEM((heads, block_q, width), f32),
            pltpu.VMEM((heads, block_q, fold), f32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * 3,
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="align_kl",
    )(
        qi.reshape(b, s, heads * channels),
        jnp.concatenate([ki] * pack, axis=-1),
        w, selected,
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        lse.reshape(b, n_kv, n_head // n_kv, s).transpose(0, 1, 3, 2),
    )
    d_ki = d_ki.reshape(b, s, pack, channels).sum(axis=2)
    return (
        kl[..., 0], d_qi.reshape(qi.shape), d_ki.astype(ki.dtype), d_w,
    )
