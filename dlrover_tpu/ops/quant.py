"""Block-wise int8 quantization for optimizer state.

Reference: atorch's CUDA quantization kernels + low-bit optimizer
(atorch/ops/csrc/quantization/*.cu, optimizers/low_bit/functional.py:543L).
TPU-native: the quantize/dequantize math is plain jnp — XLA fuses it into
the optimizer update so there is no extra HBM round-trip, which is what the
hand-written CUDA kernels existed to avoid.

``quantize_optimizer_state(opt)`` wraps any optax transformation so its
large float32 state leaves (Adam moments etc.) live as int8 + per-block
scales — a ~3.5× optimizer-memory cut.
"""


import math

import jax
import jax.numpy as jnp
import numpy as np
import optax

BLOCK = 256
MIN_QUANT_SIZE = 4096  # leave small leaves (scalars, counts) untouched


@jax.tree_util.register_pytree_node_class
class QuantizedArray:
    """int payload + per-block scales; shape/dtype kept for dequant.

    ``bits=8``: one value per int8 byte. ``bits=4``: two values packed per
    byte (low/high nibble), halving state memory again — the reference's
    4-bit optimizer (low_bit/functional.py) packing scheme, minus the CUDA.

    Registered as a pytree whose children are only (q, scale); shape/dtype/
    bits are static aux data, so instances flow through jit/scan/pjit as
    optimizer-state leaves (a ShapeDtypeStruct leaf would not trace).
    """

    __slots__ = ("q", "scale", "shape", "dtype", "bits")

    def __init__(self, q, scale, shape, dtype, bits: int = 8):
        self.q = q
        self.scale = scale
        self.shape = tuple(shape)
        self.dtype = jnp.dtype(dtype)
        self.bits = int(bits)

    @property
    def meta(self):
        return jax.ShapeDtypeStruct(self.shape, self.dtype)

    def tree_flatten(self):
        return (self.q, self.scale), (self.shape, str(self.dtype), self.bits)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale = children
        shape, dtype, bits = aux
        return cls(q, scale, shape, dtype, bits)

    def __repr__(self):
        return (
            f"QuantizedArray(shape={self.shape}, dtype={self.dtype}, "
            f"bits={self.bits})"
        )


def _quant_blocks(blocks: jax.Array, bits: int):
    """Quantize ``(..., BLOCK)`` float32 blocks → (packed int8, scale)."""
    qmax = 127.0 if bits == 8 else 7.0
    scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / qmax
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale), -qmax, qmax).astype(jnp.int8)
    if bits == 4:
        # two's-complement nibbles packed pairwise into one byte
        lo = q[..., 0::2] & 0xF
        hi = (q[..., 1::2] & 0xF) << 4
        q = (lo | hi).astype(jnp.int8)
    return q, scale


def _dequant_blocks(q: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    """Inverse of ``_quant_blocks``: packed blocks → float32 ``(..., BLOCK)``."""
    if bits == 4:
        # sign-extend each nibble: shift into high bits, arithmetic-shift back
        lo = (q.astype(jnp.int8) << 4) >> 4
        hi = q.astype(jnp.int8) >> 4
        q = jnp.stack([lo, hi], axis=-1).reshape(*q.shape[:-1], -1)
    return q.astype(jnp.float32) * scale


def quantize(x: jax.Array, bits: int = 8) -> QuantizedArray:
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    shape, dtype = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % BLOCK
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, BLOCK)
    q, scale = _quant_blocks(blocks, bits)
    return QuantizedArray(q=q, scale=scale, shape=shape, dtype=dtype, bits=bits)


def dequantize(qa: QuantizedArray) -> jax.Array:
    flat = _dequant_blocks(qa.q, qa.scale, qa.bits).reshape(-1)
    size = 1
    for d in qa.shape:
        size *= d
    return flat[:size].reshape(qa.shape).astype(qa.dtype)


def _should_quantize(leaf) -> bool:
    return (
        isinstance(leaf, (jax.Array, jnp.ndarray))
        and jnp.issubdtype(leaf.dtype, jnp.floating)
        and leaf.size >= MIN_QUANT_SIZE
    )


def quantize_tree(state, bits: int = 8):
    """Blockwise-quantize every large float leaf of a pytree (small
    leaves pass through untouched). Inverse: ``dequantize_tree``."""
    return jax.tree.map(
        lambda leaf: quantize(leaf, bits) if _should_quantize(leaf) else leaf,
        state,
    )


def dequantize_tree(state):
    return jax.tree.map(
        lambda leaf: dequantize(leaf)
        if isinstance(leaf, QuantizedArray)
        else leaf,
        state,
        is_leaf=lambda x: isinstance(x, QuantizedArray),
    )


# intra-module aliases (historical names)
_quantize_tree = quantize_tree
_dequantize_tree = dequantize_tree


# ---------------------------------------------------------------------------
# Bucketed wire format for gradient collectives
# ---------------------------------------------------------------------------
# One flat stream, fixed-size buckets, blockwise int8 scales. The
# update-sharding gradient exchange (parallel/sharding.py) rides the
# row-wise pair below inside its shard_map; local-SGD outer-group syncs
# (parallel/local_sgd.py) ship whole pseudo-gradient trees in the same
# encoding via the tree-level pair.


def wire_encode_rows(rows: jax.Array):
    """Encode ``[r, n]`` f32 (n a multiple of BLOCK) → (int8 ``[r, n]``,
    f32 scales ``[r, n // BLOCK]``), one scale per block per row."""
    r, n = rows.shape
    q, scale = _quant_blocks(rows.reshape(r, n // BLOCK, BLOCK), 8)
    return q.reshape(r, n), scale[..., 0]


def wire_decode_sum(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Decode ``wire_encode_rows`` output and sum the rows in f32 → ``[n]``."""
    r, n = q.shape
    blocks = _dequant_blocks(
        q.reshape(r, n // BLOCK, BLOCK), scale[..., None], 8
    )
    return jnp.sum(blocks.reshape(r, n), axis=0)


def kv_block_size(row_elems: int) -> int:
    """Scale-block width for one KV token row of ``row_elems`` floats.

    A token row is ``kv_heads * head_dim`` elements — often smaller than
    the optimizer-state ``BLOCK`` (256). ``_quant_blocks`` is generic
    over the trailing dim, so narrow rows get one scale per whole row
    instead of being padded out to 256 (which would inflate the int8
    cache by the pad and wreck the resident-bytes win)."""
    if row_elems <= 0:
        raise ValueError(f"row_elems must be positive, got {row_elems}")
    if row_elems <= BLOCK:
        return row_elems
    # wide rows: largest divisor of the row that fits in BLOCK keeps
    # blocks uniform (no ragged tail inside a row)
    for cand in range(BLOCK, 0, -1):
        if row_elems % cand == 0:
            return cand
    return 1


def kv_encode_rows(rows: jax.Array, block: int):
    """Encode KV token rows ``[..., n]`` (n % block == 0) → int8 blocks.

    Returns ``(q [..., n//block, block] int8, scale [..., n//block] f32)``
    — the serving tier's paged-cache storage encoding, the same
    EQuARX-style per-block max/127 scheme the gradient wire uses
    (``wire_encode_rows``), kept unflattened so page pools can index
    whole blocks."""
    *lead, n = rows.shape
    if n % block:
        raise ValueError(f"row width {n} not a multiple of block {block}")
    blocks = rows.astype(jnp.float32).reshape(*lead, n // block, block)
    q, scale = _quant_blocks(blocks, 8)
    return q, scale[..., 0]


def kv_decode_rows(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Inverse of ``kv_encode_rows``: ``[..., nb, block]`` → ``[..., n]``.

    Dequantizes in f32 then casts to ``dtype`` (the model compute dtype)
    — the per-page dequant that runs INSIDE the jitted decode step."""
    out = _dequant_blocks(q, scale[..., None], 8)
    *lead, nb, blk = out.shape
    return out.reshape(*lead, nb * blk).astype(dtype)


def kv_encode_rows_np(rows: np.ndarray, block: int):
    """Host-side ``kv_encode_rows``: numpy in, numpy out.

    Same per-block max/127 scheme, for row stores that live outside jit
    (the tiered cold/warm tier keeps resident rows in this encoding)."""
    rows = np.asarray(rows, np.float32)
    *lead, n = rows.shape
    if n % block:
        raise ValueError(f"row width {n} not a multiple of block {block}")
    blocks = rows.reshape(*lead, n // block, block)
    scale = np.max(np.abs(blocks), axis=-1, keepdims=True) / 127.0
    scale = np.maximum(scale, 1e-12)
    q = np.clip(np.rint(blocks / scale), -127, 127).astype(np.int8)
    return q, scale[..., 0].astype(np.float32)


def kv_decode_rows_np(q: np.ndarray, scale: np.ndarray,
                      dtype=np.float32) -> np.ndarray:
    """Inverse of ``kv_encode_rows_np``: ``[..., nb, block]`` → ``[..., n]``."""
    out = q.astype(np.float32) * scale[..., None]
    *lead, nb, blk = out.shape
    return out.reshape(*lead, nb * blk).astype(dtype)


def _wire_layout(like, bucket_bytes: int):
    sizes = [
        int(math.prod(l.shape)) for l in jax.tree.leaves(like)
    ]
    total = sum(sizes)
    bucket_elems = max(bucket_bytes // 4, BLOCK)
    bucket_elems = -(-bucket_elems // BLOCK) * BLOCK
    n_buckets = max(1, -(-total // bucket_elems))
    return sizes, total, bucket_elems, n_buckets


def wire_encode_tree(tree, bits: int = 8, bucket_bytes: int = 4 * 2**20):
    """Pytree of float arrays → ``{"q", "scale"}`` bucketed wire payload.

    Every leaf (small ones included, unlike ``quantize_tree``) joins one
    flat f32 stream, zero-padded to ``n_buckets`` fixed-size buckets;
    each bucket is quantized blockwise (``BLOCK``-sized scales). The
    payload is a plain pytree of two arrays, so it drops straight into
    npz/socket transports.
    """
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    _, total, bucket_elems, n_buckets = _wire_layout(tree, bucket_bytes)
    flat = jnp.concatenate(
        [jnp.asarray(l).astype(jnp.float32).reshape(-1) for l in jax.tree.leaves(tree)]
    )
    flat = jnp.pad(flat, (0, n_buckets * bucket_elems - total))
    blocks = flat.reshape(n_buckets, bucket_elems // BLOCK, BLOCK)
    q, scale = _quant_blocks(blocks, bits)
    return {"q": q.reshape(n_buckets, -1), "scale": scale[..., 0]}


def wire_decode_tree(payload, like, bits: int = 8,
                     bucket_bytes: int = 4 * 2**20):
    """Inverse of ``wire_encode_tree``: payload → pytree shaped like ``like``."""
    sizes, _, bucket_elems, n_buckets = _wire_layout(like, bucket_bytes)
    q, scale = payload["q"], payload["scale"]
    blocks = _dequant_blocks(
        jnp.asarray(q).reshape(n_buckets, bucket_elems // BLOCK, -1),
        jnp.asarray(scale)[..., None],
        bits,
    )
    stream = blocks.reshape(-1)
    leaves, off = [], 0
    for l, s in zip(jax.tree.leaves(like), sizes):
        leaves.append(stream[off : off + s].reshape(l.shape).astype(l.dtype))
        off += s
    return jax.tree.unflatten(jax.tree.structure(like), leaves)


def quantize_optimizer_state(
    inner: optax.GradientTransformation,
    bits: int = 8,
) -> optax.GradientTransformation:
    """Keep ``inner``'s large state leaves as block-quantized int8/int4.

    Generic wrapper for arbitrary ``inner`` transforms. NOTE: it
    round-trips the WHOLE state tree through float32 every update, so the
    step-time HBM peak is the same as unquantized state — only resident
    memory shrinks. For AdamW at billion-parameter scale use
    ``lowbit_adamw``, which streams the dequant–update–requant in bounded
    chunks and never materialises a full float32 moment tree.
    """

    def init_fn(params):
        return _quantize_tree(inner.init(params), bits)

    def update_fn(updates, state, params=None):
        full = _dequantize_tree(state)
        updates, new_state = inner.update(updates, full, params)
        return updates, _quantize_tree(new_state, bits)

    return optax.GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# Fused streaming low-bit AdamW
# ---------------------------------------------------------------------------

# Elements processed per scan iteration. 4Mi elems = 16 MB per f32 chunk
# buffer; ~6 live chunk buffers ≈ 100 MB transient regardless of leaf size.
CHUNK_ELEMS = 4 * 1024 * 1024


def _leaf_blocks(x: jax.Array) -> jax.Array:
    """Flatten + pad a leaf to ``(n_blocks, BLOCK)`` float32 blocks."""
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % BLOCK
    return jnp.pad(flat, (0, pad)).reshape(-1, BLOCK)


def _zero_quantized(x: jax.Array, bits: int) -> QuantizedArray:
    """All-zero quantized moment with the layout ``lowbit_adamw`` uses."""
    n_blocks = -(-x.size // BLOCK)
    cols = BLOCK if bits == 8 else BLOCK // 2
    return QuantizedArray(
        q=jnp.zeros((n_blocks, cols), jnp.int8),
        scale=jnp.full((n_blocks, 1), 1e-12, jnp.float32),
        shape=x.shape,
        dtype=jnp.float32,
        bits=bits,
    )


def adamw_m_ema(g32, m32, b1: float):
    """First-moment EMA step (f32 in/out) — shared by every optimizer
    variant regardless of how it encodes nu."""
    return b1 * m32 + (1 - b1) * g32


def adamw_moments(g32, m32, v32, b1: float, b2: float):
    """One EMA step of both AdamW moments (f32 in/out)."""
    return adamw_m_ema(g32, m32, b1), b2 * v32 + (1 - b2) * (g32 * g32)


def adamw_direction(m2, vhat2, bc1, bc2, eps: float,
                    weight_decay: float = 0.0, p32=None):
    """Bias-corrected AdamW update direction from moment estimates.

    The ONE copy of the update expression every state-compression
    variant in this codebase shares (lowbit_adamw, mixed_adamw,
    train/optimizer.py factored_adamw) — nu encodings differ per
    optimizer, the direction math must not drift."""
    upd = (m2 / bc1) / (jnp.sqrt(vhat2 / bc2) + eps)
    if weight_decay:
        upd = upd + weight_decay * p32
    return upd


def lowbit_adamw(
    learning_rate,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    bits: int = 8,
    chunk_elems: int = CHUNK_ELEMS,
) -> optax.GradientTransformation:
    """AdamW with block-quantized int8/int4 moments and bounded transients.

    Reference capability: atorch's low-bit optimizer
    (atorch/optimizers/low_bit/functional.py:543L) backed by CUDA
    quantization kernels (ops/csrc/quantization/quantization_optimizer.cu).
    TPU-native design: per leaf, a ``lax.scan`` streams fixed-size chunks
    through dequant → moment update → requant → AdamW step, so the float32
    working set is O(chunk) rather than O(params) — the whole point of
    low-bit state, which the generic ``quantize_optimizer_state`` wrapper
    loses at step time.
    """
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    chunk_blocks = max(1, chunk_elems // BLOCK)

    def _lr(step):
        return learning_rate(step) if callable(learning_rate) else learning_rate

    def init_fn(params):
        def moment(p):
            if _should_quantize(p):
                return _zero_quantized(p, bits)
            return jnp.zeros_like(p, jnp.float32)

        return {
            "step": jnp.zeros([], jnp.int32),
            "m": jax.tree.map(moment, params),
            "v": jax.tree.map(moment, params),
        }

    def _dense_update(g, m, v, p, bc1, bc2):
        g = g.astype(jnp.float32)
        m2, v2 = adamw_moments(g, m, v, b1, b2)
        upd = adamw_direction(
            m2, v2, bc1, bc2, eps, weight_decay,
            p.astype(jnp.float32) if weight_decay else None,
        )
        return upd, m2, v2

    def _chunked_update(g, mq: QuantizedArray, vq: QuantizedArray, p, bc1, bc2):
        n_blocks = mq.q.shape[0]
        pad_blocks = (-n_blocks) % chunk_blocks
        n_chunks = (n_blocks + pad_blocks) // chunk_blocks

        def blocks_of(x):
            b = _leaf_blocks(x)
            b = jnp.pad(b, ((0, pad_blocks), (0, 0)))
            return b.reshape(n_chunks, chunk_blocks, BLOCK)

        def chunks_of(q, scale):
            q = jnp.pad(q, ((0, pad_blocks), (0, 0)))
            scale = jnp.pad(scale, ((0, pad_blocks), (0, 0)))
            return (
                q.reshape(n_chunks, chunk_blocks, -1),
                scale.reshape(n_chunks, chunk_blocks, 1),
            )

        xs = (
            blocks_of(g),
            blocks_of(p) if weight_decay else None,
            chunks_of(mq.q, mq.scale),
            chunks_of(vq.q, vq.scale),
        )

        def body(_, x):
            gc, pc, (mqc, msc), (vqc, vsc) = x
            m = _dequant_blocks(mqc, msc, bits)
            v = _dequant_blocks(vqc, vsc, bits)
            m2, v2 = adamw_moments(gc, m, v, b1, b2)
            upd = adamw_direction(m2, v2, bc1, bc2, eps, weight_decay, pc)
            mq2, ms2 = _quant_blocks(m2, bits)
            vq2, vs2 = _quant_blocks(v2, bits)
            return None, (upd, (mq2, ms2), (vq2, vs2))

        _, (upd, (mq2, ms2), (vq2, vs2)) = jax.lax.scan(body, None, xs)

        def unchunk(x, cols):
            return x.reshape(n_chunks * chunk_blocks, cols)[:n_blocks]

        upd = upd.reshape(-1)[: g.size].reshape(g.shape)
        cols = mq.q.shape[1]
        new_m = QuantizedArray(
            unchunk(mq2, cols), unchunk(ms2, 1), mq.shape, mq.dtype, bits
        )
        new_v = QuantizedArray(
            unchunk(vq2, cols), unchunk(vs2, 1), vq.shape, vq.dtype, bits
        )
        return upd, new_m, new_v

    def update_fn(updates, state, params=None):
        if weight_decay and params is None:
            raise ValueError("lowbit_adamw with weight_decay needs params")
        step = state["step"] + 1
        t = step.astype(jnp.float32)
        bc1 = 1 - b1**t
        bc2 = 1 - b2**t
        # schedule parity with optax.scale_by_schedule: the lr for
        # update t reads schedule(count BEFORE increment) — bias
        # correction uses the incremented count
        lr = _lr(state["step"])
        p_tree = params if params is not None else updates

        def leaf(g, m, v, p):
            if isinstance(m, QuantizedArray):
                upd, m2, v2 = _chunked_update(g, m, v, p, bc1, bc2)
            else:
                upd, m2, v2 = _dense_update(g, m, v, p, bc1, bc2)
            return (-lr * upd).astype(g.dtype), m2, v2

        out = jax.tree.map(
            leaf,
            updates,
            state["m"],
            state["v"],
            p_tree,
            is_leaf=lambda x: isinstance(x, QuantizedArray),
        )
        unzip = lambda i: jax.tree.map(
            lambda x: x[i], out, is_leaf=lambda x: isinstance(x, tuple)
        )
        return unzip(0), {"step": step, "m": unzip(1), "v": unzip(2)}

    return optax.GradientTransformation(init_fn, update_fn)


def mixed_adamw(
    learning_rate,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    v_bits: int = 8,
    m_dtype=jnp.bfloat16,
) -> optax.GradientTransformation:
    """AdamW with bf16 first moment and block-quantized int8 second moment.

    The memory/fidelity middle ground between bf16 states and
    ``lowbit_adamw``: the momentum (whose sign structure steers the
    update) keeps bf16, while the variance — already a smooth, positive
    statistic that Adafactor famously rank-1-factorizes with no loss
    curve change — drops to int8 blocks. At 1.4B params this frees
    ~2 GiB of HBM versus bf16 nu on a 16 GiB chip.

    Unlike ``lowbit_adamw``'s chunk-streamed scan (bounded f32 working
    set, built for when BOTH moments are int8/int4 at >=1.5B), this is a
    plain vectorized leaf update: the f32 transient is one leaf's worth,
    XLA fuses dequant -> update -> requant into the optimizer pass, and
    the step-time cost is NEGATIVE versus bf16 nu (0.68 GiB of nu reads
    plus writes instead of 2.7 GiB each way).

    Reference capability: atorch low-bit optimizers
    (atorch/optimizers/low_bit/functional.py) — this variant's
    moment-asymmetric precision is TPU-motivated (HBM roofline), not a
    translation.
    """
    if v_bits not in (4, 8):
        raise ValueError(f"v_bits must be 4 or 8, got {v_bits}")

    def _lr(step):
        return learning_rate(step) if callable(learning_rate) else learning_rate

    def init_fn(params):
        def m0(p):
            return jnp.zeros_like(p, m_dtype if _should_quantize(p)
                                  else jnp.float32)

        def v0(p):
            if _should_quantize(p):
                return _zero_quantized(p, v_bits)
            return jnp.zeros_like(p, jnp.float32)

        return {
            "step": jnp.zeros([], jnp.int32),
            "m": jax.tree.map(m0, params),
            "v": jax.tree.map(v0, params),
        }

    def update_fn(updates, state, params=None):
        if weight_decay and params is None:
            raise ValueError("mixed_adamw with weight_decay needs params")
        step = state["step"] + 1
        t = step.astype(jnp.float32)
        bc1 = 1 - b1**t
        bc2 = 1 - b2**t
        # schedule parity with optax.scale_by_schedule: the lr for
        # update t reads schedule(count BEFORE increment) — bias
        # correction uses the incremented count
        lr = _lr(state["step"])
        p_tree = params if params is not None else updates

        def leaf(g, m, v, p):
            g32 = g.astype(jnp.float32)
            m2 = adamw_m_ema(g32, m.astype(jnp.float32), b1)
            # nu is stored on SQRT scale: int8's ~2 decades of blockwise
            # dynamic range cover sqrt(nu)'s spread twice as well as
            # nu's, and sqrt(nu) is what the update actually consumes
            if isinstance(v, QuantizedArray):
                v32 = jnp.square(dequantize(v))
            else:
                v32 = v
            v2 = b2 * v32 + (1 - b2) * (g32 * g32)
            upd = adamw_direction(
                m2, v2, bc1, bc2, eps, weight_decay,
                p.astype(jnp.float32) if weight_decay else None,
            )
            new_v = (
                quantize(jnp.sqrt(v2), v_bits)
                if isinstance(v, QuantizedArray)
                else v2
            )
            return (-lr * upd).astype(g.dtype), m2.astype(m.dtype), new_v

        out = jax.tree.map(
            leaf,
            updates,
            state["m"],
            state["v"],
            p_tree,
            is_leaf=lambda x: isinstance(x, QuantizedArray),
        )
        unzip = lambda i: jax.tree.map(
            lambda x: x[i], out, is_leaf=lambda x: isinstance(x, tuple)
        )
        return unzip(0), {"step": step, "m": unzip(1), "v": unzip(2)}

    return optax.GradientTransformation(init_fn, update_fn)
