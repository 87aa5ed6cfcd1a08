"""Pallas TPU fused norm kernels: rmsnorm / layernorm with an optional
fused residual add.

Why a kernel for a memory-bound op: with the matmul side saturated
(flash attention + fused CE, see docs/performance.md), the residue of
the step is elementwise HBM traffic. XLA lowers the jnp norm as a
reduce pass plus a broadcast-apply pass, and the residual add that
precedes the second norm of every layer body is a third full
[B,S,d_model] round-trip (write x+attn, read it back, write the normed
value). Here each grid program holds a row block in VMEM, computes the
f32 statistics and the normed output in one visit, and — when
``residual`` is passed — also emits the summed stream, so
``x + attn_out -> norm(...)`` costs one read and two writes instead of
three round-trips.

Numerics mirror ``models/decoder.py::_norm`` exactly: the (optional)
residual add happens in the input dtype, statistics are f32
(single-pass E[x], E[x^2] for layernorm), the output is cast back to
the input dtype. The kernels take the last dim AS IT IS (PR 74): a
block's last dimension is the array's, which Mosaic accepts at any
width, so a width off the 128 lanes (GPT-2 XL's 1,600) is neither
padded before the call nor sliced after it — at 1,664 that was seven
whole-activation passes a layer. The sums along the last axis reduce
the logical ``[rows, d]`` value: Mosaic masks the last lane tile's
tail (unspecified in VMEM, not zero) out of a reduction, and the
divisor is the true dim. At a multiple of 128 nothing differs.

Backward is a custom_vjp with row-local Pallas kernels that recompute
the statistics from the saved summed stream (cheaper than storing
per-row stats: in the fused-residual case the stream is a forward
OUTPUT already, so the residuals cost nothing extra). The per-program
scale/bias cotangent partials are summed at the jnp level. The summed
stream's own cotangent is added to ``dx`` inside the backward kernel at
a width of whole lane tiles and by XLA at a width off them
(``_norm_call_bwd`` says why).

Off-TPU the public entry point falls back to the jnp reference; the
``INTERPRET`` hook (or the ``DLROVER_TPU_PALLAS_INTERPRET`` env var,
which also flips ``pallas_attention``) runs the real kernels through
the pallas interpreter so the CPU test mesh exercises the kernel path.
"""

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # pltpu only resolves on TPU builds of jaxlib
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

from dlrover_tpu.common import device
from dlrover_tpu.observability.tracing import counters, set_counter
from dlrover_tpu.ops.pallas_attention import _out_struct
from dlrover_tpu.ops.pallas_ssd import _traced_once

# test hook: run every kernel in pallas interpret mode (CPU-executable).
# Seeded from the environment so a whole pytest run can flip it without
# monkeypatching each module.
INTERPRET = os.environ.get(
    "DLROVER_TPU_PALLAS_INTERPRET", ""
).lower() in ("1", "true", "yes")

# eps defaults matching models/decoder.py::_norm — the decoder wires
# this module in WITHOUT passing eps, so these two constants are the
# single point of truth shared by kernel and fallback
RMS_EPS = 1e-6
LN_EPS = 1e-5

# per-program f32 row-block VMEM budget: bounds [rows, d] f32
# transients (d rounded up to whole 128-lane tiles, what they occupy)
# to ~2 MB each (the kernel holds a handful alongside the input-dtype
# block), far under the ~16 MB VMEM/core
_ROW_BLOCK_BYTES = 2 * 1024 * 1024


def kernels_available(interpret=None) -> bool:
    """True when the Pallas path would actually run (real TPU or
    interpret mode) — what ``cfg.fused_norm=None`` (auto) keys off."""
    interpret = INTERPRET if interpret is None else interpret
    return pltpu is not None and (device.on_tpu() or interpret)


def _fit_rows(n: int, d: int, dtype) -> int:
    """Rows per grid program: largest power-of-two block that divides
    the row count, respects the dtype's min sublane tile, and keeps
    [rows, d] f32 — at the lanes it occupies in VMEM, d rounded up to
    128 — under the VMEM budget. None = shape untileable (fall back to
    the jnp reference)."""
    min_rows = 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8
    budget = _ROW_BLOCK_BYTES // (4 * ((d + 127) // 128 * 128))
    for bn in (512, 256, 128, 64, 32, 16, 8):
        if bn <= budget and bn >= min_rows and n % bn == 0:
            return bn
    return None


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, kind, eps, d, has_bias, has_res):
    it = iter(refs)
    x_ref = next(it)
    scale_ref = next(it)
    bias_ref = next(it) if has_bias else None
    res_ref = next(it) if has_res else None
    out_ref = next(it)
    h_ref = next(it) if has_res else None

    x = x_ref[...]
    if has_res:
        # input-dtype add, matching the jnp path's `x = x + attn`
        x = x + res_ref[...]
        h_ref[...] = x
    x32 = x.astype(jnp.float32)
    s32 = scale_ref[...].astype(jnp.float32)
    if kind == "rmsnorm":
        ms = jnp.sum(x32 * x32, axis=-1, keepdims=True) / d
        out = x32 * jax.lax.rsqrt(ms + eps) * s32
    else:
        mean = jnp.sum(x32, axis=-1, keepdims=True) / d
        ex2 = jnp.sum(x32 * x32, axis=-1, keepdims=True) / d
        var = jnp.maximum(ex2 - mean * mean, 0.0)
        out = (x32 - mean) * jax.lax.rsqrt(var + eps) * s32
        if has_bias:
            out = out + bias_ref[...].astype(jnp.float32)
    out_ref[...] = out.astype(out_ref.dtype)


def _bwd_kernel(*refs, kind, eps, d, has_bias, has_res):
    it = iter(refs)
    g_ref = next(it)
    h_ref = next(it)
    scale_ref = next(it)
    gh_ref = next(it) if has_res else None
    dx_ref = next(it)
    ds_ref = next(it)
    db_ref = next(it) if has_bias else None

    g32 = g_ref[...].astype(jnp.float32)
    h32 = h_ref[...].astype(jnp.float32)
    s32 = scale_ref[...].astype(jnp.float32)
    # recompute the f32 statistics from the saved stream — one VPU
    # reduction instead of storing per-row stats in HBM
    if kind == "rmsnorm":
        ms = jnp.sum(h32 * h32, axis=-1, keepdims=True) / d
        r = jax.lax.rsqrt(ms + eps)
        gx = g32 * s32
        dot = jnp.sum(gx * h32, axis=-1, keepdims=True) / d
        dx = r * gx - (r * r * r) * dot * h32
        ds_ref[0] = jnp.sum(g32 * h32 * r, axis=0, keepdims=True)
    else:
        mean = jnp.sum(h32, axis=-1, keepdims=True) / d
        ex2 = jnp.sum(h32 * h32, axis=-1, keepdims=True) / d
        var = jnp.maximum(ex2 - mean * mean, 0.0)
        r = jax.lax.rsqrt(var + eps)
        xhat = (h32 - mean) * r
        gx = g32 * s32
        m1 = jnp.sum(gx, axis=-1, keepdims=True) / d
        m2 = jnp.sum(gx * xhat, axis=-1, keepdims=True) / d
        dx = r * (gx - m1 - xhat * m2)
        ds_ref[0] = jnp.sum(g32 * xhat, axis=0, keepdims=True)
        if has_bias:
            db_ref[0] = jnp.sum(g32, axis=0, keepdims=True)
    if has_res:
        # the summed stream's own downstream cotangent folds in here so
        # backward too is one visit per row block
        dx = dx + gh_ref[...].astype(jnp.float32)
    dx_ref[...] = dx.astype(dx_ref.dtype)


# ---------------------------------------------------------------------------
# custom_vjp (operates on [N, d] 2-D views)
# ---------------------------------------------------------------------------


def _compiler_params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=("parallel",))


def _call_fwd(kind, eps, dims, interpret, x, scale, bias, res):
    d, bn = dims
    n = x.shape[0]
    has_bias = bias is not None
    has_res = res is not None
    row_spec = pl.BlockSpec((bn, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    in_specs = [row_spec, vec_spec]
    inputs = [x, scale]
    if has_bias:
        in_specs.append(vec_spec)
        inputs.append(bias)
    if has_res:
        in_specs.append(row_spec)
        inputs.append(res)
    out_specs = [row_spec]
    out_shape = [_out_struct((n, d), x.dtype, x)]
    if has_res:
        out_specs.append(row_spec)
        out_shape.append(_out_struct((n, d), x.dtype, x))
    outs = pl.pallas_call(
        functools.partial(
            _fwd_kernel, kind=kind, eps=eps, d=d,
            has_bias=has_bias, has_res=has_res,
        ),
        grid=(n // bn,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="norm_fwd",
    )(*inputs)
    if has_res:
        return outs[0], outs[1]
    return outs[0], x


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _norm_call(kind, eps, dims, interpret, x, scale, bias, res):
    out, h = _call_fwd(kind, eps, dims, interpret, x, scale, bias, res)
    return (out, h) if res is not None else out


def _norm_call_fwd(kind, eps, dims, interpret, x, scale, bias, res):
    out, h = _call_fwd(kind, eps, dims, interpret, x, scale, bias, res)
    primal = (out, h) if res is not None else out
    # h IS the residual set: in the fused-residual case it's already a
    # forward output (free), otherwise it's the input x
    return primal, (h, scale, bias, res is not None)


def _norm_call_bwd(kind, eps, dims, interpret, saved, g):
    d, bn = dims
    h, scale, bias, has_res = saved
    has_bias = bias is not None
    if has_res:
        gout, gh = g
    else:
        gout, gh = g, None
    n = h.shape[0]
    grid = n // bn
    # the summed stream's own cotangent: at a width of whole lane tiles
    # the kernel adds it. At a width off them XLA does (PR 74): an
    # operand of a custom call is not prefetched into VMEM for its
    # other readers, and the carried cotangent's are the MLP's two
    # weight-gradient matmuls (GPT-2 XL on one chip: 9 ms a step);
    # XLA sums the stream's cotangents in a pass of its own anyway
    fold = has_res and d % 128 == 0
    row_spec = pl.BlockSpec((bn, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    # per-program partials live in a [grid, 1, d] array so the block's
    # last two dims equal the array's: Mosaic refuses a (1, d) block
    # over a (grid, d) array (sublane dim must be 8-divisible or full)
    part_spec = pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0))
    part_shape = _out_struct((grid, 1, d), jnp.float32, h)
    in_specs = [row_spec, row_spec, vec_spec]
    inputs = [gout, h, scale]
    if fold:
        in_specs.append(row_spec)
        inputs.append(gh)
    out_specs = [row_spec, part_spec]
    out_shape = [
        _out_struct((n, d), h.dtype, h),
        part_shape,
    ]
    if has_bias:
        out_specs.append(part_spec)
        out_shape.append(part_shape)
    outs = pl.pallas_call(
        functools.partial(
            _bwd_kernel, kind=kind, eps=eps, d=d,
            has_bias=has_bias, has_res=fold,
        ),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="norm_bwd",
    )(*inputs)
    dx = outs[0]
    if has_res and not fold:
        dx = dx + gh
    dscale = outs[1].sum(axis=0).astype(scale.dtype)
    dbias = (
        outs[2].sum(axis=0).astype(bias.dtype)
        if has_bias
        else None
    )
    # d(x + res)/dx = d(x + res)/dres = identity: both get the stream
    # cotangent (gh is in dx already)
    dres = dx if has_res else None
    return dx, dscale, dbias, dres


_norm_call.defvjp(_norm_call_fwd, _norm_call_bwd)


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------


def _reference(x, scale, bias, kind, eps, residual):
    """jnp fallback — the exact math of models/decoder.py::_norm (with
    the pre-norm residual add in the input dtype when fused)."""
    h = x + residual if residual is not None else x
    x32 = h.astype(jnp.float32)
    if kind == "rmsnorm":
        rms = jax.lax.rsqrt(
            jnp.mean(x32 * x32, -1, keepdims=True) + eps
        )
        out = x32 * rms * scale.astype(jnp.float32)
    else:
        mean = jnp.mean(x32, -1, keepdims=True)
        ex2 = jnp.mean(x32 * x32, -1, keepdims=True)
        var = jnp.maximum(ex2 - mean * mean, 0.0)
        out = (x32 - mean) * jax.lax.rsqrt(var + eps)
        out = out * scale.astype(jnp.float32)
        if bias is not None:
            out = out + bias.astype(jnp.float32)
    out = out.astype(x.dtype)
    return (out, h) if residual is not None else out


def norm(
    x,
    scale,
    bias=None,
    kind: str = "rmsnorm",
    *,
    residual=None,
    eps: float = None,
    interpret: bool = None,
):
    """Fused norm over the last axis of ``x`` ([..., D]).

    Without ``residual``: returns ``norm(x)``. With ``residual``:
    returns ``(norm(x + residual), x + residual)`` — the summed stream
    is emitted from the same kernel visit so the caller's residual
    carry costs no extra HBM round-trip.

    ``kind``: "rmsnorm" (bias ignored) | "layernorm". Off-TPU (and for
    untileable shapes) this is the jnp reference with identical
    numerics semantics (f32 statistics, output in ``x.dtype``).
    """
    if kind not in ("rmsnorm", "layernorm"):
        raise ValueError(f"unknown norm kind {kind!r}")
    interpret = INTERPRET if interpret is None else interpret
    if eps is None:
        eps = RMS_EPS if kind == "rmsnorm" else LN_EPS
    if kind == "rmsnorm":
        bias = None
    d = x.shape[-1]
    if not (pltpu is not None and (device.on_tpu() or interpret)):
        return _reference(x, scale, bias, kind, eps, residual)
    n = math.prod(x.shape[:-1])
    bn = _fit_rows(n, d, x.dtype)
    if bn is None:
        return _reference(x, scale, bias, kind, eps, residual)
    # trace time: the call sites that hand the kernels a width off the
    # 128 lanes (their blocks' last tile is part empty)
    set_counter(
        "norm.unaligned_calls",
        counters().get("norm.unaligned_calls", 0) + (d % 128 != 0),
    )
    out = _norm_call(
        kind,
        eps,
        (d, bn),
        interpret,
        x.reshape(n, d),
        scale.reshape(1, d),
        None if bias is None else bias.reshape(1, d),
        None if residual is None else residual.reshape(n, d),
    )
    return jax.tree.map(lambda a: a.reshape(x.shape), out)


# ---------------------------------------------------------------------------
# The L2 norm of every head of a row, on the flat layout
# ---------------------------------------------------------------------------

# columns of a block at most, and elements a turn of the kernel's loop
# works on (32 float32 registers): the body is unrolled over a block's
# heads, and what a body costs before it runs goes by its text
# (``ops/pallas_ssd.py``'s docstring), so a block is few heads wide and
# a turn as many rows as fill the registers' half
_L2_LANES = 1024
_L2_TURN = 32 * 1024


def _fit_heads(n: int, w: int, d: int, dtype):
    """(rows, columns, rows a turn) of ``l2_heads``' block over x
    [n, w], heads of ``d`` columns: whole heads, the widest run of them
    that divides w inside ``_L2_LANES``; as many rows as the VMEM budget
    holds, in whole turns, no more than n's; a turn whole sublane tiles
    of the dtype (8 rows of float32, 16 of bf16), ``_L2_TURN`` elements
    where the block has them. The grid takes the rows' last block short.
    On a v5e at [16384, 4096] float32 (my chip run, PR 69; ms forward |
    back): this tile, 512 x 1024 x 32, 0.84 | 1.20 (637 | 671 GB/s of
    819); 128 rows of all 32 heads, 8 rows a turn, the same (0.85 |
    1.21) at four times the text: the Kimi-Linear step's trace and
    lowering +0.85 s over the parent's on this sandbox's CPU where this
    tile's is -0.4, its warm build on the chip's host +3.8 and +7.5 s
    where this tile's is +2.1 and +2.2; 512 x 1024 at 8 rows a turn 0.94 |
    1.64; 512 columns 1.7 | 3.1 whatever the rows; XLA's fusions on the
    same flat array 4.4 | 8.3."""
    sub = max(8, 32 // jnp.dtype(dtype).itemsize)
    heads = w // d
    cols = d * max(
        k for k in range(1, heads + 1)
        if heads % k == 0 and d * k <= max(d, _L2_LANES)
    )
    fit = (n + sub - 1) // sub * sub
    held = max(sub, _ROW_BLOCK_BYTES // (4 * cols) // sub * sub)
    turn = min(max(sub, _L2_TURN // cols // sub * sub), held, fit)
    rows = min(held // turn * turn, (fit + turn - 1) // turn * turn)
    return rows, cols, turn


def _l2_turns(kernel, rows, turn, *refs):
    """``kernel(row window)`` over a block's rows a turn at a time, one
    rolled loop: a turn's columns stay in registers between the sum
    over a head's lanes and the product with what comes of it."""
    def one(i, carry):
        kernel(pl.ds(pl.multiple_of(i * turn, turn), turn), *refs)
        return carry

    jax.lax.fori_loop(0, rows // turn, one, 0)


def _l2_fwd_rows(at, x_ref, y_ref, *, d, scale, eps):
    for h in range(x_ref.shape[1] // d):
        cols = slice(h * d, (h + 1) * d)
        x = x_ref[at, cols].astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)
        y_ref[at, cols] = (x * (inv * scale)).astype(y_ref.dtype)


def _l2_bwd_rows(at, x_ref, dy_ref, dx_ref, *, d, scale, eps):
    for h in range(x_ref.shape[1] // d):
        cols = slice(h * d, (h + 1) * d)
        x = x_ref[at, cols].astype(jnp.float32)
        dy = dy_ref[at, cols].astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)
        unit = x * inv
        along = jnp.sum(dy * unit, -1, keepdims=True)
        dx_ref[at, cols] = (
            (inv * scale) * (dy - unit * along)
        ).astype(dx_ref.dtype)


def _l2_pass(rows_kernel, name, arrays, *, d, scale, eps, tile, interpret):
    """One pass of ``rows_kernel`` over ``arrays`` [n, w] (all alike),
    a block of ``tile`` a grid step: returns one array like them."""
    like = arrays[0]
    n, w = like.shape
    rows, cols, turn = tile
    spec = pl.BlockSpec((rows, cols), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(
            _l2_turns,
            functools.partial(rows_kernel, d=d, scale=scale, eps=eps),
            rows, turn,
        ),
        grid=(pl.cdiv(n, rows), w // cols),
        in_specs=[spec] * len(arrays),
        out_specs=spec,
        out_shape=_out_struct((n, w), like.dtype, like),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name=name,
    )(*arrays)


_L2_STATIC = ("d", "scale", "eps", "tile", "interpret")
_l2_fwd = _traced_once(
    lambda x, **how: _l2_pass(_l2_fwd_rows, "l2_heads_fwd", (x,), **how),
    _L2_STATIC,
)
_l2_bwd = _traced_once(
    lambda x, dy, **how: _l2_pass(
        _l2_bwd_rows, "l2_heads_bwd", (x, dy), **how
    ),
    _L2_STATIC,
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _l2_call(x, *how):
    return _l2_fwd(x, **dict(zip(_L2_STATIC, how)))


def _l2_call_fwd(x, *how):
    return _l2_call(x, *how), x


def _l2_call_bwd(*args):
    *how, x, dy = args
    return (_l2_bwd(x, dy, **dict(zip(_L2_STATIC, how))),)


_l2_call.defvjp(_l2_call_fwd, _l2_call_bwd)


def l2_heads(x, d: int, scale: float = 1.0, eps: float = 1e-6,
             interpret: bool = None):
    """Every head of x [..., H * d] — a head a run of ``d`` columns, d a
    multiple of the 128 lanes — over its L2 norm, times ``scale``:

        y = x * rsqrt(sum(x², a head's columns) + eps) * scale

    float32 inside, the output in x's dtype: ``decoder._l2_heads``'
    formula, on the layout the delta rules' kernels take (``[B, S,
    H * D]``) where that one norms ``[B, S, H, D]``. Two kernels,
    ``l2_heads_fwd`` (one read, one write) and ``l2_heads_bwd`` (x and
    dy read, the inverse norm made again, ``dx = inv scale (dy − x̂
    ⟨dy, x̂⟩)`` with x̂ = x inv written), behind one ``custom_vjp`` that
    keeps x alone. The caller asks ``kernels_available()`` first."""
    interpret = INTERPRET if interpret is None else interpret
    w = x.shape[-1]
    if d % 128 or w % d:
        raise ValueError(f"{w} columns are no heads of {d} on the lanes")
    flat = x.reshape(-1, w)
    tile = _fit_heads(flat.shape[0], w, d, x.dtype)
    return _l2_call(
        flat, d, float(scale), float(eps), tile, bool(interpret)
    ).reshape(x.shape)
