"""The mixers' depthwise causal conv (``ops/ssd.py::causal_conv``) as
Pallas TPU kernels: x read once where it lies, y written once, a pass.

    y_t = b + Σ_j w_j ⊙ x_{t-K+1+j}        (x before the first token 0)

A 4-tap depthwise conv has no matmul form worth the matrix unit and no
reuse beyond three tokens: it is bound by the memory (336 MB a forward
and 504 MB a backward at either cell's size, 0.41 and 0.62 ms at 819
GB/s) and, in bf16, by the vector unit, which works in float32 on twice
the vregs. The XLA body pads x by K - 1 tokens, casts the whole of it to
float32 and slices K copies shifted by a token — a shift along the
sublanes, which the compiler makes as copies — and its transpose sums
the taps' gradients in fusions of their own: 2.95 ms forward and 7.23
backward at ``bf16[1, 8192, 10240]``, 1.23 and 3.52 at ``f32[1, 8192,
5120]`` (below).

Both kernels walk the grid ``(batch, channel block, token block)``, the
token axis sequential. A block of ``TOKENS`` x ``block`` is loaded once,
as it lies — no pad, no cast outside, no reshape; where the caller hands
over a wider array with the conv's first column (``ssd.Columns``: a
mixer's in-projection), the block spec adds ``start / block`` and the
slice that would hand the columns over, a copy of 168 MB each way, is
never made — and cast to float32 in VMEM. The K taps are that block
rotated 1 .. K - 1 rows along the sublanes (``_shifted``); the one tile
at the block's edge is patched by a select from 8 rows carried in VMEM
scratch from the block before: zeros at the first block.

Forward (``conv_fwd``): ``b + Σ_j w_j ⊙ x_{t-K+1+j}``, j ascending, the
XLA body's order of summation, in float32; y in x's dtype. The primal
and the forward rule share one trace (``_traced_once``).

Backward (``conv_bwd``), the token blocks last to first, from dy and x:
with ``dy_{t+d}`` the cotangent moved d rows the other way (the carried
rows are the first 8 of the block after; zeros past the last token),

    dx_t = Σ_j w_j ⊙ dy_{t+K-1-j}
    dw_j = Σ_t dy_{t+K-1-j} ⊙ x_t,      db = Σ_t dy_t

so one set of moved blocks serves both. The sums over the tokens stay
``[K + 1, 8, block]`` float32 (vreg adds only) in an output block
resident over the token axis; the 8 sublanes and the batch are summed by
XLA outside (1.6 MB). Residuals: the caller's three arrays. Where x was
a window of a wider array its cotangent is dx padded to that width,
which XLA fuses into the sum that builds the projection's cotangent.

Precision is the XLA body's: the taps, the bias, every product and sum
float32. Operands of another dtype are cast inside the kernels (x, dy)
or by the rule (the taps, the bias: ``[K, C]``).

The sweep, on a v5e (my chip runs, PR 55; ms a call, forward / backward
alone, the smallest of five runs of thirty calls), at Nemotron-3's
``bf16[1, 8192, 10240]`` | Jamba2's ``f32[1, 8192, 5120]``:

- the XLA body 2.95 / 7.23 | 1.23 / 3.52; the compiler's one-fusion form
  (a shifted slice a tap in x's dtype, no pad of the whole, no whole
  cast) 1.21 / 5.31 | 1.23 / 3.64; ``lax.conv_general_dilated`` with a
  group a channel 4.06 | 3.70 forward: none within 1.5 x of a kernel;
- the first form — the block and its 8 carried rows stored to a float32
  scratch, the taps as LOADS at row offsets 5 .. 8 of it — by (tokens,
  channels) a block: (128, 1024) 0.78 / 1.24 | 0.57 / 0.89, (256, 512)
  0.81 / 1.23 | 0.57 / 0.88, (256, 1024) 0.72 / 1.32 | 0.54 / 0.84,
  (256, 5120) 0.78 / 1.43 | 0.54 / 0.80, (512, 512) 0.74 / 1.27 | 0.53
  / 0.84, (512, 1024) 0.74 / 1.37 | 0.53 / 0.82, (1024, 1024) 0.75 /
  1.39 | 0.53 / 0.81: flat within 10%. Float32 runs at 77% of the
  memory's rate whatever the block; bf16 at 56% forward, 43-50% back:
  the vector unit's, and a load off the tile grid is not one load;
- the 8 rows before the block by a second ``BlockSpec`` on the same
  array in place of the carried rows (the token axis parallel): 0.73-
  0.76 | 0.54 forward, the same: the carried rows are taken, they need
  no second operand and no bf16 tile of 16;
- this form, the taps as sublane rotates: (256, 512) 0.77 / 1.06 | 0.55
  / 0.82, (256, 1024) 0.69 / 0.99 | 0.54 / 0.82, **(512, 1024) 0.63 /
  0.96 | 0.53 / 0.81**, (512, 512) 0.71 / 0.95 | 0.53 / 0.79, (1024,
  512) 0.64 / 0.87 | 0.53 / 0.78: the bf16 backward a quarter faster,
  and Mosaic builds it in half the time (0.93 s for 2.56 at (512,
  1024), the sandbox's CPU).

In the cells' traced steps (``conv_fwd`` / ``conv_bwd``, ms a call):
0.62 / 0.93 on Nemotron, 0.51 / 0.78 on Jamba, ten + five and twenty-six
+ thirteen calls a step; section 6 of PERF.md has what the steps gained
and what stood at the kernels' doors.

What a kernel costs before it runs (``ops/pallas_ssd.py``'s docstring):
bodies traced once a process and laid in as plain equations — 48 and 79
of them; ``tests/test_tpu_compile.py`` holds them.

The GATED conv (``gated_conv``; ``ops/ssd.py::gated_conv``; PR 73), a
mixer by itself (LFM2's short conv): with the in-projection
``[B | C | x]`` of 3 C columns as it lies,

    z = B ⊙ x;   c_t = Σ_j w_j ⊙ z_{t-K+1+j};   y = C ⊙ c

no bias, no activation. Composed from the kernels above it is three
passes — z written out, ``conv``, the product with C — 8 array-passes
of [B, S, C] forward where the operation needs 4 (three windows read, y
written) and going back many more than its 7 (dy and the windows read,
the three cotangents written). ``gated_conv_fwd`` / ``gated_conv_bwd``
make the one pass each way. The grid is ``(batch, token block)``, the
token axis sequential; a step takes the channels WHOLE — the three
windows through three block specs on the one array — and works them
``chunk`` columns at a time inside, because the backward writes ``[dB |
dC | dx]`` as ONE block of 3 C columns of one output: no concatenate
and no pad behind the kernel, which would move the 6 array-passes the
fusion saves. Forward: z's last 8 rows carried in VMEM from block to
block, as x's are above. Backward, blocks of ``GATED_TOKENS_BWD`` last
to first: ``dc = dy ⊙ C`` moved the other way from its carried first 8
rows gives ``dz`` and the taps' sums exactly as ``conv_bwd``; ``dC = dy
⊙ c`` needs c, which is REMADE from z and z's 8 rows before the block —
those come from a second pair of block specs on the same array (16
rows of B and of x, a bf16 tile; zeros before the first token), since
the walk goes the other way; ``dB = dz ⊙ x``, ``dx = dz ⊙ B``.
Residuals: the caller's two arrays. Float32 inside, one rounding out.

The sweep, on a v5e (my chip runs, PR 73; ms a call at LFM2's
``bf16[8, 4096, 6144]``, 3 taps, forward / backward alone, the smallest
of five runs of twenty calls): the XLA body (``ssd._gated_conv``: the
pad, the whole cast, the shifted copies) 2.34 / 8.25; the compiler's
one-fusion form (shifted slices of z, no pad of the whole) 2.35 / 8.41;
the composition of ``B ⊙ x``, the kernels above and ``⊙ C`` 1.65 /
5.12; these kernels by (forward tokens, backward tokens, chunk): **(512,
256, 1024) 0.801 / 1.481**, (512, 256, 512) 0.802 / 1.479, (512, 256,
2048) 0.802 / 1.477, (256, 256, 1024) 0.801 / 1.475, (512, 512, 1024)
0.799 / 1.467, (512, 512, 512) 0.801 / 1.467, (512, 128, 1024) 0.802 /
1.534, (1024, 256, 1024) 0.806 / 1.478: flat within 1% but for the
smallest backward block, 671 and 635 GB/s over the operation's 537 and
940 MB, 82% and 78% of the memory's rate — bf16 here runs nearer the
memory than the ungated kernels' 56% / 43-50% because each element
loaded does four to seven times the arithmetic's worth of traffic. The
kernels win by 2.9 x forward and 5.6 x backward and are kept; (512,
256, 1024) is taken, the smaller backward block leaving VMEM for wider
models. On the chip against the XLA body: y equal to the bit, the
projection's cotangent within 1.8e-3 of its largest entry (the XLA
body's transpose rounds in another order), the taps' 4.4e-5. In the
LFM2 cell's traced step: 0.77 / 1.44 ms a call, ten + five calls
(PERF.md section 6, PR 73).
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
from dlrover_tpu.ops.pallas_ssd import VMEM_LIMIT, _traced_once

SUBLANES = 8
F32 = jnp.float32
# a grid step's block: TOKENS tokens of the widest of CHANNELS that
# divides the width (the sweep: module docstring)
TOKENS = 512
CHANNELS = (1024, 512, 256, 128)
# the rows carried from block to block: a float32 tile, so K - 1 <= 8
HALO = SUBLANES


def tile(s: int, channels: int, taps: int, start: int = 0, mesh=None):
    """The kernels' channel block for a conv of ``taps`` taps over ``s``
    tokens of ``channels`` channels that begin at column ``start`` of
    the array handed over, or None where the XLA body runs: off the TPU
    (and not interpreted), on a mesh of several devices (a Mosaic call
    is not partitioned: ROADMAP S6), or at shapes the tiles do not fit —
    channels (or their first column) off the 128-lane grid, a length
    that is not whole blocks of ``TOKENS``, more taps than a carried
    tile holds."""
    if pltpu is None or not (device.on_tpu() or pallas_attention.INTERPRET):
        return None
    if mesh is not None and mesh.size > 1:
        return None
    if s % TOKENS or taps - 1 > HALO:
        return None
    return next(
        (c for c in CHANNELS if channels % c == 0 and start % c == 0), None
    )


def _shifted(block, edge, taps, back=False):
    """``block`` [T, Cb] float32 moved 0 .. ``taps`` - 1 rows along the
    tokens, as a list: entry d row t is row ``t - d`` of the block (``t
    + d`` going ``back``), the rows that fall off the block's edge taken
    from ``edge`` [8, Cb] — the last 8 rows of the block before it (the
    first 8 of the one after). A rotate along the sublanes and a select
    on the one tile at the edge: no copy through memory, no load off the
    tile grid."""
    t = block.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, edge.shape, 0)
    out = [block]
    for d in range(1, taps):
        if back:  # row t <- t + d; the last tile's tail from the edge
            moved = pltpu.roll(block, t - d, 0)
            tail = jax.lax.select(
                rows < HALO - d, moved[t - HALO:],
                pltpu.roll(edge, HALO - d, 0),
            )
            out.append(jnp.concatenate([moved[:t - HALO], tail], axis=0))
        else:  # row t <- t - d; the first tile's head from the edge
            moved = pltpu.roll(block, d, 0)
            head = jax.lax.select(
                rows < d, pltpu.roll(edge, d, 0), moved[:HALO]
            )
            out.append(jnp.concatenate([head, moved[HALO:]], axis=0))
    return out


def _fwd_kernel(
    x_ref,  # [T, Cb]
    w_ref,  # [K, Cb] float32
    b_ref,  # [1, Cb] float32
    y_ref,  # [T, Cb]
    edge,  # [8, Cb] float32: the last 8 tokens of the block before
):
    t, k = x_ref.shape[0], w_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        edge[...] = jnp.zeros_like(edge)

    x = x_ref[...].astype(F32)
    taps = _shifted(x, edge[...], k)
    out = b_ref[...]
    for j in range(k):
        out = out + taps[k - 1 - j] * w_ref[pl.ds(j, 1), :]
    y_ref[...] = out.astype(y_ref.dtype)
    edge[...] = x[t - HALO:]


def _bwd_kernel(
    dy_ref, x_ref,  # [T, Cb]: the blocks last to first
    w_ref,  # [K, Cb] float32
    dx_ref,  # [T, Cb]
    sums_ref,  # [K + 1, 8, Cb] float32: dw's and db's sums, 8 rows each,
    # resident over the token axis
    edge,  # [8, Cb] float32: dy's first 8 tokens of the block after
):
    t, k = x_ref.shape[0], w_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        edge[...] = jnp.zeros_like(edge)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    dy = dy_ref[...].astype(F32)
    x = x_ref[...].astype(F32)
    later = _shifted(dy, edge[...], k, back=True)

    def rows(p):
        """Σ over the tokens of p [T, Cb], as the 8 rows of a tile."""
        return jnp.sum(p.reshape(t // SUBLANES, SUBLANES, -1), axis=0)

    dx = None
    for j in range(k):
        # tap j of token t + K - 1 - j read x_t
        g = later[k - 1 - j]
        term = g * w_ref[pl.ds(j, 1), :]
        dx = term if dx is None else dx + term
        sums_ref[j] += rows(g * x)
    sums_ref[k] += rows(dy)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    edge[...] = dy[:HALO]


def _params(interpret, parallel=2):
    """The grid's leading ``parallel`` axes parallel, the token axis
    behind them sequential."""
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * parallel + ("arbitrary",),
        vmem_limit_bytes=VMEM_LIMIT,
    )


def _specs(s, taps, block, start, reverse):
    """The block specs on the grid (batch, channel block, step): the
    token block is the step, or the last minus it going back; ``wide``
    is x where it lies, its channels from column ``start`` of an array
    that may hold more."""
    n = s // TOKENS

    def tokens(first):
        return pl.BlockSpec(
            (None, TOKENS, block),
            lambda b, j, i: (b, n - 1 - i if reverse else i, first + j),
        )

    return dict(
        wide=tokens(start // block),
        x=tokens(0),
        w=pl.BlockSpec((taps, block), lambda b, j, i: (0, j)),
        b=pl.BlockSpec((1, block), lambda b, j, i: (0, j)),
        sums=pl.BlockSpec(
            (None, taps + 1, SUBLANES, block), lambda b, j, i: (b, 0, 0, j)
        ),
    )


_STATIC = ("block", "start", "interpret")


@functools.partial(_traced_once, static=_STATIC)
def _forward(x, weight, bias, *, block, start, interpret):
    """y [B, S, C] in x's dtype of the C channels of x [B, S, >= C] from
    column ``start``, weight [K, C] and bias [C] float32, S whole token
    blocks and C (and ``start``) whole blocks of ``block``."""
    bsz, s, _ = x.shape
    taps, ch = weight.shape
    spec = _specs(s, taps, block, start, False)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(bsz, ch // block, s // TOKENS),
        in_specs=[spec["wide"], spec["w"], spec["b"]],
        out_specs=spec["x"],
        out_shape=pallas_attention._out_struct((bsz, s, ch), x.dtype, x),
        scratch_shapes=[pltpu.VMEM((HALO, block), F32)],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="conv_fwd",
    )(x, weight, bias.reshape(1, ch))


@functools.partial(_traced_once, static=_STATIC)
def _backward(x, weight, dy, *, block, start, interpret):
    """(dx [B, S, C] in x's dtype, dw [K, C], db [C] float32) from x
    (its C channels from column ``start``), the float32 taps and y's
    cotangent."""
    bsz, s, _ = x.shape
    taps, ch = weight.shape
    spec = _specs(s, taps, block, start, True)
    like = pallas_attention._out_struct
    dx, sums = pl.pallas_call(
        _bwd_kernel,
        grid=(bsz, ch // block, s // TOKENS),
        in_specs=[spec["x"], spec["wide"], spec["w"]],
        out_specs=[spec["x"], spec["sums"]],
        out_shape=[
            like(dy.shape, x.dtype, x),
            like((bsz, taps + 1, SUBLANES, ch), F32, x),
        ],
        scratch_shapes=[pltpu.VMEM((HALO, block), F32)],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="conv_bwd",
    )(dy, x, weight)
    sums = jnp.sum(sums, axis=(0, 2))
    return dx, sums[:taps], sums[taps]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def conv(x, weight, bias, block, start=0):
    """``ssd.causal_conv`` on the kernels: y [B, S, C] in x's dtype from
    the C channels of x [B, S, >= C] that begin at column ``start``,
    weight [K, C] and bias [C], at shapes ``tile`` admits (``block`` its
    answer). Differentiable in all three."""
    return _conv_fwd(x, weight, bias, block, start)[0]


def _conv_fwd(x, weight, bias, block, start):
    y = _forward(
        x, weight.astype(F32), bias.astype(F32), block=block, start=start,
        interpret=pallas_attention.INTERPRET,
    )
    return y, (x, weight, bias)


def _conv_bwd(block, start, residuals, dy):
    x, weight, bias = residuals
    dx, dw, db = _backward(
        x, weight.astype(F32), dy, block=block, start=start,
        interpret=pallas_attention.INTERPRET,
    )
    rest = x.shape[2] - start - dx.shape[2]
    if start or rest:  # the other columns had no part in y
        dx = jax.lax.pad(
            dx, jnp.zeros((), dx.dtype),
            ((0, 0, 0), (0, 0, 0), (start, rest, 0)),
        )
    return dx, dw.astype(weight.dtype), db.astype(bias.dtype)


conv.defvjp(_conv_fwd, _conv_bwd)


# ---------------------------------------------------------------------------
# The GATED conv: y = C ⊙ conv(B ⊙ x), the three read where they lie
# ---------------------------------------------------------------------------

# a grid step's tokens going forward and going back, and the widest of
# the chunks of channels a step works at a time inside that divides the
# width (the sweep: module docstring)
GATED_TOKENS = 512
GATED_TOKENS_BWD = 256
GATED_CHUNKS = (1024, 512, 256, 128)
# rows of the block before a token block that the backward is handed
# beside it: a bf16 tile, of which the last ``HALO`` are read
GATED_BEFORE = 16
# bytes of VMEM the widest block may take, both buffers: past it the XLA
# body runs
GATED_VMEM = VMEM_LIMIT // 2


def gated_tile(s: int, channels: int, taps: int, itemsize: int = 2,
               mesh=None):
    """The gated kernels' channel chunk for ``s`` tokens of ``channels``
    channels a gate, or None where the XLA body runs: ``tile``'s reasons
    (off the TPU and not interpreted, a mesh of several devices, channels
    off the 128-lane grid, more taps than a carried tile holds), a
    length that is not whole blocks of ``GATED_TOKENS`` (a multiple of
    ``GATED_TOKENS_BWD``), and a width whose rows do not fit the
    kernels' blocks: a step takes the channels whole, because the
    backward writes its three cotangents as ONE block of 3 x
    ``channels`` columns."""
    if tile(GATED_TOKENS, channels, taps, 0, mesh) is None:
        return None
    if s % GATED_TOKENS:
        return None
    # a step's blocks, twice: the three windows in and y out going
    # forward; dy and the three windows in, the three cotangents out
    rows = max(4 * GATED_TOKENS, 7 * GATED_TOKENS_BWD)
    if 2 * rows * channels * itemsize > GATED_VMEM:
        return None
    return next(c for c in GATED_CHUNKS if channels % c == 0)


def _conv_of(z, edge, w_ref, at):
    """Σ_j w_j ⊙ z_{t-K+1+j} of a chunk z [T, Cb] whose rows before the
    block are ``edge`` [8, Cb]; ``at`` the chunk's columns of the taps."""
    k = w_ref.shape[0]
    taps = _shifted(z, edge, k)
    out = taps[k - 1] * w_ref[pl.ds(0, 1), at]
    for j in range(1, k):
        out = out + taps[k - 1 - j] * w_ref[pl.ds(j, 1), at]
    return out


def _gated_fwd_kernel(
    b_ref, c_ref, x_ref,  # [T, C]: the in-projection's three windows
    w_ref,  # [K, C] float32
    y_ref,  # [T, C]
    edge,  # [8, C] float32: B ⊙ x of the last 8 tokens of the block before
    *, chunk,
):
    t = x_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _():
        edge[...] = jnp.zeros_like(edge)

    for lo in range(0, x_ref.shape[1], chunk):
        at = pl.ds(lo, chunk)
        z = b_ref[:, at].astype(F32) * x_ref[:, at].astype(F32)
        c = _conv_of(z, edge[:, at], w_ref, at)
        y_ref[:, at] = (c_ref[:, at].astype(F32) * c).astype(y_ref.dtype)
        edge[:, at] = z[t - HALO:]


def _gated_bwd_kernel(
    dy_ref,  # [T, C]: the blocks last to first
    b_ref, c_ref, x_ref,  # [T, C]: the three windows
    b_before, x_before,  # [16, C]: B's and x's rows before the block
    w_ref,  # [K, C] float32
    d_ref,  # [T, 3 C]: [dB | dC | dx], one block
    sums_ref,  # [K, 8, C] float32: dw's sums, resident over the tokens
    edge,  # [8, C] float32: dy ⊙ C of the first 8 tokens of the block after
    *, chunk,
):
    t, k = x_ref.shape[0], w_ref.shape[0]
    ch = x_ref.shape[1]
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _():
        edge[...] = jnp.zeros_like(edge)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    # the first token block has nothing before it (its halo block is a
    # clamped read of its own rows)
    first = step == pl.num_programs(1) - 1
    keep = jax.lax.select(first, jnp.zeros((), F32), jnp.ones((), F32))

    def rows(p):
        """Σ over the tokens of p [T, Cb], as the 8 rows of a tile."""
        return jnp.sum(p.reshape(t // SUBLANES, SUBLANES, -1), axis=0)

    for lo in range(0, ch, chunk):
        at = pl.ds(lo, chunk)
        dy = dy_ref[:, at].astype(F32)
        b = b_ref[:, at].astype(F32)
        x = x_ref[:, at].astype(F32)
        z = b * x
        before = (
            b_before[:, at].astype(F32) * x_before[:, at].astype(F32)
        )[GATED_BEFORE - HALO:]
        # c, the conv's output, remade: dC = dy ⊙ c
        c = _conv_of(z, before * keep, w_ref, at)
        d_ref[:, pl.ds(ch + lo, chunk)] = (dy * c).astype(d_ref.dtype)
        # dz from dc = dy ⊙ C moved the other way, as ``_bwd_kernel``'s dx
        dc = dy * c_ref[:, at].astype(F32)
        later = _shifted(dc, edge[:, at], k, back=True)
        dz = None
        for j in range(k):
            g = later[k - 1 - j]
            term = g * w_ref[pl.ds(j, 1), at]
            dz = term if dz is None else dz + term
            sums_ref[j, :, at] += rows(g * z)
        d_ref[:, at] = (dz * x).astype(d_ref.dtype)
        d_ref[:, pl.ds(2 * ch + lo, chunk)] = (dz * b).astype(d_ref.dtype)
        edge[:, at] = dc[:HALO]


def _gated_windows(tokens, ch, n, reverse):
    """The block specs of [B | C | x], each [tokens, ch] of the
    in-projection's 3 x ch columns, on the grid (batch, step): the token
    block is the step, or the last minus it going back."""
    return [
        pl.BlockSpec(
            (None, tokens, ch),
            lambda b, i, w=w: (b, n - 1 - i if reverse else i, w),
        )
        for w in range(3)
    ]


_GATED_STATIC = ("chunk", "interpret")


@functools.partial(_traced_once, static=_GATED_STATIC)
def _gated_forward(proj, weight, *, chunk, interpret):
    """y [B, S, C] in proj's dtype of proj [B, S, 3 C] = [B | C | x] and
    weight [K, C] float32, S whole blocks of ``GATED_TOKENS``."""
    bsz, s, _ = proj.shape
    taps, ch = weight.shape
    n = s // GATED_TOKENS
    return pl.pallas_call(
        functools.partial(_gated_fwd_kernel, chunk=chunk),
        grid=(bsz, n),
        in_specs=[
            *_gated_windows(GATED_TOKENS, ch, n, False),
            pl.BlockSpec((taps, ch), lambda b, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (None, GATED_TOKENS, ch), lambda b, i: (b, i, 0)
        ),
        out_shape=pallas_attention._out_struct(
            (bsz, s, ch), proj.dtype, proj
        ),
        scratch_shapes=[pltpu.VMEM((HALO, ch), F32)],
        compiler_params=_params(interpret, parallel=1),
        interpret=interpret,
        name="gated_conv_fwd",
    )(proj, proj, proj, weight)


@functools.partial(_traced_once, static=_GATED_STATIC)
def _gated_backward(proj, weight, dy, *, chunk, interpret):
    """(dproj [B, S, 3 C] = [dB | dC | dx] in proj's dtype, dw [K, C]
    float32) from proj, the float32 taps and y's cotangent."""
    bsz, s, _ = proj.shape
    taps, ch = weight.shape
    tokens = GATED_TOKENS_BWD
    n = s // tokens
    per = tokens // GATED_BEFORE
    like = pallas_attention._out_struct

    def before(w):
        # the 16 rows before token block n - 1 - i; the first block's own
        # first rows where there are none (the kernel reads zeros there)
        return pl.BlockSpec(
            (None, GATED_BEFORE, ch),
            lambda b, i: (b, jnp.maximum((n - 1 - i) * per - 1, 0), w),
        )

    dproj, sums = pl.pallas_call(
        functools.partial(_gated_bwd_kernel, chunk=chunk),
        grid=(bsz, n),
        in_specs=[
            pl.BlockSpec((None, tokens, ch), lambda b, i: (b, n - 1 - i, 0)),
            *_gated_windows(tokens, ch, n, True),
            before(0), before(2),
            pl.BlockSpec((taps, ch), lambda b, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec(
                (None, tokens, 3 * ch), lambda b, i: (b, n - 1 - i, 0)
            ),
            pl.BlockSpec(
                (None, taps, SUBLANES, ch), lambda b, i: (b, 0, 0, 0)
            ),
        ],
        out_shape=[
            like(proj.shape, proj.dtype, proj),
            like((bsz, taps, SUBLANES, ch), F32, proj),
        ],
        scratch_shapes=[pltpu.VMEM((HALO, ch), F32)],
        compiler_params=_params(interpret, parallel=1),
        interpret=interpret,
        name="gated_conv_bwd",
    )(dy, proj, proj, proj, proj, proj, weight)
    return dproj, jnp.sum(sums, axis=(0, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def gated_conv(proj, weight, chunk):
    """``ssd.gated_conv`` on the kernels: y [B, S, C] in proj's dtype,
    ``C ⊙ conv(B ⊙ x)`` of proj [B, S, 3 C] = [B | C | x] (a mixer's
    in-projection, read where it lies) and weight [K, C], no bias, at
    shapes ``gated_tile`` admits (``chunk`` its answer). Differentiable
    in both; proj's cotangent is one array, [dB | dC | dx]."""
    return _gated_conv_fwd(proj, weight, chunk)[0]


def _gated_conv_fwd(proj, weight, chunk):
    y = _gated_forward(
        proj, weight.astype(F32), chunk=chunk,
        interpret=pallas_attention.INTERPRET,
    )
    return y, (proj, weight)


def _gated_conv_bwd(chunk, residuals, dy):
    proj, weight = residuals
    dproj, dw = _gated_backward(
        proj, weight.astype(F32), dy, chunk=chunk,
        interpret=pallas_attention.INTERPRET,
    )
    return dproj, dw.astype(weight.dtype)


gated_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)
