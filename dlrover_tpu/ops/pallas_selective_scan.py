"""Mamba-1's selective scan (``ops/selective_scan.py``) as Pallas TPU
kernels: the forward and the backward of the same recurrence, the state
on the chip from a chunk's first token to its last.

Layout. A block of 1,024 channels is ONE float32 vreg, ``[8 sublanes,
128 lanes]``, sublane k the block's k-th slab of 128 channels. A block's
state is its N states as N such tiles, and ``B_t[n]``, ``C_t[n]`` are
SCALARS read from SMEM (a chunk's ``[chunk * N]`` floats each), so

    s[n] <- exp(Δ_t A[n]) s[n] + (Δ_t u_t) B_t[n];   y_t = Σ_n C_t[n] s[n]

is scalar-times-vreg work with no move across lanes or sublanes in the
forward at all. u, Δ, y and their cotangents are NOT reshaped to ``[B,
S, C / 1024, 8, 128]``: on the chip an array ``[B, S, C]`` lies in tiles
of (8 tokens, 128 channels), that reshape is a copy of it (XLA lays one
in: 168 MB a pass at the Jamba cell's size, five of them a backward),
and the first form of these kernels lost a third of its gain to those
copies. They go in as ``_tiled``, the array's own tile order spelled as
a shape — ``[B, S / 8, C / 128 * 8, 128]``, a bitcast for XLA — and a
token's tile is sublane r of 8 consecutive tiles: ONE strided load (or
store), never a lane window.

Both kernels walk the grid ``(batch, channel block, chunk)``, the chunk
axis sequential, with the block's state (its cotangent, going back) in
VMEM scratch from the first chunk to the last; a chunk's tokens go
through an in-kernel loop, a group of 8 a turn, with the tiles as its
carry.

Forward (``sscan_fwd``) writes y and the state each chunk STARTS from
(``[S / chunk, B, N, C]`` float32 with C as the kernels tile it, the
one residual beside the operands, as in the XLA body). The primal and
the forward rule share one trace (``_traced_once``).

Backward (``sscan_bwd``), the chunks last to first: a chunk is remade
from its start INTO VMEM — per token and state the decay ``a_t`` and the
kept state ``a_t s_{t-1}``, two tiles (``[chunk, N, 8, 128]`` float32
each, 8 MB at 128 tokens of 16 states) — and walked back there with the
state's cotangent and ``dA`` carried; no chunk of states exists in HBM.
The walk goes ``PASS`` states at a time over the chunk's tokens, so that
a pass's carried tiles and its rows of A stay in vregs. ``du`` and
``dΔ`` are vreg work like the forward. ``dB_t[n] = Σ_c g Δ u`` and
``dC_t[n] = Σ_c dy s`` are true sums over the channels of one number a
token and state; reducing a vreg to a scalar 2 N times a token would
cost more than the walk, so they are deferred: the walk stores the
product TILES (over the remade tiles it has just read), and once a
chunk (a) the 8 sublanes of 8 tiles are summed by 8 strided loads — row
r of load k is sublane k of tile r, so their sum is the 8 tiles' sublane
sums as the rows of one vreg, a transposition through memory that costs
a load and an add a tile — and (b) the 128 lanes by the matrix unit,
``ones · Rᵀ`` in float32 (``HIGHEST``), which also lays the chunk's
``[chunk * N]`` sums along lanes as the row they are stored as. These
are sums over ONE block's channels: the partials ``[C / 1024, B, S, N]``
are summed over the blocks by XLA outside (2.6 MB a tensor at 5 blocks).
The other way — the block the grid's inner axis and the sums in scratch
— would keep every block's state cotangent and ``dA`` resident and
re-read B and C's chunk a block for no less work: the reduction's cost
is a tile a (token, state, block) either way.

Precision is the XLA body's: Δ, A, every decay, the state and every sum
float32, ``exp`` in float32. Operands of another dtype are cast by the
caller (``sscan``), outside the kernels.

The sweep, on a v5e at ``[1, 8192, 5120]`` x 16 states, float32 (my
chip runs, PR 54; ms a call forward / forward and backward, the smallest
of five; the XLA body 5.82-5.96 / 19.48-19.56):

- the first form (u, Δ, y reshaped to ``[.., 5, 8, 128]``, the decay
  made again in the walk), chunks of 128, 1 / 2 / 4 / 8 tokens a turn:
  3.86 / 15.09, 3.62 / 14.50, 3.48 / 14.03, 3.43 / 14.05; chunks of 64
  at 4: 3.41 / 14.04; of 256 at 2: 3.66 / 14.17 — the copies inside;
- ``_tiled``, 8 tokens a turn, the walk on three stored tiles a token
  and state and all 16 states a pass: chunks of 64 / 128 / 256: 2.02 /
  10.89, 1.89-1.93 / 10.69-10.89, 1.85 / 10.53. ``exp2`` on A scaled
  outside: 1.79 / 10.73 (not taken: the mathematics would no longer be
  the XLA body's, for 0.1 ms);
- this form (two stored tiles), chunks of 128: ``PASS`` 4 / 8 / 16:
  1.89 / 10.09, 1.89-1.94 / 9.83-9.95, 1.88 / 10.14; ``PASS`` 8 at
  chunks of 64 / 256: 1.98 / 10.01, 1.89 / 9.95.

The forward stands at 44 cycles a token and block for 160 vector
operations: the four vector slots are full. The backward stands at
about 180 for some 370, half of what the slots allow: what is in its
way is not known (its loads and stores, by elimination; section 7 of
PERF.md). In the Jamba cell's step (traced): 1,303.8 ms on the XLA body,
1,132.5 on the first form, 1,044.0 with ``_tiled``, 1,033.2 now.

What a kernel costs before it runs (``ops/pallas_ssd.py``'s docstring,
PR 49's lesson): bodies traced once a process and laid in as plain
equations, the token loops rolled (a group of 8 tokens an inner loop
unrolled whole: traced once), the per-state Python loops the only
unrolled text — 242 and 797 equations; ``tests/test_tpu_compile.py``
holds them.
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

LANES = pallas_attention.LANES
SUBLANES = 8
BLOCK = SUBLANES * LANES  # channels a block: one float32 vreg a state
F32 = jnp.float32
# states a pass of the backward's walk over a chunk (the sweep: module
# docstring)
PASS = 8


def tile(s: int, channels: int, states: int, chunk: int, mesh=None):
    """Whether the kernels run a scan of ``s`` (padded) tokens in chunks
    of ``chunk`` over ``channels`` channels of ``states`` states; where
    not, the XLA body does: off the TPU (and not interpreted), on a mesh
    of several devices (a Mosaic call is not partitioned: ROADMAP S6),
    or at shapes the tiles do not fit — channels that do not fill blocks
    of 1,024, states off the grid of 8, a chunk that does not divide the
    length or is no multiple of 8."""
    if pltpu is None or not (device.on_tpu() or pallas_attention.INTERPRET):
        return False
    if mesh is not None and mesh.size > 1:
        return False
    return not (
        channels % BLOCK or states % SUBLANES or chunk % SUBLANES
        or s % chunk
    )


def _tokens(chunk, body, init, reverse=False):
    """``body(at, t, carry)`` over a chunk's tokens, first to last or
    ``reverse``: ``t`` the token and ``at`` where its tile lies in a
    block of u's layout, ``ref[at]`` (``_tiled``: a group of 8 tokens
    on the leading axis, then sublane r of each of the 8 slabs of 128
    channels — one strided load). A group is a turn of a rolled loop
    and its 8 tokens an inner loop unrolled whole, whose text is traced
    once (Mosaic rolls a loop whole or not at all)."""
    groups = chunk // SUBLANES

    def turn(k, carry):
        g = groups - 1 - k if reverse else k

        def token(q, carry):
            r = SUBLANES - 1 - q if reverse else q
            at = (g, pl.ds(r, SUBLANES, stride=SUBLANES), slice(None))
            return body(at, g * SUBLANES + r, carry)

        return jax.lax.fori_loop(0, SUBLANES, token, carry, unroll=SUBLANES)

    return jax.lax.fori_loop(0, groups, turn, init)


def _sum(parts):
    """Σ parts, pairwise: a chain of N adds a token is N latencies."""
    parts = list(parts)
    while len(parts) > 1:
        parts = [
            parts[k] + parts[k + 1] if k + 1 < len(parts) else parts[k]
            for k in range(0, len(parts), 2)
        ]
    return parts[0]


def _fwd_kernel(
    b_ref, c_ref,  # SMEM [1, chunk * N]: B_t[n], C_t[n] at t * N + n
    u_ref, d_ref,  # [chunk / 8, 64, 128]: ``_tiled``
    a_ref,  # [N, 8, 128]
    y_ref,  # [chunk / 8, 64, 128]
    start_ref,  # [N, 8, 128]: the state the chunk starts from
    s_scr,  # [N, 8, 128]: the state
):
    chunk = u_ref.shape[0] * SUBLANES
    n_states = a_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    start_ref[...] = s_scr[...]

    def token(at, t, state):
        step = d_ref[at]
        new = step * u_ref[at]
        base = t * n_states
        out = tuple(
            jnp.exp(step * a_ref[n]) * s_n + new * b_ref[0, base + n]
            for n, s_n in enumerate(state)
        )
        y_ref[at] = _sum(s_n * c_ref[0, base + n] for n, s_n in enumerate(out))
        return out

    state = _tokens(
        chunk, token, tuple(s_scr[n] for n in range(n_states))
    )
    for n, s_n in enumerate(state):
        s_scr[n] = s_n


def _bwd_kernel(
    b_ref, c_ref,  # SMEM [1, chunk * N]
    u_ref, d_ref, dy_ref,  # [chunk / 8, 64, 128]: ``_tiled``
    a_ref,  # [N, 8, 128]
    start_ref,  # [N, 8, 128]
    du_ref, dd_ref,  # [chunk / 8, 64, 128]
    da_ref,  # [N, 8, 128]: summed over the chunks in place
    db_ref, dc_ref,  # [chunks, chunk * N]: this block's sums, a chunk a row
    g_scr,  # [N, 8, 128]: the cotangent of the state the chunk ends in
    # a chunk's remade tiles, [chunk * N * 8, 128] each, tile (t, n) at
    # rows (t * N + n) * 8:
    kept_scr,  # a_t s_{t-1}; then dB's product tiles
    decay_scr,  # a_t; then dC's product tiles
    red_scr,  # [2, chunk * N, 128]: the tiles' sublane sums (dB, dC)
):
    chunk = u_ref.shape[0] * SUBLANES
    n_states = a_ref.shape[0]
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        g_scr[...] = jnp.zeros_like(g_scr)
        da_ref[...] = jnp.zeros_like(da_ref)

    def tiles(t, states):
        """The rows of token t's tiles of ``states`` in a chunk's
        scratch."""
        first = pl.multiple_of(t * (n_states * SUBLANES), SUBLANES)
        return [
            pl.ds(pl.multiple_of(first + n * SUBLANES, SUBLANES), SUBLANES)
            for n in states
        ]

    def remake(at, t, state):
        step = d_ref[at]
        new = step * u_ref[at]
        base = t * n_states
        rows = tiles(t, range(n_states))
        out = []
        for n, s_n in enumerate(state):
            decay = jnp.exp(step * a_ref[n])
            kept = decay * s_n
            decay_scr[rows[n], :] = decay
            kept_scr[rows[n], :] = kept
            out.append(kept + new * b_ref[0, base + n])
        return tuple(out)

    _tokens(chunk, remake, tuple(start_ref[n] for n in range(n_states)))

    # the walk back, ``PASS`` states a pass over the chunk's tokens: a
    # pass's carried tiles (the state's cotangent and dA) and its rows of
    # A stay in vregs
    for first in range(0, n_states, PASS):
        states = range(first, min(first + PASS, n_states))
        a_rows = [a_ref[n] for n in states]

        def back(at, t, carry):
            later, d_a = carry[:len(states)], carry[len(states):]
            step, u_t, dy_t = d_ref[at], u_ref[at], dy_ref[at]
            new = step * u_t
            base = t * n_states
            rows = tiles(t, states)
            to_b, to_step, out_g, out_a = [], [], [], []
            for k, n in enumerate(states):
                b_n = b_ref[0, base + n]
                kept = kept_scr[rows[k], :]
                g = dy_t * c_ref[0, base + n] + later[k]
                g_kept = g * kept
                out_g.append(decay_scr[rows[k], :] * g)
                decay_scr[rows[k], :] = dy_t * (kept + new * b_n)  # -> dC
                kept_scr[rows[k], :] = g * new  # -> dB
                to_b.append(g * b_n)
                to_step.append(g_kept * a_rows[k])
                out_a.append(d_a[k] + g_kept * step)
            to_b = _sum(to_b)
            d_u, d_step = to_b * step, _sum(to_step) + to_b * u_t
            if first:
                d_u, d_step = du_ref[at] + d_u, dd_ref[at] + d_step
            du_ref[at] = d_u
            dd_ref[at] = d_step
            return tuple(out_g) + tuple(out_a)

        carry = _tokens(
            chunk, back,
            tuple(g_scr[n] for n in states) + tuple(da_ref[n] for n in states),
            reverse=True,
        )
        for k, n in enumerate(states):
            g_scr[n] = carry[k]
            da_ref[n] = carry[len(states) + k]

    # (a) the sublanes: 8 tiles a turn, sublane k of each by one strided
    # load, their sum the tiles' sublane sums as 8 rows
    def sublanes(r, _):
        first = r * (SUBLANES * SUBLANES)
        for which, scr in enumerate((kept_scr, decay_scr)):
            total = None
            for k in range(SUBLANES):
                part = scr[pl.ds(first + k, SUBLANES, stride=SUBLANES), :]
                total = part if total is None else total + part
            red_scr[
                which, pl.ds(pl.multiple_of(r * SUBLANES, SUBLANES), SUBLANES),
                :,
            ] = total
        return 0

    jax.lax.fori_loop(0, chunk * n_states // SUBLANES, sublanes, 0)
    # (b) the lanes, on the matrix unit: ones · Rᵀ is the row [chunk * N]
    ones = jnp.ones((SUBLANES, LANES), F32)
    row = pl.ds(db_ref.shape[0] - 1 - i, 1)  # the chunk: last to first
    for which, ref in enumerate((db_ref, dc_ref)):
        ref[row, :] = jax.lax.dot_general(
            ones, red_scr[which], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=F32,
        )[:1]


def _params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT,
    )


def _specs(chunk, n_states, n_chunks, reverse):
    """The block specs on the grid (batch, channel block, step): the
    chunk is the step, or the last minus it going back."""

    def at(i):
        return n_chunks - 1 - i if reverse else i

    tile_ = (SUBLANES, LANES)
    return dict(
        # B, C [B, chunks, 1, chunk * N] as scalars
        bc=pl.BlockSpec(
            (None, None, 1, chunk * n_states),
            lambda b, j, i: (b, at(i), 0, 0), memory_space=pltpu.SMEM,
        ),
        # u, Δ, y as ``_tiled``: [B, S / 8, C / 128 * 8, 128]
        x=pl.BlockSpec(
            (None, chunk // SUBLANES, SUBLANES * SUBLANES, LANES),
            lambda b, j, i: (b, at(i), j, 0),
        ),
        # A [N, blocks, 8, 128]
        a=pl.BlockSpec((n_states, None, *tile_), lambda b, j, i: (0, j, 0, 0)),
        # starts [chunks, B, N, blocks, 8, 128]
        start=pl.BlockSpec(
            (None, None, n_states, None, *tile_),
            lambda b, j, i: (at(i), b, 0, j, 0, 0),
        ),
        # dA [B, N, blocks, 8, 128]
        da=pl.BlockSpec(
            (None, n_states, None, *tile_), lambda b, j, i: (b, 0, j, 0, 0)
        ),
        # dB, dC [blocks, B, chunks, chunk * N], whole over the chunks
        dbc=pl.BlockSpec(
            (None, None, n_chunks, chunk * n_states),
            lambda b, j, i: (j, b, 0, 0),
        ),
    )


def _tiled(t):
    """[B, S, C] as [B, S / 8, C / 128 * 8, 128], row ``slab * 8 + r``
    of group g the 128 channels of slab ``slab`` at token ``8 g + r``:
    the order the (8, 128) tiles of the array lie in memory already, so
    XLA makes it a bitcast (a plain reshape to [.., C / 1024, 8, 128]
    is a copy of the array: 168 MB a pass at the Jamba cell's size)."""
    bsz, s, ch = t.shape
    t = t.reshape(bsz, s // SUBLANES, SUBLANES, ch // LANES, LANES)
    return t.transpose(0, 1, 3, 2, 4).reshape(
        bsz, s // SUBLANES, ch // LANES * SUBLANES, LANES
    )


def _whole(t, shape):
    """``_tiled``'s inverse, to ``shape`` [B, S, C]."""
    bsz, s, ch = shape
    t = t.reshape(bsz, s // SUBLANES, ch // LANES, SUBLANES, LANES)
    return t.transpose(0, 1, 3, 2, 4).reshape(shape)


def _scalars(t, chunk):
    """B or C [B, S, N] as [B, chunks, 1, chunk * N]."""
    bsz, s, n = t.shape
    return t.reshape(bsz, s // chunk, 1, chunk * n)


@functools.partial(_traced_once, static=("chunk", "interpret"))
def _forward(u, delta, a, b, c, *, chunk, interpret):
    """(y [B, S, C], the state each chunk starts from [S / chunk, B, N,
    C]) of float32 u, Δ [B, S, C], a [C, N], b, c [B, S, N], S whole
    chunks and C whole blocks."""
    bsz, s, ch = u.shape
    n = a.shape[1]
    n_chunks, blocks = s // chunk, ch // BLOCK
    spec = _specs(chunk, n, n_chunks, False)
    like = pallas_attention._out_struct
    y, starts = pl.pallas_call(
        _fwd_kernel,
        grid=(bsz, blocks, n_chunks),
        in_specs=[spec["bc"], spec["bc"], spec["x"], spec["x"], spec["a"]],
        out_specs=[spec["x"], spec["start"]],
        out_shape=[
            like(_tiled(u).shape, F32, u),
            like((n_chunks, bsz, n, blocks, SUBLANES, LANES), F32, u),
        ],
        scratch_shapes=[pltpu.VMEM((n, SUBLANES, LANES), F32)],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="sscan_fwd",
    )(
        _scalars(b, chunk), _scalars(c, chunk), _tiled(u), _tiled(delta),
        a.T.reshape(n, blocks, SUBLANES, LANES),
    )
    return _whole(y, u.shape), starts


@functools.partial(_traced_once, static=("chunk", "interpret"))
def _backward(u, delta, a, b, c, starts, dy, *, chunk, interpret):
    """(du, dΔ [B, S, C], dA [C, N], dB, dC [B, S, N]), float32, from
    the forward's operands, its chunk starts and y's cotangent."""
    bsz, s, ch = u.shape
    n = a.shape[1]
    n_chunks, blocks = s // chunk, ch // BLOCK
    spec = _specs(chunk, n, n_chunks, True)
    like = pallas_attention._out_struct
    tiled = _tiled(u).shape
    sums = (blocks, bsz, n_chunks, chunk * n)
    du, d_delta, d_a, db, dc = pl.pallas_call(
        _bwd_kernel,
        grid=(bsz, blocks, n_chunks),
        in_specs=[spec["bc"], spec["bc"], spec["x"], spec["x"], spec["x"],
                  spec["a"], spec["start"]],
        out_specs=[spec["x"], spec["x"], spec["da"], spec["dbc"],
                   spec["dbc"]],
        out_shape=[
            like(tiled, F32, u), like(tiled, F32, u),
            like((bsz, n, blocks, SUBLANES, LANES), F32, u),
            like(sums, F32, u), like(sums, F32, u),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, SUBLANES, LANES), F32),
            *[pltpu.VMEM((chunk * n * SUBLANES, LANES), F32)] * 2,
            pltpu.VMEM((2, chunk * n, LANES), F32),
        ],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="sscan_bwd",
    )(
        _scalars(b, chunk), _scalars(c, chunk), _tiled(u), _tiled(delta),
        _tiled(dy), a.T.reshape(n, blocks, SUBLANES, LANES),
        starts,
    )
    return (
        _whole(du, u.shape), _whole(d_delta, u.shape),
        jnp.sum(d_a, axis=0).reshape(n, ch).T,
        jnp.sum(db, axis=0).reshape(b.shape),
        jnp.sum(dc, axis=0).reshape(c.shape),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def sscan(u, delta, a, b, c, chunk):
    """``selective_scan._scan`` on the kernels: y [B, S, C] in u's dtype
    from u [B, S, C], Δ [B, S, C] and a [C, N] float32, b and c [B, S,
    N], at shapes ``tile`` admits. Differentiable in all five."""
    return _sscan_fwd(u, delta, a, b, c, chunk)[0]


def _sscan_fwd(u, delta, a, b, c, chunk):
    y, starts = _forward(
        u.astype(F32), delta, a, b.astype(F32), c.astype(F32), chunk=chunk,
        interpret=pallas_attention.INTERPRET,
    )
    return y.astype(u.dtype), (u, delta, a, b, c, starts)


def _sscan_bwd(chunk, residuals, dy):
    u, delta, a, b, c, starts = residuals
    grads = _backward(
        u.astype(F32), delta, a, b.astype(F32), c.astype(F32), starts,
        dy.astype(F32), chunk=chunk, interpret=pallas_attention.INTERPRET,
    )
    return tuple(g.astype(t.dtype) for g, t in zip(grads, (u, delta, a, b, c)))


sscan.defvjp(_sscan_fwd, _sscan_bwd)
