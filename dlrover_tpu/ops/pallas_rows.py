"""The routed blocks over the held prefix: the sum over a token's HELD
rows (``parallel/moe.py``: ``_combine_weighted`` forward, ``_dispatch``
backward) as a Pallas TPU kernel that touches the held rows alone, and
— further down, since PR 72 — the experts' activation between the
grouped matmuls and its derivative over the same prefix
(``experts_act``, ``experts_act_bwd``).

Where a chip holds a part of the experts, the pairs whose expert is here
are the PREFIX of the expert order, ``held_rows`` of them, a number the
device knows and the trace does not. Both sites are

    out[token_of[i]] += w[i] * rows[i]        for i < held_rows

in float32, with ``rows`` [n, d] in expert order. The XLA body gathers
all t·k rows by ``inv`` into token order, selects the held ones and sums
over k: 2.65 ms a call at 65,536 rows of 2,048 bf16 whatever the held
count (40 ns a row: the rows lie anywhere in 256 MB).

A DMA a held row, HBM to VMEM, is what ISSUE 59 asked for first, and
Mosaic refuses it: an array in HBM lies in (8, 128) tiles — (8, 128)(2,
1) in bf16, two rows a word —, a row of it is a sublane of d / 128 tiles
and "slice shape along dimension 0 must be aligned to tiling (8)". So
the kernel turns the sum around: the prefix is CONTIGUOUS, so the grid's
inner axis walks it a tile of ``ROWS`` rows at a time through a plain
block spec, the outer axis takes ``lanes`` columns of d, and the
columns' sums for ALL t tokens stay in VMEM (``[t, lanes]`` float32)
from the prefix's first tile to its last. A tile is cast to float32
once; then, a row at a time, the token's sums are loaded at a dynamic
sublane, the row (times its weight, a scalar from SMEM) added, the sums
stored: lanes / 128 loads, adds and stores a row. A grid step whose tile
lies wholly past ``held_rows`` re-addresses the last live tile (no new
block is fetched) and runs nothing; the rows of the last live tile past
the count are zeroed by a select on the row index, so what lies there —
``ragged_dot`` leaves garbage past its groups — is never summed: a
select, not a product.

The sweep, on a v5e (my chip runs, PR 59; ms a call, the smallest of
three runs of twenty calls; t 8,192 tokens, k 8, d 2,048 bf16 unless
said). XLA body | this kernel at (512 rows, 512 columns), by held rows of
65,536: 2,060 2.69 | 0.21; 8,194 2.65 | 0.38; 14,453 2.65 | 0.53; 65,536
(every row held: OLMoE's case, not taken here) 2.72 | 1.77. By (rows,
columns) at 8,297 held: (512, 256) 0.62, (512, 512) 0.37, (512, 1024)
0.28, (1024, 512) 0.32, (2048, 256) 0.52, (2048, 512) 0.30, **(2048,
1024) 0.25**; at 16,384 tokens (GLM k 4, Trinity k 8 of 131,072 rows,
16,427 held), where 1,024 columns of sums do not fit: (512, 512) 0.42 |
0.71, **(2048, 512) 0.35 | 0.57**; Nemotron's 65,536 rows of 1,024 at
2,824 held: 0.19-0.22 whatever the tile. A row costs 5 to 7 ns a visit
whatever the columns up to 512 and about 9 at 1,024, so the widest block
whose sums fit VMEM is taken; a skipped grid step costs 0.35 us, so the
tiles are long. The same sum as a loop of XLA scatter-adds over chunks
of the prefix: 1.02 ms at 8,194 rows, 6.7 at 65,536 (a row scattered
costs 100 ns); with ``unique_indices`` a group at a time 1.7. In the
cells' traced steps (``rows_sum``, ms a call): 0.20 on Keye, 0.17 on GLM
at a fifth of the balanced rows and 0.29 at all of them, 0.35-0.58 on
Trinity, 0.01 on Nemotron; section 6 of PERF.md has what the steps
gained.

What the kernel costs before it runs (``ops/pallas_ssd.py``'s
docstring): one body of 115 equations weighted and 99 unweighted, each
traced once a process; ``tests/test_tpu_compile.py`` holds them.
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
from dlrover_tpu.ops.pallas_ssd import _traced_once

F32 = jnp.float32
SUBLANES = 8
# the prefix's rows a grid step, the longest that divides n (a skipped
# step costs 0.35 us); a bf16 tile is 16 rows
ROWS = (2048, 1024, 512, 256, 128, 64, 32, 16)
# the columns a pass, the widest that divides d and whose sums fit
WIDTHS = (1024, 512, 256, 128)
# what a pass may hold in VMEM: the tokens' float32 sums, the output
# block twice (the pipeline's two buffers), the tile twice and its
# float32 copy
VMEM_BUDGET = 88 * 1024 * 1024
VMEM_LIMIT = 100 * 1024 * 1024


def _vmem_bytes(t, rows, lanes, itemsize):
    return lanes * (t * (4 + 2 * itemsize) + rows * (4 + 2 * itemsize))


def _tile_rows(n: int):
    """Rows a grid step of a walk over the prefix of n rows, the longest
    of ``ROWS`` that divides n; None off the TPU (and not interpreted)
    or where none does."""
    if pltpu is None or not (device.on_tpu() or pallas_attention.INTERPRET):
        return None
    return next((r for r in ROWS if n % r == 0), None)


def _live(i, held_ref, rows):
    """Tile i of ``rows`` rows, or the last that holds a held row: a
    grid step past it addresses no new block."""
    return jnp.minimum(i, jnp.maximum(held_ref[0] - 1, 0) // rows)


def tile(t: int, n: int, d: int, dtype):
    """(rows a grid step, columns a pass) for the sum of ``n`` rows of
    ``d`` columns into ``t`` tokens, or None where the XLA body runs:
    off the TPU (and not interpreted), or at shapes the tiles do not fit
    — columns off the 128-lane grid, tokens off the sublane grid, no
    tile of 16 rows and more that divides n. The caller keeps the call
    off a mesh of several devices (a Mosaic call is not partitioned:
    ROADMAP S6)."""
    rows = _tile_rows(n)
    if rows is None or t % SUBLANES:
        return None
    itemsize = jnp.dtype(dtype).itemsize
    lanes = next(
        (
            w for w in WIDTHS
            if d % w == 0 and _vmem_bytes(t, rows, w, itemsize) <= VMEM_BUDGET
        ),
        None,
    )
    return None if lanes is None else (rows, lanes)


def _sum_kernel(
    held_ref,  # SMEM [1]: the prefix's length (scalar prefetch)
    tok_ref,  # SMEM [1, R]: the tile's tokens
    w_ref,  # SMEM [1, R] float32: the tile's weights
    rows_ref,  # [R, L]: the tile's rows, L columns of them
    out_ref,  # [t, L]: every token's sum, resident over the prefix
    acc,  # [t, L] float32
    tile_f32,  # [R, L] float32
    *,
    weighted,
):
    step = pl.program_id(1)
    rows = tile_f32.shape[0]

    @pl.when(step == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    left = held_ref[0] - step * rows  # held rows from this tile on

    @pl.when(left > 0)
    def _():
        live = jax.lax.broadcasted_iota(jnp.int32, tile_f32.shape, 0) < left
        tile_f32[...] = jax.lax.select(
            live, rows_ref[...].astype(F32), jnp.zeros(tile_f32.shape, F32)
        )

        def turn(b, carry):
            for u in range(SUBLANES):
                r = b * SUBLANES + u
                row = tile_f32[pl.ds(r, 1), :]
                if weighted:
                    row = row * w_ref[0, r]
                at = pl.ds(tok_ref[0, r], 1)
                acc[at, :] = acc[at, :] + row
            return carry

        turns = (jnp.minimum(left, rows) + SUBLANES - 1) // SUBLANES
        jax.lax.fori_loop(0, turns, turn, 0)

    @pl.when(step == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(
    _traced_once, static=("t", "dtype", "rows", "lanes", "interpret")
)
def _sum(held_rows, token_of, weights, rows_in, *, t, dtype, rows, lanes,
         interpret):
    n, d = rows_in.shape
    weighted = weights is not None
    if not weighted:
        weights = jnp.zeros((n,), F32)  # a block to address; never read

    scalars = pl.BlockSpec(
        (None, 1, rows),
        lambda c, i, held_ref: (_live(i, held_ref, rows), 0, 0),
        memory_space=pltpu.SMEM,
    )
    return pl.pallas_call(
        functools.partial(_sum_kernel, weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(d // lanes, n // rows),
            in_specs=[
                scalars, scalars,
                pl.BlockSpec(
                    (rows, lanes),
                    lambda c, i, held_ref: (_live(i, held_ref, rows), c),
                ),
            ],
            out_specs=pl.BlockSpec((t, lanes), lambda c, i, held_ref: (0, c)),
            scratch_shapes=[
                pltpu.VMEM((t, lanes), F32), pltpu.VMEM((rows, lanes), F32),
            ],
        ),
        out_shape=pallas_attention._out_struct((t, d), dtype, rows_in),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="rows_sum",
    )(
        jnp.reshape(held_rows, (1,)).astype(jnp.int32),
        token_of.astype(jnp.int32).reshape(-1, 1, rows),
        weights.astype(F32).reshape(-1, 1, rows),
        rows_in,
    )


def rows_sum(rows, token_of, weights, held_rows, t, dtype, tiles):
    """``out[token_of[i]] += weights[i] * rows[i]`` over ``i <
    held_rows``, summed in float32: out [t, d] in ``dtype`` from
    ``rows`` [n, d], ``token_of`` [n] int, ``weights`` [n] (None: ones)
    and the int32 scalar ``held_rows``, at shapes ``tile`` admits
    (``tiles`` its answer). A token no row names reads 0; rows from
    ``held_rows`` on are not read into any sum."""
    return _sum(
        held_rows, token_of, weights, rows, t=t, dtype=jnp.dtype(dtype),
        rows=tiles[0], lanes=tiles[1], interpret=pallas_attention.INTERPRET,
    )


# ---------------------------------------------------------------------------
# The experts' interior over the held prefix
# ---------------------------------------------------------------------------

# Between the grouped matmuls (``moe._ragged_experts``) the experts'
# activation, ``h = silu(gate) · up`` or ``h = relu(up)²`` where they have
# no gate, and its derivative are passes over [n, d_expert] of which the
# held prefix alone was written by ``ragged_dot``. The two kernels below
# walk that prefix: the grid's inner axis takes the n rows a tile at a
# time with ``held_rows`` prefetched, and a step whose tile lies wholly
# past the count re-addresses the last live tile — no block fetched, none
# written back — and runs nothing (``_live``, as ``_sum`` does). A live
# tile is
# worked a turn of rows at a time in one rolled loop, float32 inside, ONE
# rounding on the way out; the last live tile's turns stop at the count.
# What lies behind the prefix is neither read nor written: on the chip
# ``ragged_dot``, its transposes and the weight gradient ignore NaN rows
# behind the groups (my chip runs, PRs 59 and 72: prefix and ``dW``
# bit-equal to the clean run's).
#
# On a v5e (my chip runs, PR 72; bf16). XLA's fusions over ALL n rows, ms
# a call forward | derivative: 1.22 | 2.04 at 131,072 x 1,024
# (Trinity-Mini, Kimi-Linear), 2.09 | 3.54 at 262,144 x 896 (Mellum2),
# 1.10 | 1.57 at 65,536 x 2,688 without a gate (Nemotron): 670 GB/s of
# the chip's 819. These kernels at Mellum2's 65,536 held rows: 0.555 |
# 0.933 at tiles of 2,048 rows (635 and 630 GB/s over the prefix), 0.563 |
# 0.945 at 1,024, 0.585 | 0.977 at 512; 16, 32 or 64 rows a turn within
# 0.01 of each other; every row held 2.08 | 3.52, XLA's time. In
# Trinity-Mini's traced step, by the rows its four layers' routers send
# here: 0.08-0.16 forward and 0.12-0.27 back, 1.88 ms a step for the
# twelve calls where the fusions took 17.6. (A call alone is bound by
# the host's dispatch below 0.2 ms: smaller shares were read in a step.)
# The bodies are 36 | 45 equations with a gate and 33 | 36 without, each
# traced once a process (``tests/test_tpu_compile.py``).

# elements a turn of a tile's loop works on: a turn's arrays stay in
# registers from the loads to the stores (``pallas_norm._L2_TURN``)
ACT_TURN = 16 * 1024


def act_tile(n: int, d: int, dtype, gated: bool):
    """(rows a grid step, columns a pass, rows a turn) for the experts'
    interior over [n, d], or None where the XLA body runs: off the TPU
    (and not interpreted), columns off the 128 lanes, no tile of 16
    rows and more that divides n. The rows are the longest of ``ROWS``
    (a skipped step costs 0.35 us), the columns the widest run of lanes
    that divides d and whose tiles fit ``VMEM_BUDGET``: the derivative's
    arrays (three in and two out with a gate, two and one without), each
    in the pipeline's two buffers. The caller keeps the call off a mesh
    of several devices, as ``tile``'s does."""
    rows = _tile_rows(n)
    if rows is None or d % 128:
        return None
    itemsize = jnp.dtype(dtype).itemsize
    arrays = 5 if gated else 3
    lanes = d // 128
    cols = 128 * max(
        w for w in range(1, lanes + 1)
        if lanes % w == 0
        and 2 * arrays * rows * 128 * w * itemsize <= VMEM_BUDGET
    )
    sub = max(SUBLANES, 32 // itemsize)  # a bf16 tile is 16 rows
    turn = sub
    while 2 * turn * cols <= ACT_TURN and 2 * turn <= rows:
        turn *= 2
    return rows, cols, turn


def _act_rows(at, *refs, gated, grad):
    """One turn's rows of the interior (``grad``: of its derivative)."""
    read = lambda ref: ref[at, :].astype(F32)

    def write(ref, value):
        ref[at, :] = value.astype(ref.dtype)

    if gated and grad:
        gate_ref, up_ref, dh_ref, dgate_ref, dup_ref = refs
        gate, dh = read(gate_ref), read(dh_ref)
        sig = jax.lax.logistic(gate)
        silu = gate * sig
        write(dgate_ref, dh * read(up_ref) * (sig + silu * (1.0 - sig)))
        write(dup_ref, dh * silu)
    elif gated:
        gate_ref, up_ref, h_ref = refs
        gate = read(gate_ref)
        write(h_ref, gate * jax.lax.logistic(gate) * read(up_ref))
    elif grad:
        up_ref, dh_ref, dup_ref = refs
        write(dup_ref, read(dh_ref) * (2.0 * jnp.maximum(read(up_ref), 0.0)))
    else:
        up_ref, h_ref = refs
        relu = jnp.maximum(read(up_ref), 0.0)
        write(h_ref, relu * relu)


def _act_kernel(held_ref, *refs, gated, grad, turn):
    rows = refs[0].shape[0]
    left = held_ref[0] - pl.program_id(1) * rows  # held rows from here on

    @pl.when(left > 0)
    def _():
        def one(i, carry):
            at = pl.ds(pl.multiple_of(i * turn, turn), turn)
            _act_rows(at, *refs, gated=gated, grad=grad)
            return carry

        turns = (jnp.minimum(left, rows) + turn - 1) // turn
        jax.lax.fori_loop(0, turns, one, 0)


@functools.partial(
    _traced_once, static=("gated", "grad", "tiles", "interpret")
)
def _act(held_rows, *arrays, gated, grad, tiles, interpret):
    like = arrays[0]
    n, d = like.shape
    rows, cols, turn = tiles
    # the last live tile stands for every tile behind it, in and out:
    # a skipped step moves nothing
    spec = pl.BlockSpec(
        (rows, cols), lambda c, i, held_ref: (_live(i, held_ref, rows), c)
    )
    out = pallas_attention._out_struct((n, d), like.dtype, like)
    two = gated and grad
    return pl.pallas_call(
        functools.partial(_act_kernel, gated=gated, grad=grad, turn=turn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(d // cols, n // rows),
            in_specs=[spec] * len(arrays),
            out_specs=[spec, spec] if two else spec,
        ),
        out_shape=[out, out] if two else out,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="experts_act_bwd" if grad else "experts_act",
    )(jnp.reshape(held_rows, (1,)).astype(jnp.int32), *arrays)


def experts_act(up, gate, held_rows, tiles):
    """``h = silu(gate) · up`` (``gate`` None: ``relu(up)²``) over the
    rows ``i < held_rows`` of ``up`` and ``gate`` [n, d], float32 inside
    and rounded once to their dtype, at shapes ``act_tile`` admits
    (``tiles`` its answer). Rows from ``held_rows`` on are not read and
    what ``h`` holds there is unspecified."""
    arrays = (up,) if gate is None else (gate, up)
    return _act(
        held_rows, *arrays, gated=gate is not None, grad=False, tiles=tiles,
        interpret=pallas_attention.INTERPRET,
    )


def experts_act_bwd(up, gate, d_h, held_rows, tiles):
    """``experts_act``'s derivative over the same rows: ``(d_up,
    d_gate)`` from ``d_h`` — ``d_gate`` None without a gate —, each in
    its primal's dtype and unspecified from ``held_rows`` on."""
    how = dict(grad=True, tiles=tiles, interpret=pallas_attention.INTERPRET)
    if gate is None:
        return _act(held_rows, up, d_h, gated=False, **how), None
    d_gate, d_up = _act(held_rows, gate, up, d_h, gated=True, **how)
    return d_up, d_gate
