"""The routed blocks' sum over a token's HELD rows (``parallel/moe.py``:
``_combine_weighted`` forward, ``_dispatch`` backward) as a Pallas TPU
kernel that touches the held rows alone.

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


def tile(t: int, n: int, d: int, dtype):
    """(rows a grid step, columns a pass) for the sum of ``n`` rows of
    ``d`` columns into ``t`` tokens, or None where the XLA body runs:
    off the TPU (and not interpreted), or at shapes the tiles do not fit
    — columns off the 128-lane grid, tokens off the sublane grid, no
    tile of 16 rows and more that divides n. The caller keeps the call
    off a mesh of several devices (a Mosaic call is not partitioned:
    ROADMAP S6)."""
    if pltpu is None or not (device.on_tpu() or pallas_attention.INTERPRET):
        return None
    if t % SUBLANES:
        return None
    rows = next((r for r in ROWS if n % r == 0), None)
    if rows is None:
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

    def live(i, held_ref):
        """Tile i, or the last that holds a held row: a step past it
        fetches nothing new."""
        return jnp.minimum(i, jnp.maximum(held_ref[0] - 1, 0) // rows)

    scalars = pl.BlockSpec(
        (None, 1, rows), lambda c, i, held_ref: (live(i, held_ref), 0, 0),
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
                    lambda c, i, held_ref: (live(i, held_ref), c),
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
