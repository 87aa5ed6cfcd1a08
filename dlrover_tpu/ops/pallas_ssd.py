"""Mamba-2's chunked scan (``ops/ssd.py``) as Pallas TPU kernels: the
forward, the backward, and between them the forward's state pass.

All walk a sequence chunk by chunk on the grid ``(batch, B/C group,
chunk, turn)``, the last two axes sequential, with the group's state —
every head of the group side by side, a slab of 128 lanes at a time,
``[slabs, state, 128]`` float32, the state TRANSPOSED so that a head is
a run of lanes — in VMEM scratch from the first chunk to the last. A
grid step holds one TURN of one chunk of one group, ``TURN`` slabs: x as
those columns of the projection's own ``[B, S, H * P]`` array, B and C
``[Q, N]``, and per head and token Δ and the running log-decay ``cum``
(float32, made by XLA), a column a query (``[Q, heads]``), ``cum`` also
a row a key (``[heads, Q]``). What the group shares is made in its first
turn and kept in scratch for the others: ``G = C Bᵀ`` and the spread Δ
and ``cum`` (below); what the backward sums over the group (``dG``,
``dB``, ``dC``, ``dΔ``, ``d cum``) is summed in scratch and written in
its last.

Forward (``ssd_fwd``), a chunk: ``G = C Bᵀ`` once; for each head the
decay block ``exp(cum_t − cum_s)`` under the causal mask in float32,
``(L ∘ G)`` in the operands' dtype times ``Δ ⊙ x``; the read-out
``exp(cum) ⊙ (C · state)`` and the update ``exp(last) · state + Bᵀ (Δ x
⊙ to_end)`` one product each a slab of 128 lanes (the width of a v5e's
matrix unit), every head of the slab at once. Neither a decay block, nor
a masked score block, nor ``Δ ⊙ x``, nor a state is written to memory.

Lanes. A head's ``cum`` lies along sublanes (a query a row) and every
use of it wants it along lanes as well. That move is the one thing here
the vector unit cannot do, and it is what the first form of these
kernels spent its time on (three moves a head: the decay block's column
and the two decays a channel; with them the forward took 0.96 ms, 0.68
without the channels' two, 0.52 with none: PR 49's chip runs). So a
head's column is spread over 128 lanes ONCE a chunk (``_spread_heads``,
into scratch, at lanes Python numbers), and the decay block,
``exp(cum)`` and ``to_end`` are all made from that spread copy by
exponentials, which are free beside the moves. A later turn finds its
heads' copies by their number on the scratch's leading axis, and a
head's row by a slice of one sublane. Heads narrower than the 128 lanes
share a slab of ``pack = 128 // P``: a head's product takes the whole
slab as its operand and its own lanes of the result are kept
(``_by_head``), so slabs are read and written whole.

What a kernel costs BEFORE it runs. A kernel's body is Python that is
traced to a jaxpr and lowered to Mosaic in every process that builds a
program holding it, cold or warm: the compile cache keeps the backend's
work only. The Nemotron cell pays it in the step and in the benchmark's
nine forward-only checking programs, inside ``setup_s``. PR 49 landed
these kernels with all eight slabs of a group laid out in Python in one
body and ``_forward`` / ``_backward`` behind a nested ``jax.jit``; the
step ran 9.6% faster and the PR was refused for 4.5 s of set-up (ledger,
PR 49: ``compile.step_trace_s`` 3.19 -> 5.25, ``compile.step_lower_s``
1.54 -> 3.82). Profiled on the chip's host (cProfile around
``step.trace()`` / ``.lower()`` as the benchmark's runner builds them,
and one checking program; my chip runs, PR 50; seconds unprofiled,
parent 2.53-2.78 trace / 1.17-1.28 lower / 0.59-0.65 + 0.39-0.40 the
checking program):

- the seconds go by the EQUATIONS of the bodies traced, about 2,500 a
  second on that host (0.4 ms each; it is a third to a half as fast as
  the sandbox's CPU at this): ``_trace_kernel_to_jaxpr`` and, inside it,
  the inner ``jit`` of every ``jnp.where`` and ``jnp.sum``. ``jax.
  checkpoint`` keeps a layer's trace, so the five layers already share
  one trace and one lowering of each body: call sites cost nothing.
  PR 49's bodies were 651 (forward, traced for the primal and again for
  the forward rule) + 393 (states) + 1,696 (backward) equations: called
  plainly, +1.95 s of trace and +0.04-0.15 s of lowering in the step,
  and +0.22 / +0.02 s in EVERY checking program;
- a nested ``jax.jit`` keeps a trace for the process, so the checking
  programs retrace nothing (+0.0 s), but the ``pjit`` equation it leaves
  costs by itself: +1.15 s of trace and +0.45 s of lowering in the step,
  +0.69 s of lowering in a checking program — and as much with bodies a
  third the size (+1.1 / +0.5 / +0.15 s): partial evaluation,
  transposition and the lowering of a function inside the step's, not
  the kernels. Its trace cache also missed between the primal and the
  forward rule: ``jit`` keys on the tracing context, whose abstract mesh
  is None in the one and an empty mesh under differentiation.

So, here: (1) ``_traced_once`` — ``jax.jit(..., inline=True)``, which
keeps the trace for the process and lays it into the caller as plain
equations, with the abstract mesh named so that primal and forward rule
share one: three bodies traced a process, none in a checking program
(+0.01 s of trace and +0.02 s of lowering in each; a plain call
retraced the forward there, +0.3 s a program, nine programs);
(2) shorter bodies: masks and lane iotas made once a kernel and chosen
by ``lax.select`` (``jnp.where`` is a jitted function of three
equations): 490 / 288 / 1,216 equations with a group whole where PR 49's
were 651 / 393 / 1,696; (3) ``TURN`` slabs a grid step, the rest of the
group on the grid's last axis, which divides the per-slab text — TURN 4
is 332 / 717 equations, TURN 2 230 / 441, TURN 1 179 / 303 — against
about 0.35 us a grid step and a pipeline that drains between turns.

The sweep, on a v5e at 1 x 8,192 tokens, 128 heads of 64 in 8 groups,
state 128, chunks of 256 (my chip runs, PR 50). Kernels alone, ms a
call, forward / states / backward: a group whole 0.93 / 0.63 / 2.03,
TURN 4 1.25 / 1.17 / 2.55, TURN 2 1.45 / 1.36 / 2.73, TURN 1 1.76 /
1.69 / 2.84 (at chunks of 128 2.43 / 2.58 / 3.30). The state pass's
body is short whatever the turn and loses most to it, so it takes a
group whole: a step's twenty calls are 22.6 ms with every group whole,
28.3 at TURN 4 (states whole), 40.2 at TURN 1 throughout. The step's
build on the chip's host, this form, trace + lower over the parent's
(2.64 + 1.26 s): a group whole +1.04 + 0.11 s, TURN 4 +0.94 - 0.03,
TURN 2 +0.71 - 0.06; on the sandbox's CPU, for a described v5e, the
differences are inside its noise (parent 1.7-2.0 + 0.9-1.1 s, TURN 4
1.9-2.1 + 1.0-1.2). In the cell (warm, three traced pairs): TURN 4
``compile.step_trace_s`` 3.06-3.14 -> 4.07-4.27, ``_lower_s`` 1.49-1.59
-> 1.43-1.61, ``_backend_s`` 3.85-4.01 -> 3.12-3.23 (a smaller XLA
program), ``compile.other_s`` 7.64-7.70 -> 7.97-8.32: +0.5 to +1.1 s in
all, where PR 49's form was +3.6; ``setup_s`` 41.64 -> 41.86 s and
8,752 -> 9,521 tokens/s over six pairs (+8.8%), and 9,572 with every
group whole, whose build is about 0.4 s more. TURN 4 is taken: the PR
before this one was lost to set-up, not to speed.

PR 49's other sweeps stand: a rolled ``fori_loop`` over the slabs with
traced lane windows lowers fast and runs a third slower (1.37 / 1.14 /
2.90; two slabs a turn 1.26 / 0.93 / 2.79; four 1.18 / 0.81 / 2.59:
traced windows alias for the compiler) — the grid's turns cost the same
and need no traced window; chunks of 128 0.79 / 0.91 / 2.02 in the
first form, 512 1.21 forward: 256 halves the states and is taken first.

Backward (``ssd_bwd``), the chunks in reverse with ``d state`` in
scratch: a chunk's decay blocks are made again from ``cum``, as they
lie and transposed (``exp`` twice, no transpose of a block); a head's
``Y`` and ``d(Δx) = Mᵀ dY + to_end ⊙ (B · d state)`` in float32;
``dG = Σ_heads L ∘ (dY (Δx)ᵀ)`` and from it ``dB``, ``dC`` with the
read-out's and the update's terms (one product each a slab, summed in
float32 over the group's slabs).
``d cum`` needs no sum over a decay block: what a query reads less what
a key hands on,

    d cum_t = Σ_p dY ⊙ Y − Σ_p (Δx) ⊙ d(Δx)   (+ d last on a chunk's last
    token: exp(last) ⟨d state, state⟩ + Σ_s Σ_p (Δx ⊙ to_end) ⊙ (B · d state))

as flash attention's ``Σ dO ⊙ O``: the row sums of ``dM ∘ M`` ARE ``Σ_p
dY ⊙ Y_within``, its column sums ``Σ_p (Δx) ⊙ d(Δx)_within``, and the
read-out's and ``to_end``'s terms complete both. The two sums cancel
over a chunk, so both are taken on the operands the products took (Δx
as rounded). ``dΔ``'s own term is ``Σ_p x ⊙ d(Δx)``; XLA takes ``d cum``
through the cumulative sum to the rest of ``dΔ`` and to ``dA``.

The state each chunk STARTS from, ``[B, G, chunks, N, heads * P]``
float32 (128 MiB a layer at Nemotron-3's widths and chunks of 256), is
the one thing the walk back needs and cannot remake. It is not a
residual: the backward rule makes it by a pass of the forward kernel
that makes nothing else (``ssd_states``), behind a barrier on ``dy``.

Precision is the XLA body's: Δ, ``cum``, the decays, the state and every
sum float32; the products' operands in the compute dtype (``dM`` and
``dG`` stay float32 until they are an operand, where the XLA body
rounds the first to the compute dtype as a cotangent).

The chunk is the kernel's own tile (``CHUNKS``), not the model's
``ssm_chunk``: any chunk is the same recurrence.
"""

import functools
import math

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
F32 = jnp.float32
# the kernels' chunk: the first of these that divides the padded length
CHUNKS = (256, 128)
VMEM_LIMIT = 64 * 1024 * 1024


def tile(s: int, per_group: int, channels: int, state: int, mesh=None):
    """The kernels' chunk for a sequence of ``s`` (padded) tokens whose
    B/C groups hold ``per_group`` heads of ``channels`` over a state of
    ``state``, or None where the XLA body runs: off the TPU (and not
    interpreted), on a mesh of several devices (a Mosaic call is not
    partitioned: ROADMAP S6), or at shapes the tiles do not fit — heads
    that do not fill 128-lane slabs, a state off the 128 grid, a length
    no chunk divides."""
    if pltpu is None or not (device.on_tpu() or pallas_attention.INTERPRET):
        return None
    if mesh is not None and mesh.size > 1:
        return None
    if channels < LANES:
        if LANES % channels or per_group % (LANES // channels):
            return None
    elif channels % LANES:
        return None
    if state % LANES:
        return None
    return next((q for q in CHUNKS if s % q == 0), None)


def _nt(a, b):
    """a [m, k] @ b[n, k]^T in float32."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=F32
    )


def _tn(a, b):
    """a[k, m]^T @ b [k, n] in float32."""
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=F32
    )


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=F32)


def _pack(channels):
    """Heads a 128-lane slab holds."""
    return max(1, LANES // channels)


def _lane_masks(rows, channels):
    """For each head of a slab [rows, pack * channels], the mask of its
    own lanes: made once a kernel, every choice among a slab's heads is
    one ``select`` on it."""
    pack = _pack(channels)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, pack * channels), 1)
    return [
        (lane >= i * channels) & (lane < (i + 1) * channels)
        for i in range(pack)
    ]


def _by_head(parts, own):
    """[rows, W] taking head i's lanes (``own[i]``) from ``parts[i]``."""
    out = parts[0]
    for mask, part in zip(own[1:], parts[1:]):
        out = jax.lax.select(mask, part, out)
    return out


def _own_lanes(slab, own, i):
    """``slab`` [rows, W] with every head's lanes but head ``i``'s
    zeroed."""
    if len(own) == 1:
        return slab
    return jax.lax.select(own[i], slab, jnp.zeros_like(slab))


def _spread_heads(step, col, dts_scr, rep_scr, own):
    """Every head's column of ``cum`` [Q, heads], a token a row, the
    same in every lane of a slab, into ``rep_scr`` [heads, Q, W], and Δ
    likewise over each head's own lanes of its slab into ``dts_scr``
    [slabs, Q, W]: the one move across lanes a head's Δ or ``cum``
    costs, made once a chunk. The decay blocks and the two decays a
    channel are made from the spread ``cum`` on the vector unit."""
    heads = rep_scr.shape[0]
    pack = len(own)

    def spread(t, r):
        return jnp.broadcast_to(t[:, r:r + 1], rep_scr.shape[1:])

    for r in range(heads):
        rep_scr[r] = spread(col, r)
    for k in range(heads // pack):
        dts_scr[k] = _by_head(
            [spread(step, k * pack + i) for i in range(pack)], own
        )


def _decay(rep, row, mask, transposed=False):
    """A head's decay block ``exp(±(cum_t − cum_s))`` under ``mask``,
    [Q, Q] float32: a query a row and a key a lane as it lies (the
    causal mask), or ``transposed``, a key a row and a query a lane (the
    mask transposed) — from the head's spread column [Q, W] and its row
    [1, Q], an exponential and no move."""
    q = rep.shape[0]
    seg = jnp.concatenate([rep[:, :LANES]] * (q // LANES), axis=1) - row
    if transposed:
        seg = -seg
    return jnp.exp(jax.lax.select(mask, seg, jnp.full_like(seg, -jnp.inf)))


def _masks(q):
    """(s <= t, t >= s transposed) of a [q, q] block: rows queries and
    lanes keys, rows keys and lanes queries."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return rows >= lanes, lanes >= rows


def _turn(slabs, turn):
    """(this grid step's first slab, whether it is the group's first
    turn, whether its last). With one turn a chunk the slab numbers are
    Python's and every window static."""
    if turn == slabs:
        return 0, True, True
    j = pl.program_id(3)
    return j * turn, j == 0, j == slabs // turn - 1


def _when(cond):
    """``pl.when`` that runs a statically true branch in place."""
    if cond is True:
        return lambda body: body()
    return pl.when(cond)


def _fwd_kernel(
    x_ref,  # [1, Q, T*W]: the turn's slabs of the group's x
    b_ref, c_ref,  # [1, Q, N]
    dt_ref,  # [1, 1, Q, R] f32: Δ, a column a query
    col_ref,  # [1, 1, Q, R] f32: cum, a column a query
    row_ref,  # [1, 1, R, Q] f32: cum, a row a key
    out_ref,  # y [1, Q, T*W], or ``starts``: the state the chunk starts
    # from, [1, 1, 1, N, T*W] f32, and nothing of y made
    s_scr,  # [slabs, N, W] f32: the state
    dts_scr,  # [slabs, Q, W] f32: Δ over each head's lanes of a slab
    rep_scr,  # [R, Q, W] f32: a head's cum over a slab's lanes
    g_scr,  # [Q, Q] f32: C Bᵀ
    *, channels, turn, starts,
):
    q = x_ref.shape[1]
    dtype = x_ref.dtype
    slabs, _, width = s_scr.shape
    pack = width // channels
    own = _lane_masks(q, channels)
    first_slab, first_turn, _ = _turn(slabs, turn)
    first_chunk = pl.program_id(2) == 0

    @_when(first_turn)
    def _():
        @pl.when(first_chunk)
        def _():
            s_scr[...] = jnp.zeros_like(s_scr)

        _spread_heads(dt_ref[0, 0], col_ref[0, 0], dts_scr, rep_scr, own)
        if not starts:
            g_scr[...] = _nt(c_ref[0], b_ref[0])

    bm = b_ref[0]
    if not starts:
        cm = c_ref[0]
        scores = g_scr[...]
        causal, _ = _masks(q)

    for kk in range(turn):
        k = first_slab + kk
        lanes = slice(kk * width, (kk + 1) * width)
        heads_here = [k * pack + i for i in range(pack)]
        reps = [rep_scr[r] for r in heads_here]
        xs = (x_ref[0, :, lanes].astype(F32) * dts_scr[k]).astype(
            dtype
        )  # Δ ⊙ x
        rep = _by_head(reps, own)  # cum, a head its own lanes
        grow = jnp.exp(rep)
        to_end = jnp.exp(rep[q - 1:q, :] - rep)
        state = s_scr[k]
        if starts:
            out_ref[0, 0, 0, :, lanes] = state
        else:
            within = [
                _nn((_decay(
                    rep_r, row_ref[0, 0, pl.ds(r, 1), :], causal
                ) * scores).astype(dtype), xs)
                for r, rep_r in zip(heads_here, reps)
            ]
            read = _nn(cm, state.astype(dtype))  # C · state, [Q, W]
            out_ref[0, :, lanes] = (
                _by_head(within, own) + grow * read
            ).astype(dtype)
        x_end = (xs.astype(F32) * to_end).astype(dtype)
        s_scr[k] = grow[q - 1:q, :] * state + _tn(bm, x_end)


def _bwd_kernel(
    x_ref, dy_ref,  # [1, Q, T*W]
    b_ref, c_ref,  # [1, Q, N]
    dt_ref,  # Δ [1, 1, Q, R] f32
    col_ref, row_ref,  # cum [1, 1, Q, R], [1, 1, R, Q] f32
    start_ref,  # [1, 1, 1, N, T*W] f32: the state the chunk started from
    dx_ref,  # [1, Q, T*W]
    db_ref, dc_ref,  # [1, Q, N]
    ddt_ref, dcum_ref,  # [1, 1, Q, R] f32
    ds_scr,  # [slabs, N, W] f32: d state at the chunk's end
    dts_scr,  # [slabs, Q, W] f32: Δ over each head's lanes of a slab
    rep_scr,  # [R, Q, W] f32: a head's cum over a slab's lanes
    g_scr,  # [2, Q, Q] f32: C Bᵀ a query a row, and a key a row
    dg_scr,  # [Q, Q] f32: dG summed over the group's heads
    dbc_scr,  # [2, Q, N] f32: the read-out's dC and the update's dB
    dcol_scr,  # [2, Q, R] f32: dΔ's own term, and d cum
    *, channels, turn,
):
    q = x_ref.shape[1]
    dtype = x_ref.dtype
    slabs, _, width = ds_scr.shape
    pack = width // channels
    own = _lane_masks(q, channels)
    first_slab, first_turn, last_turn = _turn(slabs, turn)
    first_chunk = pl.program_id(2) == 0

    @_when(first_turn)
    def _():
        @pl.when(first_chunk)
        def _():
            ds_scr[...] = jnp.zeros_like(ds_scr)

        _spread_heads(dt_ref[0, 0], col_ref[0, 0], dts_scr, rep_scr, own)
        g_scr[0] = _nt(c_ref[0], b_ref[0])  # a query a row
        g_scr[1] = _nt(b_ref[0], c_ref[0])  # a key a row
        dg_scr[...] = jnp.zeros_like(dg_scr)
        dbc_scr[...] = jnp.zeros_like(dbc_scr)
        dcol_scr[...] = jnp.zeros_like(dcol_scr)

    bm, cm = b_ref[0], c_ref[0]
    scores, scores_t = g_scr[0], g_scr[1]
    causal, causal_t = _masks(q)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, dcol_scr.shape[1:], 1)
    last_token = jax.lax.broadcasted_iota(jnp.int32, (q, width), 0) == q - 1

    def per_head(t, i):
        """Σ over head i's lanes of ``t`` [Q, W], spread over the heads'
        columns [Q, R]."""
        return jnp.broadcast_to(jnp.sum(
            _own_lanes(t, own, i), axis=1, keepdims=True
        ), head_lane.shape)

    d_scores = dg_scr[...]
    d_step, d_cum = dcol_scr[0], dcol_scr[1]
    dc, db = dbc_scr[0], dbc_scr[1]
    for kk in range(turn):
        k = first_slab + kk
        lanes = slice(kk * width, (kk + 1) * width)
        heads_here = [k * pack + i for i in range(pack)]
        reps = [rep_scr[r] for r in heads_here]
        raw, dys = x_ref[0, :, lanes].astype(F32), dy_ref[0, :, lanes]
        step_x = dts_scr[k]
        xs = (raw * step_x).astype(dtype)  # Δ ⊙ x
        xf, dyf = xs.astype(F32), dys.astype(F32)
        state, d_state = start_ref[0, 0, 0, :, lanes], ds_scr[k]
        state_op, d_state_op = state.astype(dtype), d_state.astype(dtype)
        read = _nn(cm, state_op)  # C · state, [Q, W]
        back = _nn(bm, d_state_op)  # B · d state
        y_in, dx_in = [], []
        for i, (r, rep_r) in enumerate(zip(heads_here, reps)):
            row = row_ref[0, 0, pl.ds(r, 1), :]
            decay = _decay(rep_r, row, causal)
            decay_t = _decay(rep_r, row, causal_t, transposed=True)
            y_in.append(_nn((decay * scores).astype(dtype), xs))
            dx_in.append(_nn((decay_t * scores_t).astype(dtype), dys))
            d_scores = d_scores + decay * _nt(_own_lanes(dys, own, i), xs)
        rep = _by_head(reps, own)  # cum, a head its own lanes
        grow = jnp.exp(rep)
        to_end = jnp.exp(rep[q - 1:q, :] - rep)
        dy_grown = dyf * grow
        x_end = xf * to_end
        last = grow[q - 1:q, :]
        y = _by_head(y_in, own) + grow * read
        dx = _by_head(dx_in, own) + to_end * back  # d(Δ x)
        dx_ref[0, :, lanes] = (dx * step_x).astype(dtype)
        # d cum a query and channel: what the query reads less what the
        # key hands on, ``dY ⊙ Y − (Δ x) ⊙ d(Δ x)`` — two sums that cancel
        # over a chunk, so both on the operands the products took (Δ x
        # as rounded); the chunk's last token takes ``d last`` besides
        d_last = last * jnp.sum(
            d_state * state, axis=0, keepdims=True
        ) + jnp.sum(x_end * back, axis=0, keepdims=True)
        moved = dyf * y - xf * dx + jax.lax.select(
            last_token, jnp.broadcast_to(d_last, (q, width)),
            jnp.zeros((q, width), F32),
        )
        to_step = raw * dx  # dΔ's own term, a channel
        for i, r in enumerate(heads_here):
            d_step = jax.lax.select(
                head_lane == r, per_head(to_step, i), d_step
            )
            d_cum = jax.lax.select(head_lane == r, per_head(moved, i), d_cum)
        dy_grown, x_end = dy_grown.astype(dtype), x_end.astype(dtype)
        ds_scr[k] = last * d_state + _tn(cm, dy_grown)
        dc = dc + _nt(dy_grown, state_op)
        db = db + _nt(x_end, d_state_op)

    if last_turn is not True:
        dg_scr[...] = d_scores
        dcol_scr[0], dcol_scr[1] = d_step, d_cum
        dbc_scr[0], dbc_scr[1] = dc, db

    @_when(last_turn)
    def _():
        d_op = d_scores.astype(dtype)
        ddt_ref[0, 0] = d_step
        dcum_ref[0, 0] = d_cum
        dc_ref[0] = (_nn(d_op, bm) + dc).astype(dc_ref.dtype)
        db_ref[0] = (_tn(d_op, cm) + db).astype(db_ref.dtype)


def _layouts(dt, cum, groups):
    """Δ and ``cum`` [B, S, H] float32 a column a query ([B, G, S, R]),
    and ``cum`` a row a key ([B, G, R, S])."""
    b, s, h = cum.shape
    shape = (b, s, groups, h // groups)
    by_group = cum.reshape(shape)
    return (
        dt.reshape(shape).transpose(0, 2, 1, 3),
        by_group.transpose(0, 2, 1, 3), by_group.transpose(0, 2, 3, 1),
    )


def _scratch(state, wide, per_group, q, channels, extra=()):
    """A walk's scratch: the group's state (or its cotangent) a slab,
    Δ a slab and ``cum`` a head spread over a slab's lanes, and ``C Bᵀ``
    (``extra`` shapes follow)."""
    width = max(channels, LANES)
    return [
        pltpu.VMEM((wide // width, state, width), F32),
        pltpu.VMEM((wide // width, q, width), F32),
        pltpu.VMEM((per_group, q, width), F32),
    ] + [pltpu.VMEM(shape, F32) for shape in extra]


def _params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary"
        ),
        vmem_limit_bytes=VMEM_LIMIT,
    )


def _specs(q, per_group, channels, state, n_chunks, turn, reverse):
    """The block specs of a chunk's operands on the grid (batch, group,
    step, turn): the chunk is the step, or the last minus it going back;
    a turn is ``turn`` slabs of the group's lanes."""
    width = max(channels, LANES)
    turns = per_group * channels // (width * turn)
    wide = turn * width

    def chunk(i):
        return n_chunks - 1 - i if reverse else i

    return dict(
        x=pl.BlockSpec(
            (1, q, wide), lambda b, g, i, j: (b, chunk(i), g * turns + j)
        ),
        bc=pl.BlockSpec((1, q, state), lambda b, g, i, j: (b, chunk(i), g)),
        col=pl.BlockSpec(
            (1, 1, q, per_group), lambda b, g, i, j: (b, g, chunk(i), 0)
        ),
        row=pl.BlockSpec(
            (1, 1, per_group, q), lambda b, g, i, j: (b, g, 0, chunk(i))
        ),
        start=pl.BlockSpec(
            (1, 1, 1, state, wide),
            lambda b, g, i, j: (b, g, chunk(i), 0, j),
        ),
    )


# Slabs of 128 lanes a grid step of the forward and the backward kernel
# (0: a group whole). Build seconds against milliseconds a step, from the
# module's docstring (my chip runs, PR 50; ms a step of twenty calls /
# the step's trace + lower over the parent's, chip's host / equations
# traced a process): a group whole 22.6 / +1.15 s / 1,994; 4: 28.3 /
# +0.91 / 1,337; 2: 31.3 / +0.65 / 959; 1: 34.9 / not measured in this
# form / 770. Rejected whatever the turn: plain calls (+0.3 s in
# each of the benchmark's nine checking programs), a nested ``jax.jit``
# that stays (+1.6 s a step by itself)
TURN = 4
_STATIC = ("q", "channels", "state", "interpret", "turn")


def _traced_once(fn, static):
    """``fn`` traced once a PROCESS for each set of shapes, and laid
    into every program that calls it as plain equations: ``jax.jit``
    with ``inline=True`` keeps the trace and leaves no nested function
    behind (one that stays, PR 49's form, cost the chip's host 1.1 s of
    tracing and 0.5 s of lowering a step however short the kernels: my
    chip runs, PR 50). ``jit`` keys a trace on the tracing context, and
    the context's abstract mesh is None where the step's forward is
    traced and an empty mesh under differentiation, so it is named on
    both sides: the forward rule then takes the primal's trace."""
    traced = jax.jit(fn, static_argnames=static, inline=True)

    @functools.wraps(fn)
    def call(*args, **kwargs):
        with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
            return traced(*args, **kwargs)

    return call


def _sizes(x, cum, b_mat, q, channels, state, turn):
    """(batch, groups, heads a group, chunks, a group's lanes, its
    slabs, slabs a turn: what ``turn`` and the slabs both divide by,
    all of them for 0)."""
    bsz, s, _ = x.shape
    groups = b_mat.shape[2] // state
    per_group = cum.shape[2] // groups
    wide = per_group * channels
    slabs = wide // max(channels, LANES)
    return bsz, groups, per_group, s // q, wide, slabs, math.gcd(turn, slabs)


@functools.partial(_traced_once, static=_STATIC + ("starts",))
def _forward(x, dt, cum, b_mat, c_mat, *, q, channels, state, interpret,
             turn, starts=False):
    """y [B, S, H*P] — or, with ``starts``, the state each chunk starts
    from, [B, G, chunks, N, R*P] float32 — of x [B, S, H*P], dt and cum
    [B, S, H] float32 (Δ and the running sum of A Δ within chunks of
    ``q``), b_mat and c_mat [B, S, G*N]. The state pass takes a chunk
    whole: its body is short."""
    bsz, groups, per_group, n_chunks, wide, slabs, turn = _sizes(
        x, cum, b_mat, q, channels, state, 0 if starts else turn
    )
    spec = _specs(q, per_group, channels, state, n_chunks, turn, False)
    out_shape = (bsz, groups, n_chunks, state, wide) if starts else x.shape
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, channels=channels, turn=turn, starts=starts,
        ),
        grid=(bsz, groups, n_chunks, slabs // turn),
        in_specs=[spec["x"], spec["bc"], spec["bc"], spec["col"],
                  spec["col"], spec["row"]],
        out_specs=spec["start" if starts else "x"],
        out_shape=pallas_attention._out_struct(
            out_shape, F32 if starts else x.dtype, x
        ),
        scratch_shapes=_scratch(
            state, wide, per_group, q, channels, [(q, q)]
        ),
        compiler_params=_params(interpret),
        interpret=interpret,
        name="ssd_states" if starts else "ssd_fwd",
    )(x, b_mat, c_mat, *_layouts(dt, cum, groups))


@functools.partial(_traced_once, static=_STATIC)
def _backward(x, dt, cum, b_mat, c_mat, dy, *, q, channels, state, interpret,
              turn):
    """(dx, dΔ, d cum, dB, dC) from the forward's operands and y's
    cotangent; dΔ is Δ's own part, what reaches it through ``cum`` is
    the caller's. The state each chunk started from, which the walk back
    needs and cannot remake, comes from a pass of the forward kernel
    that makes nothing else, held behind ``dy``: without the barrier the
    compiler runs that pass as soon as its operands exist, and the
    states (128 MiB a layer at Nemotron-3's widths) are alive at the
    step's memory peak, the gated norm's backward."""
    x, dt, cum, b_mat, c_mat, dy = jax.lax.optimization_barrier(
        (x, dt, cum, b_mat, c_mat, dy)
    )
    starts = _forward(
        x, dt, cum, b_mat, c_mat, q=q, channels=channels, state=state,
        interpret=interpret, turn=turn, starts=True,
    )
    bsz, groups, per_group, n_chunks, wide, slabs, turn = _sizes(
        x, cum, b_mat, q, channels, state, turn
    )
    heads = cum.shape[2]
    spec = _specs(q, per_group, channels, state, n_chunks, turn, True)
    step, col, row = _layouts(dt, cum, groups)
    like = pallas_attention._out_struct
    dx, db, dc, d_step, d_cum = pl.pallas_call(
        functools.partial(_bwd_kernel, channels=channels, turn=turn),
        grid=(bsz, groups, n_chunks, slabs // turn),
        in_specs=[spec["x"], spec["x"], spec["bc"], spec["bc"], spec["col"],
                  spec["col"], spec["row"], spec["start"]],
        out_specs=[spec["x"], spec["bc"], spec["bc"], spec["col"],
                   spec["col"]],
        out_shape=[
            like(x.shape, x.dtype, x),
            like(b_mat.shape, b_mat.dtype, x),
            like(c_mat.shape, c_mat.dtype, x),
            like(col.shape, F32, x),
            like(col.shape, F32, x),
        ],
        scratch_shapes=_scratch(
            state, wide, per_group, q, channels,
            [(2, q, q), (q, q), (2, q, state), (2, q, per_group)],
        ),
        compiler_params=_params(interpret),
        interpret=interpret,
        name="ssd_bwd",
    )(x, dy, b_mat, c_mat, step, col, row, starts)

    def tokens_first(t):
        return t.transpose(0, 2, 1, 3).reshape(bsz, x.shape[1], heads)

    return dx, tokens_first(d_step), tokens_first(d_cum), db, dc


def _statics(q, channels, state):
    return dict(
        q=q, channels=channels, state=state,
        interpret=pallas_attention.INTERPRET, turn=TURN,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def scan(x, dt, cum, b_mat, c_mat, q, channels, state):
    """The chunked scan on whole chunks of ``q`` tokens: y [B, S, H*P]
    from x [B, S, H*P] (heads of ``channels``), dt and cum [B, S, H]
    float32 (Δ, and the running sum of A Δ within each chunk), b_mat and
    c_mat [B, S, G*N] (groups of ``state``). Differentiable in all
    five."""
    return _forward(x, dt, cum, b_mat, c_mat, **_statics(q, channels, state))


def _scan_fwd(x, dt, cum, b_mat, c_mat, q, channels, state):
    operands = (x, dt, cum, b_mat, c_mat)
    return _forward(*operands, **_statics(q, channels, state)), operands


def _scan_bwd(q, channels, state, operands, dy):
    return _backward(*operands, dy, **_statics(q, channels, state))


scan.defvjp(_scan_fwd, _scan_bwd)
