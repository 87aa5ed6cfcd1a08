"""The gated delta rule's walk from chunk to chunk (``ops/gated_delta.py``)
as Pallas TPU kernels: the forward, the backward, and between them the
forward's state pass; and, before the walk, the triangular inverse of
whole chunks (``inverse``, the kernel ``tri_inverse``, which the rule
with a decay a key channel calls too).

The walk's three kernels go through a sequence chunk by chunk (chunks of
``CHUNK`` = 64 tokens) on the grid ``(batch, key head, chunk)``, the
last axis sequential, with the key head's state — its R value heads',
``[R, Dk, Dv]`` float32 — in VMEM scratch from the first chunk to the
last. A grid step holds one chunk of one key head: q and k as that
head's columns of the caller's ``[B, S, Hk * Dk]`` arrays (one array a
KEY head: its R value heads are handled in the one visit), v and o as
the R heads' columns of ``[B, S, Hv * Dv]``, and what is made of whole
chunks before the walk: the triangular inverse ``T = (I + A)^{-1}``,
float32, the R heads' matrices SIDE BY SIDE on the lanes (``[C, R C]``:
value head h is lanes ``[C h, C h + C)``, a static slice in the visit;
[64, 128] at R = 2, where ``[R, 64, 64]`` was twice its bytes in HBM, the
64 columns padded to a tile's 128 lanes), the running log-decay γ a
column a token (``[C, R]``) and a row a token, with β a row a token
beside it (``[2 R, C]``). Everything else of a chunk is made in the
visit and never written: the decay block ``exp(γ_i − γ_j)`` (the
difference first), ``Q Kᵀ``, ``W = (T ⊙ β e^γ) K``, ``U = (T ⊙ β) V``,
``V' = U − W S``, the read-out ``e^γ ⊙ (Q S) + (Q Kᵀ ⊙ decay) V'`` and
the update ``S ← e^{γ_C} S + Kᵀ (e^{γ_C − γ} ⊙ V')``.

Why T comes from outside the walk. The inverse is by substitution
(backward stable where a chunk's keys repeat), rows one at a time inside
blocks of 16: on the vector unit that is work over the BATCH of chunks
laid on the lanes (8,192 chunks and heads a layer: a thousandth of the
rule's work, and not a step of the 256-step dependence), and inside a
visit it would be 16 dependent sublane steps of a ``[16, 16]`` block.
So ``A`` and T's hand-written derivative (``−strict_lower(Tᵀ dT Tᵀ)``)
stay XLA's, parallel over the chunks, T is a kernel of its own, parallel
over the chunks too, and the walk takes ``T`` and hands back ``dT``.

The inverse (``tri_inverse``, PR 71; ``gated_delta._inverse_of``'s
algorithm to the multiply-add). As XLA's it was a dozen passes over
``[8192, 64, 64]`` arrays that tiles pad — A moved lanes-last, its
blocks cut out, t11 and t22 moved back, the last merge's batched
products, the halves concatenated: 7 ms a pass and layer for 0.3 ms of
substitution (PERF.md section 6, PR 71) — because the batch must lie on
the lanes for the substitution and first for everything else, and each
change of mind is a copy through HBM. In the kernel a grid step takes
``INVERSE_ROWS`` = 128 rows of the batch as one block ``[128 C, W]``
(W = P C lanes: P matrices side by side), turns matrix row i of all of
them — a strided load ``[128, W]`` — to ``[W, 128]`` by one transpose,
64 of them, and has A in VMEM as ``[row, (p, column), batch]``. The
substitution's row i of a diagonal block is then a ``[16, 128]`` array
(the block's columns on the sublanes) and ``a_il`` a ``[1, 128]`` row
spread over them; a merge's two products are rolled loops of such
multiply-adds; T goes back the way A came. HBM sees A once and T once.

Forward (``gdn_fwd``) writes o alone; the state never leaves VMEM.
``gdn_states`` is the same body writing only the state each chunk STARTS
from, ``[B, N, Hk, R, Dk, Dv]`` float32 (537 MB a layer at Qwen3-Next's
widths and 16,384 tokens): the one thing the walk back needs and cannot
remake, made by the backward rule and not kept from the forward.

Backward (``gdn_bwd``), the chunks last to first with the state's
cotangent in scratch. A visit remakes the chunk's operands from q, k, v,
T, γ, β and its starting state, then, with ``a = e^γ``, ``b = e^{γ_C −
γ}``, ``λ = e^{γ_C}``, ``M = Q Kᵀ ⊙ decay``, ``P = Q S``:

    dVb = K dS'                       dK += (b ⊙ V') dS'ᵀ
    dV' = Mᵀ dO + b ⊙ dVb             dM  = dO V'ᵀ
    dQ += (a ⊙ dO) Sᵀ                 dW  = −dV' Sᵀ       dU = dV'
    dS  = λ dS' + Qᵀ (a ⊙ dO) − Wᵀ dV'
    dTw = dW Kᵀ    dK += (T ⊙ β e^γ)ᵀ dW    dTu = dU Vᵀ    dV = (T ⊙ β)ᵀ dU
    dT  = dTw ⊙ β e^γ + dTu ⊙ β       dQ += (dM ⊙ decay) K    dK += (dM ⊙ decay)ᵀ Q
    dβ_j = e^{γ_j} Σ_i dTw_ij T_ij + Σ_i dTu_ij T_ij
    dγ_i = Σ_e dO ⊙ a P − Σ_e dVb ⊙ b V'  + Σ_j dM ⊙ M      (a column)
    dγ_j = β_j e^{γ_j} Σ_i dTw_ij T_ij − Σ_i dM ⊙ M          (a row)
    dγ_C += Σ_i Σ_e dVb ⊙ b V' + λ ⟨dS', S⟩

γ's cotangent comes out in two layouts (what sums over a row of a block
is a column, what sums over a column a row); the caller adds them, takes
the sum through the cumulative sum to g, and takes dT through the
inverse to k, g and β.

Precision is the XLA body's (``gated_delta``'s docstring): g, β, γ, the
differences, the decays, T and the state float32; every product on
operands of the compute dtype, summed in float32 — one pass on bf16
operands; on float32 operands three passes of bf16 pieces, ``x = hi +
lo`` split here (``_pieces``) and ``hi·hi + hi·lo + lo·hi`` summed in
float32, which is what ``Precision.HIGH`` is for XLA and what Mosaic
does not take by name. Cotangents are operands like the rest.

What a kernel costs before it runs (``pallas_ssd``'s docstring): the
three bodies go through ``_traced_once``.
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

LANES = pallas_attention.LANES
F32 = jnp.float32
BF16 = jnp.bfloat16
# the kernels' chunk, and the only one they take
CHUNK = 64
VMEM_LIMIT = 64 * 1024 * 1024

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def tile(dk: int, dv: int, chunk: int, mesh=None) -> bool:
    """Whether the kernels take a call: on a TPU (or interpreted), on one
    device (a Mosaic call is not partitioned: ROADMAP S6), key and value
    channels on the 128-lane grid, chunks of ``CHUNK``. Anywhere else
    the XLA body runs."""
    if pltpu is None or not (device.on_tpu() or pallas_attention.INTERPRET):
        return False
    if mesh is not None and mesh.size > 1:
        return False
    return chunk == CHUNK and dk % LANES == 0 and dv % LANES == 0


def _pieces(x, dtype):
    """``x`` as a product takes it: rounded to the compute dtype; a
    float32 operand as its two bf16 pieces, ``x ≈ hi + lo``."""
    x = x.astype(dtype)
    if dtype != F32:
        return (x,)
    hi = x.astype(BF16)
    return hi, (x - hi.astype(F32)).astype(BF16)


def _dot(a, b, dims):
    """The product of two operands in pieces, summed in float32: one
    pass, or ``hi·lo + lo·hi + hi·hi``."""

    def mxu(x, y):
        return jax.lax.dot_general(x, y, dims, preferred_element_type=F32)

    if len(a) == 1:
        return mxu(a[0], b[0])
    return (mxu(a[0], b[1]) + mxu(a[1], b[0])) + mxu(a[0], b[0])


def _head(refs, h, n_heads, chunk, lower):
    """What a visit makes of value head ``h`` before it meets the state:
    (γ a column [C, 1], e^γ and β a row [1, C], T [C, C] — the head's
    lanes of the key head's [C, R C] —, the decay block [C, C])
    float32."""
    t_ref, col_ref, row_ref = refs
    gcol = col_ref[0, 0, 0][:, h:h + 1]
    rows = row_ref[0, 0, 0]
    grow = rows[h:h + 1, :]
    brow = rows[n_heads + h:n_heads + h + 1, :]
    decay = jnp.exp(jax.lax.select(
        lower, jnp.broadcast_to(gcol, (chunk, chunk)) - grow,
        jnp.full((chunk, chunk), -jnp.inf, F32),
    ))
    t = t_ref[0, 0, 0, :, h * chunk:(h + 1) * chunk]
    return gcol, jnp.exp(grow), brow, t, decay


def _lower(chunk):
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return rows >= lanes


def _fwd_kernel(
    *refs,
    # q_ref (not with ``starts``), k_ref [1, C, Dk]; v_ref [1, C, R*Dv];
    # t_ref [1, 1, 1, C, R*C] f32: head h the lanes [C h, C h + C);
    # col_ref [1, 1, 1, C, R] f32: γ;
    # row_ref [1, 1, 1, 2R, C] f32: γ, then β;
    # out_ref: o [1, C, R*Dv], or with ``starts`` the state the chunk
    # starts from [1, 1, 1, R, Dk, Dv] f32; s_scr [R, Dk, Dv] f32
    starts,
):
    if starts:
        k_ref, v_ref, *small, out_ref, s_scr = refs
    else:
        q_ref, k_ref, v_ref, *small, out_ref, s_scr = refs
    chunk = k_ref.shape[1]
    dtype = v_ref.dtype
    n_heads, _, dv = s_scr.shape
    op = functools.partial(_pieces, dtype=dtype)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    lower = _lower(chunk)
    k = op(k_ref[0])
    if not starts:
        q = op(q_ref[0])
        qk = _dot(q, k, _NT)
    for h in range(n_heads):
        lanes = slice(h * dv, (h + 1) * dv)
        gcol, erow, cu, t, decay = _head(small, h, n_heads, chunk, lower)
        cw = cu * erow  # β e^γ, and β: the scales of T's columns
        state = s_scr[h]
        if starts:
            out_ref[0, 0, 0, h] = state
        s_op = op(state)
        w = _dot(op(t * cw), k, _NN).astype(dtype)
        u = _dot(op(t * cu), op(v_ref[0, :, lanes]), _NN).astype(dtype)
        fresh = u.astype(F32) - _dot(op(w), s_op, _NN)  # V' [C, Dv]
        last = gcol[chunk - 1:chunk, :]
        if not starts:
            out_ref[0, :, lanes] = (
                jnp.exp(gcol) * _dot(q, s_op, _NN)
                + _dot(op(qk * decay), op(fresh), _NN)
            ).astype(dtype)
        # (a [1, 1] goes over the lanes first: Mosaic spreads over one
        # axis at a time)
        s_scr[h] = jnp.exp(jnp.broadcast_to(last, (1, dv))) * state + _dot(
            k, op(fresh * jnp.exp(last - gcol)), _TN
        )


def _bwd_kernel(
    q_ref, k_ref,  # [1, C, Dk]
    v_ref, do_ref,  # [1, C, R*Dv]
    t_ref, col_ref, row_ref,  # as the forward's
    start_ref,  # [1, 1, 1, R, Dk, Dv] f32: the state the chunk started from
    dq_ref, dk_ref,  # [1, C, Dk]
    dv_ref,  # [1, C, R*Dv]
    dt_ref,  # [1, 1, 1, C, R*C] f32: as T
    dcol_ref,  # [1, 1, 1, C, R] f32: dγ, what sums to a column
    drow_ref,  # [1, 1, 1, 2R, C] f32: dγ, what sums to a row, then dβ
    ds_scr,  # [R, Dk, Dv] f32: d state at the chunk's end
):
    chunk = k_ref.shape[1]
    dtype = v_ref.dtype
    n_heads, _, dv = ds_scr.shape
    op = functools.partial(_pieces, dtype=dtype)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    lower = _lower(chunk)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, n_heads), 1)
    last_token = jax.lax.broadcasted_iota(
        jnp.int32, (chunk, 1), 0
    ) == chunk - 1
    q, k = op(q_ref[0]), op(k_ref[0])
    qk = _dot(q, k, _NT)
    dq = jnp.zeros(q_ref.shape[1:], F32)
    dk = jnp.zeros(k_ref.shape[1:], F32)
    dqk = jnp.zeros((chunk, chunk), F32)
    dcol = jnp.zeros((chunk, n_heads), F32)
    for h in range(n_heads):
        lanes = slice(h * dv, (h + 1) * dv)
        gcol, erow, cu, t, decay = _head(
            (t_ref, col_ref, row_ref), h, n_heads, chunk, lower
        )
        cw = cu * erow  # β e^γ, and β: the scales of T's columns
        state, d_end = start_ref[0, 0, 0, h], ds_scr[h]
        s_op, d_end_op = op(state), op(d_end)
        # the chunk's operands again
        tw, tu, v = op(t * cw), op(t * cu), op(v_ref[0, :, lanes])
        w = op(_dot(tw, k, _NN))
        u = _dot(tu, v, _NN).astype(dtype).astype(F32)
        fresh = u - _dot(w, s_op, _NN)  # V'
        attn = qk * decay
        last = gcol[chunk - 1:chunk, :]
        grow, to_end, keep = (
            jnp.exp(gcol), jnp.exp(last - gcol), jnp.exp(last)
        )
        kept = fresh * to_end  # b ⊙ V'
        d_out = do_ref[0, :, lanes].astype(F32)
        # back through the update, the read-out and V'
        d_kept = _dot(k, d_end_op, _NN)  # K dS' [C, Dv]
        dk = dk + _dot(op(kept), d_end_op, _NT)
        d_out_op = op(d_out)
        d_fresh = _dot(op(attn), d_out_op, _TN) + to_end * d_kept
        d_fresh_op = op(d_fresh)
        d_attn = _dot(d_out_op, op(fresh), _NT)  # [C, C]
        d_read = op(grow * d_out)
        dq = dq + _dot(d_read, s_op, _NT)
        d_w = op(-_dot(d_fresh_op, s_op, _NT))  # [C, Dk]
        ds_scr[h] = (
            jnp.exp(jnp.broadcast_to(last, (1, dv))) * d_end
            + _dot(q, d_read, _TN)
            - _dot(w, d_fresh_op, _TN)
        )
        # back through W, U and the block of scores
        d_tw = _dot(d_w, k, _NT)  # [C, C]
        d_tu = _dot(d_fresh_op, v, _NT)
        dk = dk + _dot(tw, d_w, _TN)
        dv_ref[0, :, lanes] = _dot(tu, d_fresh_op, _TN).astype(dtype)
        dt_ref[0, 0, 0, :, h * chunk:(h + 1) * chunk] = d_tw * cw + d_tu * cu
        dqk = dqk + d_attn * decay
        # the scales' cotangents: columns of T (rows a token), the two
        # decays a token (columns), the decay block (both)
        d_cw = jnp.sum(d_tw * t, axis=0, keepdims=True)  # [1, C]
        d_cu = jnp.sum(d_tu * t, axis=0, keepdims=True)
        moved = d_attn * attn  # dM ⊙ M
        handed = jnp.sum(d_kept * kept, axis=1, keepdims=True)  # [C, 1]
        d_last = jnp.sum(handed, axis=0, keepdims=True) + keep * jnp.sum(
            jnp.sum(d_end * state, axis=1, keepdims=True), axis=0,
            keepdims=True,
        )
        d_gcol = (
            grow * jnp.sum(
                d_out * _dot(q, s_op, _NN), axis=1, keepdims=True
            ) - handed + jnp.sum(moved, axis=1, keepdims=True)
            + jax.lax.select(
                last_token, jnp.broadcast_to(d_last, (chunk, 1)),
                jnp.zeros((chunk, 1), F32),
            )
        )
        dcol = jax.lax.select(
            head_lane == h, jnp.broadcast_to(d_gcol, dcol.shape), dcol
        )
        drow_ref[0, 0, 0, h:h + 1, :] = d_cw * cw - jnp.sum(
            moved, axis=0, keepdims=True
        )
        drow_ref[0, 0, 0, n_heads + h:n_heads + h + 1, :] = (
            d_cw * erow + d_cu
        )
    dqk_op = op(dqk)
    dq_ref[0] = (dq + _dot(dqk_op, k, _NN)).astype(dq_ref.dtype)
    dk_ref[0] = (dk + _dot(dqk_op, q, _TN)).astype(dk_ref.dtype)
    dcol_ref[0, 0, 0] = dcol


def _params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT,
    )


def _specs(chunk, dk, dv, n_heads, n_chunks, reverse):
    """The block specs of a chunk's operands on the grid (batch, key
    head, step): the chunk is the step, or the last minus it going
    back."""

    def at(i):
        return n_chunks - 1 - i if reverse else i

    def by_chunk(*block):
        zeros = (0,) * len(block)
        return pl.BlockSpec(
            (1, 1, 1) + block, lambda b, h, i: (b, at(i), h) + zeros
        )

    return dict(
        key=pl.BlockSpec((1, chunk, dk), lambda b, h, i: (b, at(i), h)),
        value=pl.BlockSpec(
            (1, chunk, n_heads * dv), lambda b, h, i: (b, at(i), h)
        ),
        t=by_chunk(chunk, n_heads * chunk),
        col=by_chunk(chunk, n_heads),
        row=by_chunk(2 * n_heads, chunk),
        state=by_chunk(n_heads, dk, dv),
    )


def _sizes(t):
    """(batch, key heads, value heads a key head, chunks, the chunk) of
    T [B, N, Hk, C, R*C]."""
    bsz, n_chunks, hk, chunk, wide = t.shape
    return bsz, hk, wide // chunk, n_chunks, chunk


@functools.partial(_traced_once, static=("dk", "dv", "interpret", "starts"))
def _forward(q, k, v, t, col, row, *, dk, dv, interpret, starts=False):
    """o [B, S, Hv*Dv] — or, with ``starts``, the state each chunk starts
    from, [B, N, Hk, R, Dk, Dv] float32 — of q, k [B, S, Hk*Dk], v
    [B, S, Hv*Dv], t [B, N, Hk, C, R*C], col [B, N, Hk, C, R] and row
    [B, N, Hk, 2R, C] float32."""
    bsz, hk, n_heads, n_chunks, chunk = _sizes(t)
    spec = _specs(chunk, dk, dv, n_heads, n_chunks, False)
    out_shape = (bsz, n_chunks, hk, n_heads, dk, dv) if starts else v.shape
    operands = (k, v, t, col, row) if starts else (q, k, v, t, col, row)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, starts=starts),
        grid=(bsz, hk, n_chunks),
        in_specs=[spec["key"]] * (1 if starts else 2) + [
            spec["value"], spec["t"], spec["col"], spec["row"]
        ],
        out_specs=spec["state" if starts else "value"],
        out_shape=pallas_attention._out_struct(
            out_shape, F32 if starts else v.dtype, v
        ),
        scratch_shapes=[pltpu.VMEM((n_heads, dk, dv), F32)],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="gdn_states" if starts else "gdn_fwd",
    )(*operands)


@functools.partial(_traced_once, static=("dk", "dv", "interpret"))
def _backward(q, k, v, t, col, row, do, *, dk, dv, interpret):
    """(dq, dk, dv, dt, d col, d row) from the forward's operands and o's
    cotangent. The state each chunk started from comes from a pass of
    the forward kernel that makes nothing else, held behind ``do``:
    without the barrier the compiler may run that pass as soon as its
    operands exist, and the states (537 MB a layer at Qwen3-Next's
    widths) would be alive long before the walk back reads them."""
    q, k, v, t, col, row, do = jax.lax.optimization_barrier(
        (q, k, v, t, col, row, do)
    )
    starts = _forward(
        q, k, v, t, col, row, dk=dk, dv=dv, interpret=interpret, starts=True
    )
    bsz, hk, n_heads, n_chunks, chunk = _sizes(t)
    spec = _specs(chunk, dk, dv, n_heads, n_chunks, True)
    like = pallas_attention._out_struct
    return pl.pallas_call(
        _bwd_kernel,
        grid=(bsz, hk, n_chunks),
        in_specs=[spec["key"], spec["key"], spec["value"], spec["value"],
                  spec["t"], spec["col"], spec["row"], spec["state"]],
        out_specs=[spec["key"], spec["key"], spec["value"], spec["t"],
                   spec["col"], spec["row"]],
        out_shape=[
            like(q.shape, q.dtype, v), like(k.shape, k.dtype, v),
            like(v.shape, v.dtype, v), like(t.shape, F32, v),
            like(col.shape, F32, v), like(row.shape, F32, v),
        ],
        scratch_shapes=[pltpu.VMEM((n_heads, dk, dv), F32)],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="gdn_bwd",
    )(q, k, v, do, t, col, row, starts)


# rows of matrices a grid step of the inverse takes: the lanes of a tile
INVERSE_ROWS = 128
# rows the substitution takes one at a time: ``gated_delta._BASE``
_BASE = 16


def _inverse_kernel(*refs, gated):
    # a_ref, t_ref [M * C, W] f32: row m of the batch is matrix rows
    # [C m, C m + C), its P = W / C matrices side by side on the lanes;
    # x_scr, t_scr [C, W, M] f32: A and T with the batch on the lanes,
    # [row, (p, column), m]; p_scr [C / 2, C / 2, M] f32: a merge's
    # A21 T11. ``gated``: A is made here — kk_ref [M * C, C] f32, the
    # row's K K^T, and g_ref [M, 2 P C] f32, γ a row a token of each of
    # its P value heads, then β the same way (g_scr [2 P C, M]: the
    # batch on the lanes)
    if gated:
        kk_ref, g_ref, t_ref, x_scr, t_scr, p_scr, g_scr = refs
    else:
        a_ref, t_ref, x_scr, t_scr, p_scr = refs
    c, wide, batch = x_scr.shape
    heads = wide // c
    loop = jax.lax.fori_loop

    def to_lanes(i, carry):
        x_scr[i] = a_ref[pl.ds(i, batch, stride=c), :].T
        return carry

    def make(i, carry):
        # row i of A = strict_lower(β_i (k_i · k_j) e^{γ_i − γ_j}) of
        # every head: the difference first, then the exponential
        kk = kk_ref[pl.ds(i, batch, stride=c), :].T  # [C, M]: column j
        under = jax.lax.broadcasted_iota(jnp.int32, kk.shape, 0) < i
        for h in range(heads):
            gamma = g_scr[h * c:(h + 1) * c, :]
            decay = jnp.exp(jax.lax.select(
                under, g_scr[pl.ds(h * c + i, 1), :] - gamma,
                jnp.full(kk.shape, -jnp.inf, F32),
            ))
            x_scr[i, h * c:(h + 1) * c, :] = jax.lax.select(
                under,
                g_scr[pl.ds((heads + h) * c + i, 1), :] * kk * decay,
                jnp.zeros(kk.shape, F32),
            )
        return carry

    if gated:
        g_scr[...] = g_ref[...].T
    loop(0, c, make if gated else to_lanes, 0)
    base = min(c, _BASE)
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (base, base, batch), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (base, base, batch), 1)
    ).astype(F32)

    def substitute(block, carry):
        # diagonal block ``block`` of (p, d), rows one at a time: row l
        # is whole once the rows before it have been taken off it, and
        # is then taken off every row below, ``a_il`` times: T_i = e_i −
        # Σ_{l<i} a_il T_l, a row of T the block's columns on the
        # sublanes
        first = pl.multiple_of((block % (c // base)) * base, base)
        at = pl.multiple_of((block // (c // base)) * c + first, base)
        cols = pl.ds(at, base)
        t_scr[pl.ds(first, base), cols, :] = eye
        for l in range(base - 1):
            below = pl.ds(first + l + 1, base - 1 - l)
            t_scr[below, cols, :] = t_scr[below, cols, :] - x_scr[
                below, pl.ds(at + l, 1), :
            ] * t_scr[pl.ds(first + l, 1), cols, :]
        return carry

    loop(0, (wide // c) * (c // base), substitute, 0)

    def product(size, low, left, column, right, store):
        # rows [low, low + size) of ``left`` times ``right``, eight at a
        # time: Σ_l left[row, column + l] right(l) over ``size``, an
        # entry of the left factor spread over the sublanes of the right
        # factor's row l; ``store(first of the eight, [8, size, M])``
        def eight(i, carry):
            i = pl.multiple_of(i * 8, 8)
            acc = jnp.zeros((8, size, batch), F32)
            for l in range(size):
                acc = acc + left[
                    pl.ds(low + i, 8), pl.ds(column + l, 1), :
                ] * right(l)
            store(i, acc)
            return carry

        loop(0, size // 8, eight, 0)

    def merge(size, index):
        # [[T11, 0], [T21, T22]], T21 = −T22 A21 T11, of neighbour
        # ``index`` of (p, pair): T22's first row ``low``, T11's first
        # column ``at``
        pairs = c // (2 * size)
        low = pl.multiple_of((index % pairs) * 2 * size + size, size)
        at = pl.multiple_of((index // pairs) * c + low - size, size)
        left, right = pl.ds(at, size), pl.ds(at + size, size)
        t_scr[pl.ds(low - size, size), right, :] = jnp.zeros(
            (size, size, batch), F32
        )

        def keep(i, acc):
            p_scr[pl.ds(i, 8), :size, :] = acc

        def place(i, acc):
            t_scr[pl.ds(low + i, 8), left, :] = -acc

        product(
            size, low, x_scr, at,
            lambda l: t_scr[pl.ds(low - size + l, 1), left, :], keep,
        )
        product(
            size, low, t_scr, at + size,
            lambda l: p_scr[pl.ds(l, 1), :size, :], place,
        )

    size = base
    while size < c:
        loop(
            0, (wide // c) * (c // (2 * size)),
            lambda index, carry, size=size: merge(size, index), None,
        )
        size *= 2

    def from_lanes(i, carry):
        t_ref[pl.ds(i, batch, stride=c), :] = t_scr[i].T
        return carry

    loop(0, c, from_lanes, 0)


@functools.partial(_traced_once, static=("interpret",))
def _inverse(a, gates, *, interpret):
    """``(I + A)^{-1}`` [M, C, W] float32, M a multiple of
    ``INVERSE_ROWS``: of ``a`` [M, C, W] itself (``gates`` None), or of
    the A that ``a`` [M, C, C] = K K^T and ``gates`` [M, 2 P C] make
    (W = P C): see ``inverse`` and ``gated_inverse``."""
    rows, c, _ = a.shape
    gated = gates is not None
    wide = gates.shape[1] // 2 if gated else a.shape[2]
    batch = INVERSE_ROWS

    def block(width):
        return pl.BlockSpec((batch * c, width), lambda i: (i, 0))

    scratch = pltpu.VMEM((c, wide, batch), F32)
    operands = [a.reshape(rows * c, a.shape[2])]
    in_specs = [block(a.shape[2])]
    scratch_shapes = [
        scratch, scratch, pltpu.VMEM((c // 2, c // 2, batch), F32)
    ]
    if gated:
        operands.append(gates)
        in_specs.append(pl.BlockSpec((batch, 2 * wide), lambda i: (i, 0)))
        scratch_shapes.append(pltpu.VMEM((2 * wide, batch), F32))
    return pl.pallas_call(
        functools.partial(_inverse_kernel, gated=gated),
        grid=(rows // batch,),
        in_specs=in_specs,
        out_specs=block(wide),
        out_shape=pallas_attention._out_struct((rows * c, wide), F32, a),
        scratch_shapes=scratch_shapes,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT
        ),
        interpret=interpret,
        name="tri_inverse",
    )(*operands).reshape(rows, c, wide)


def _whole_steps(a, gates=None):
    """``_inverse`` of a batch [..., C, W] of any size: flattened, and
    padded with zero rows to whole grid steps (I + 0 inverts to I)."""
    lead = a.shape[:-2]
    a = a.reshape((-1,) + a.shape[-2:])
    pad = -a.shape[0] % INVERSE_ROWS
    if gates is not None:
        gates = jnp.pad(
            gates.reshape(a.shape[0], -1), ((0, pad), (0, 0))
        )
    t = _inverse(
        jnp.pad(a, ((0, pad), (0, 0), (0, 0))), gates,
        interpret=pallas_attention.INTERPRET,
    )
    return t[:t.shape[0] - pad].reshape(lead + t.shape[-2:])


def inverse(a):
    """``(I + a)^{-1}`` of every strictly lower [C, C] matrix of a
    [..., C, P C] float32, P of them side by side on the last axis
    (matrix p is columns ``[C p, C p + C)``; P = 1: one a row), C =
    ``CHUNK``: T in the same form. ``gated_delta._inverse_of``'s
    algorithm — rows one at a time inside diagonal blocks of 16, blocks
    merged two by two, ``T21 = −T22 A21 T11``, float32 multiply-adds
    with the batch on the lanes — as ONE kernel parallel over the batch:
    a grid step takes ``INVERSE_ROWS`` rows of the batch, turns each
    matrix row's [rows, P C] slab to [P C, rows] (a strided load and a
    transpose a matrix row), works in VMEM, and turns T back the same
    way. HBM sees A once and T once, both as wide as the caller has
    them: with P C = 128 nothing is padded to the lanes of a tile."""
    return _whole_steps(a)


def gated_inverse(kk, rows):
    """T [..., C, R C] of the gated rule's chunks, a key head's R value
    heads side by side on the last axis, from kk [..., C, C] = ``K Kᵀ``
    and rows [..., 2 R, C] (the running log-decay γ a row a token of each
    head, then β the same way; all float32): ``inverse`` of ``A =
    strict_lower(β_i kk_ij e^{γ_i − γ_j})``, which the kernel makes row
    by row as it turns ``kk`` to the lanes — A is never in HBM."""
    return _whole_steps(kk, rows)


def forward(q, k, v, t, col, row, dk, dv):
    """o [B, S, Hv*Dv] of whole chunks: see ``_forward``."""
    return _forward(
        q, k, v, t, col, row, dk=dk, dv=dv,
        interpret=pallas_attention.INTERPRET,
    )


def backward(q, k, v, t, col, row, do, dk, dv):
    """(dq, dk, dv, dt, d col, d row): see ``_backward``."""
    return _backward(
        q, k, v, t, col, row, do, dk=dk, dv=dv,
        interpret=pallas_attention.INTERPRET,
    )
