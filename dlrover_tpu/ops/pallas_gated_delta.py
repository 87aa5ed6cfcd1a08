"""The gated delta rule's walk from chunk to chunk (``ops/gated_delta.py``)
as Pallas TPU kernels: the forward, the backward, and between them the
forward's state pass.

All three walk a sequence chunk by chunk (chunks of ``CHUNK`` = 64
tokens) on the grid ``(batch, key head, chunk)``, the last axis
sequential, with the key head's state — its R value heads', ``[R, Dk,
Dv]`` float32 — in VMEM scratch from the first chunk to the last. A
grid step holds one chunk of one key head: q and k as that head's
columns of the caller's ``[B, S, Hk * Dk]`` arrays (one array a KEY
head: its R value heads are handled in the one visit), v and o as the R
heads' columns of ``[B, S, Hv * Dv]``, and what XLA makes of whole
chunks before the walk (``gated_delta._chunk_inverse``): the triangular
inverse ``T = (I + A)^{-1}`` ``[R, C, C]`` float32, the running
log-decay γ a column a token (``[C, R]``) and a row a token, with β a
row a token beside it (``[2 R, C]``). Everything else of a chunk is made
in the visit and never written: the decay block ``exp(γ_i − γ_j)`` (the
difference first), ``Q Kᵀ``, ``W = (T ⊙ β e^γ) K``, ``U = (T ⊙ β) V``,
``V' = U − W S``, the read-out ``e^γ ⊙ (Q S) + (Q Kᵀ ⊙ decay) V'`` and
the update ``S ← e^{γ_C} S + Kᵀ (e^{γ_C − γ} ⊙ V')``.

Why T comes from outside. The inverse is by substitution (backward
stable where a chunk's keys repeat), rows one at a time inside blocks of
16: on the vector unit that is work over the BATCH of chunks, which XLA
lays on the lanes (8,192 chunks and heads a layer: a thousandth of the
rule's work, and not a step of the 256-step dependence), and inside a
visit it would be 16 dependent sublane steps of a ``[16, 16]`` block.
So ``A``, ``T`` and T's hand-written derivative (``−strict_lower(Tᵀ dT
Tᵀ)``) stay XLA's, parallel over the chunks, and the kernels take ``T``
and hand back ``dT``.

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
    (γ a column [C, 1], e^γ and β a row [1, C], T [C, C], the decay
    block [C, C]) float32."""
    t_ref, col_ref, row_ref = refs
    gcol = col_ref[0, 0, 0][:, h:h + 1]
    rows = row_ref[0, 0, 0]
    grow = rows[h:h + 1, :]
    brow = rows[n_heads + h:n_heads + h + 1, :]
    decay = jnp.exp(jax.lax.select(
        lower, jnp.broadcast_to(gcol, (chunk, chunk)) - grow,
        jnp.full((chunk, chunk), -jnp.inf, F32),
    ))
    return gcol, jnp.exp(grow), brow, t_ref[0, 0, 0, h], decay


def _lower(chunk):
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return rows >= lanes


def _fwd_kernel(
    *refs,
    # q_ref (not with ``starts``), k_ref [1, C, Dk]; v_ref [1, C, R*Dv];
    # t_ref [1, 1, 1, R, C, C] f32; col_ref [1, 1, 1, C, R] f32: γ;
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
    dt_ref,  # [1, 1, 1, R, C, C] f32
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
        dt_ref[0, 0, 0, h] = d_tw * cw + d_tu * cu
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
        t=by_chunk(n_heads, chunk, chunk),
        col=by_chunk(chunk, n_heads),
        row=by_chunk(2 * n_heads, chunk),
        state=by_chunk(n_heads, dk, dv),
    )


def _sizes(t):
    """(batch, key heads, value heads a key head, chunks, the chunk) of
    T [B, N, Hk, R, C, C]."""
    bsz, n_chunks, hk, n_heads, chunk, _ = t.shape
    return bsz, hk, n_heads, n_chunks, chunk


@functools.partial(_traced_once, static=("dk", "dv", "interpret", "starts"))
def _forward(q, k, v, t, col, row, *, dk, dv, interpret, starts=False):
    """o [B, S, Hv*Dv] — or, with ``starts``, the state each chunk starts
    from, [B, N, Hk, R, Dk, Dv] float32 — of q, k [B, S, Hk*Dk], v
    [B, S, Hv*Dv], t [B, N, Hk, R, C, C], col [B, N, Hk, C, R] and row
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
