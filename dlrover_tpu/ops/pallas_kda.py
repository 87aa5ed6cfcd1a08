"""The delta rule with a decay a key CHANNEL (KDA; ``ops/gated_delta.py``,
``g`` [B, S, H, Dk]) as Pallas TPU kernels: the chunk's decayed pairs,
their pull-back, and the walk from chunk to chunk — forward, the
forward's state pass, backward.

Five kernels on the grid ``(batch, head, chunks)``, ONE value head a
visit (the decayed keys are a value head's own; the caller repeats shared
keys), ``VISIT`` chunks of 64 tokens a grid step, one after another in a
loop inside it (a grid step's fixed cost is 6% of the rule at one chunk
a step: CHANGES.md, PR 66, has the sweep). q, k, v and g are that
head's 128 columns of the caller's ``[B, S, H * D]`` arrays (g float32,
as wide as k), β a row a chunk (``[B, N, H, 1, C]`` float32). Every
visit makes the running log-decay γ of its chunk itself: the product of
a lower triangle of ones with g in THREE bf16 pieces (``_split3``: 24
bits of mantissa, the pieces' products exact, summed in float32), so γ
is float32's and no cumulative sum is XLA's.

The pairs (``kda_pairs``, chunks in parallel). ``kk_ij = Σ_d k_id k_jd
e^{γ_id − γ_jd}`` and ``M_ij = Σ_d q_id k_jd e^{γ_id − γ_jd}`` on and
under the diagonal, in sub-blocks of 16 tokens and the XLA body's
arithmetic (``gated_delta._channel_pairs``): a diagonal sub-block from
explicit differences, one ``[16, 128]`` tile a column token b —
``e^{γ_a − γ_b}`` under the mask ``a >= b``, the difference BEFORE the
exponential, float32 multiplies — and the block row left of sub-block I
as ONE product of ``[K_I; Q_I] ⊙ e^{γ − r_I}`` by ``K ⊙ e^{r_I − γ}``
over the earlier tokens, r_I the running sum at I's first token, both
exponents <= 0. No ``[C, C, Dk]`` array exists, in VMEM either. It
writes ``A = strict_lower(β_i kk_ij)`` and M, float32
``[B, N, H, C, C]`` (134 MB each at 16,384 tokens and 32 heads; 268
where (8, 128) tiles pad the 64 to 128 lanes).

Where the sums over the 128 channels run: on the vector unit's lanes
(``jnp.sum(.., axis=1)`` of a ``[16, 128]`` tile), a column of the block
a token b, set into its lane by a select: 8.3 ms a layer's pairs on a
v5e where the other way — the sixteen tiles of a sub-block stacked
``[256, 128]`` and multiplied by a matrix of ones on the matrix unit in
three bf16 pieces — read 13.5 (my chip runs, PR 66: the stacked tiles
cross VMEM and are split into pieces on the same vector slots the lane
sums use).

Between the pairs and the walk ``T = (I + A)^{-1}`` of whole chunks is
made and nothing else: since PR 71 by the kernel ``tri_inverse``
(``pallas_gated_delta.inverse``, at one matrix a row of its batch;
``pallas_gated_delta``'s docstring says why the substitution is not a
visit's work), XLA keeping the hand derivative that takes dT to dA; β
goes on T's COLUMNS in the visit: ``W = (T ⊙ β) (K ⊙ e^γ)``,
``U = (T ⊙ β) V``. A, M, T, dT, dM and dA stay ``[B, N, H, C, C]``
between these kernels: two neighbouring chunks of a head side by side
on the 128 lanes (``[B, N / 2, H, C, 2 C]``, nothing padded) were built
and measured (PR 71, one layer's rule on a v5e): a visit's chunks are a
rolled loop, so a chunk's half of a row is read through a select and
written by reading the row, selecting and storing it whole, and
``kda_pairs`` read 6.20 ms a call for 5.43, ``kda_pairs_bwd`` 10.86 for
9.33, ``kda_bwd`` 8.22 for 7.93 — more than the halved arrays gave back.

The walk (``kda_fwd``, ``kda_states``, ``kda_bwd``), the chunk axis
sequential, the head's state in VMEM scratch from the first chunk to the
last, float32 and TRANSPOSED, ``[Dv, Dk]``: the decay ``e^{γ_C}`` is one
a key channel, a row over the lanes that way, and no visit moves a row
to a column. A visit makes ``K ⊙ e^γ``, ``Q ⊙ e^γ``, ``K ⊙ e^{γ_C − γ}``,
W, U, ``V' = U − W S``, the read-out ``(Q ⊙ e^γ) S + M V'`` and the
update ``S ← Diag(e^{γ_C}) S + (K ⊙ e^{γ_C − γ})ᵀ V'`` and writes o
alone; ``kda_states`` is the same body writing only the state each chunk
STARTS from (``[B, N, H, Dv, Dk]`` float32, 537 MB a layer at 16,384
tokens and 32 heads), made by the backward rule and not kept.

Backward (``kda_bwd``), the chunks last to first with the state's
cotangent in scratch. With ``a = e^γ``, ``b = e^{γ_C − γ}``, ``λ =
e^{γ_C}``, ``Tβ = T ⊙ β``, everything of the chunk remade in the visit:

    dV' = Mᵀ dO + (K ⊙ b) dS'            dM  = dO V'ᵀ
    dQa = dO Sᵀ        dQ = a ⊙ dQa      dγ += dQa ⊙ Q ⊙ a
    dKb = V' dS'ᵀ      dK += b ⊙ dKb     dγ −= dKb ⊙ K ⊙ b
    dγ_C += Σ_i dKb ⊙ K ⊙ b + λ ⊙ Σ_e dS' ⊙ S
    dW  = −dV' Sᵀ      dTβ = dW (K ⊙ a)ᵀ + dV' Vᵀ
    dKa = Tβᵀ dW       dK += a ⊙ dKa     dγ += dKa ⊙ K ⊙ a
    dV  = Tβᵀ dV'      dS  = λ ⊙ dS' + (Q ⊙ a)ᵀ dO − Wᵀ dV'
    dT  = dTβ ⊙ β      dβ_j = Σ_i dTβ_ij T_ij

It writes dq, dk, dγ, dβ (its part of each), dv, dT and dM. XLA takes dT
through the inverse's hand derivative to dA, and ``kda_pairs_bwd``
(chunks in parallel) takes dA and dM back through the pairs, sub-block
by sub-block as they were made, with ``d kk_ij = β_i dA_ij``,

    dQ_i += Σ_j dM_ij k_j E_ij      dK_i += Σ_j dkk_ij k_j E_ij   (rows)
    dK_j += Σ_i (dkk_ij k_i + dM_ij q_i) E_ij                  (columns)
    dγ   += k ⊙ dK(rows) + q ⊙ dQ − k ⊙ dK(columns)
    dβ_i += Σ_j dA_ij kk_ij = Σ_d k_id (Σ_j dA_ij k_jd E_ijd)

(γ's cotangent of a decayed sum is the cotangents' products with the
operands themselves; the references r_I cancel; β's is the rows' sum
BEFORE β scales it, so kk is not made again), adds the walk's parts and
turns dγ into dg by the upper triangle of ones, again in three pieces:
the rule's last kernel writes dq, dk, dg and dβ whole, into the walk's
buffers.

Precision is the XLA body's: g, β, γ, every difference, every decay, A,
M, T and the state float32; products on operands of the compute dtype
summed in float32, three passes of bf16 pieces on float32 operands
(``pallas_gated_delta._pieces`` / ``_dot``).

What a kernel costs before it runs (``pallas_ssd``'s docstring): the
bodies go through ``_traced_once``, and the chunks of a visit are a
rolled loop, one body whatever ``VISIT``.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from dlrover_tpu.ops import pallas_attention
from dlrover_tpu.ops.pallas_gated_delta import (
    _NN, _NT, _TN, BF16, CHUNK, F32, VMEM_LIMIT, _dot, _lower, _params,
    _pieces, pltpu,
)
from dlrover_tpu.ops.pallas_ssd import _traced_once

# tokens a sub-block of the pairs holds: ``gated_delta._BASE``
SUB = 16


def _split3(x):
    """A float32 ``x`` as three bf16 pieces whose sum is x to float32's
    last bit, smallest first."""
    hi = x.astype(BF16)
    rest = x - hi.astype(F32)
    mid = rest.astype(BF16)
    return (rest - mid.astype(F32)).astype(BF16), mid, hi


def _by_ones(ones, x, dims):
    """``ones`` (a 0/1 matrix, bf16: exact) times a float32 ``x`` at
    float32's accuracy: three passes, the small pieces summed first."""
    lo, mid, hi = (
        jax.lax.dot_general(ones, p, dims, preferred_element_type=F32)
        for p in _split3(x)
    )
    return (lo + mid) + hi


def _ones(mask):
    # (a mask chooses among 32-bit lanes: rounded after)
    return jax.lax.select(
        mask, jnp.ones(mask.shape, F32), jnp.zeros(mask.shape, F32)
    ).astype(BF16)


def _running(g, lower):
    """γ [C, Dk]: the running sum of g down a chunk's tokens."""
    return _by_ones(_ones(lower), g, _NN)


def _turned(x, eye):
    """A row [1, C] as a column [C, 1] or a column as a row, exactly:
    the diagonal of its spread, summed along the other axis."""
    axis = 1 if x.shape[0] == 1 else 0
    spread = jax.lax.select(
        eye, jnp.broadcast_to(x, eye.shape), jnp.zeros(eye.shape, F32)
    )
    return jnp.sum(spread, axis=axis, keepdims=True)


def _eye(chunk):
    """(the diagonal, what lies strictly under it) of a [C, C] block."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return rows == lanes, rows > lanes


def _or_never(mask, x):
    """An exponent: x where ``mask``, −inf (a decay of 0) elsewhere."""
    return jax.lax.select(mask, x, jnp.full(x.shape, -jnp.inf, x.dtype))


class _Blocks:
    """What the pairs and their pull-back both make of a chunk, a
    sub-block of ``SUB`` tokens at a time: q, k float32 [C, Dk] (the
    compute dtype's values), gamma float32 [C, Dk]."""

    def __init__(self, q, k, gamma, dtype):
        self.q, self.k, self.gamma = q, k, gamma
        self.chunk, self.dk = k.shape
        self.op = functools.partial(_pieces, dtype=dtype)
        self.rows = jax.lax.broadcasted_iota(jnp.int32, (SUB, self.dk), 0)
        self.token = jax.lax.broadcasted_iota(
            jnp.int32, (self.chunk, self.dk), 0
        )
        self.lanes = jax.lax.broadcasted_iota(
            jnp.int32, (SUB, self.chunk), 1
        )

    def sub(self, i):
        at = slice(i * SUB, (i + 1) * SUB)
        return self.q[at], self.k[at], self.gamma[at]

    def decay(self, gb, b):
        """``e^{γ_a − γ_b}`` [SUB, Dk] for the sub-block's tokens a >= b
        and 0 for those before: the difference first."""
        return jnp.exp(_or_never(self.rows >= b, gb - gb[b:b + 1]))

    def across(self, i, gb):
        """Block row i's two decays about its first token's running sum:
        (``e^{γ_a − r}`` [SUB, Dk], ``e^{r − γ_j}`` [C, Dk] for the
        tokens j before the sub-block and 0 from it on)."""
        ref = gb[0:1]
        return jnp.exp(gb - ref), jnp.exp(
            _or_never(self.token < i * SUB, ref - self.gamma)
        )


def _pairs(q, k, gamma, dtype):
    """(kk, M) float32 [C, C] of a chunk: see the module's docstring."""
    blk = _Blocks(q, k, gamma, dtype)
    kk_rows, qk_rows = [], []
    for i in range(blk.chunk // SUB):
        qb, kb, gb = blk.sub(i)
        kk = jnp.zeros((SUB, blk.chunk), F32)
        qk = jnp.zeros((SUB, blk.chunk), F32)
        for b in range(SUB):
            seen = kb[b:b + 1] * blk.decay(gb, b)
            here = blk.lanes == i * SUB + b
            kk = jax.lax.select(here, jnp.broadcast_to(
                jnp.sum(kb * seen, axis=1, keepdims=True), kk.shape
            ), kk)
            qk = jax.lax.select(here, jnp.broadcast_to(
                jnp.sum(qb * seen, axis=1, keepdims=True), qk.shape
            ), qk)
        if i:
            into, out_of = blk.across(i, gb)
            off = _dot(
                blk.op(jnp.concatenate([kb * into, qb * into], axis=0)),
                blk.op(k * out_of), _NT,
            )                                        # [2 SUB, C]
            kk, qk = kk + off[:SUB], qk + off[SUB:]
        kk_rows.append(kk)
        qk_rows.append(qk)
    return jnp.concatenate(kk_rows, axis=0), jnp.concatenate(qk_rows, axis=0)


def _pairs_pull(q, k, gamma, beta, da, dm, dtype):
    """(dq, dk, dγ float32 [C, Dk], dβ a column [C, 1]) of a chunk from
    the cotangents [C, C] of ``A = strict_lower(β_i kk)`` and M (what
    lies above the diagonal is ignored), beta a column [C, 1]."""
    blk = _Blocks(q, k, gamma, dtype)
    dq_rows, dk_rows, col_rows = [], [], []
    dk_cols = jnp.zeros(k.shape, F32)
    for i in range(blk.chunk // SUB):
        at = slice(i * SUB, (i + 1) * SUB)
        qb, kb, gb = blk.sub(i)
        # A's cotangent as kk's rows see it (β comes after); its columns
        # see β with the keys
        raw, dmb = da[at], dm[at]
        k_beta = beta[at] * kb
        dq = jnp.zeros((SUB, blk.dk), F32)
        dk_row = jnp.zeros((SUB, blk.dk), F32)
        dk_col = jnp.zeros((SUB, blk.dk), F32)
        for b in range(SUB):
            j = i * SUB + b
            decay = blk.decay(gb, b)
            seen = kb[b:b + 1] * decay
            ca, cm = raw[:, j:j + 1], dmb[:, j:j + 1]
            dq = dq + cm * seen
            dk_row = dk_row + ca * seen
            dk_col = jax.lax.select(blk.rows == b, jnp.broadcast_to(
                jnp.sum(
                    (ca * k_beta + cm * qb) * decay, axis=0, keepdims=True
                ), dk_col.shape,
            ), dk_col)
        if i:
            into, out_of = blk.across(i, gb)
            left = blk.op(jnp.concatenate([kb * into, qb * into], axis=0))
            d_left = _dot(
                blk.op(jnp.concatenate([raw, dmb], axis=0)),
                blk.op(k * out_of), _NN,
            )                                        # [2 SUB, Dk]
            dk_row = dk_row + into * d_left[:SUB]
            dq = dq + into * d_left[SUB:]
            dk_cols = dk_cols + out_of * _dot(
                blk.op(jnp.concatenate([beta[at] * raw, dmb], axis=0)),
                left, _TN,
            )
        dq_rows.append(dq)
        dk_rows.append(dk_row)
        col_rows.append(dk_col)
    dq = jnp.concatenate(dq_rows, axis=0)
    unscaled = jnp.concatenate(dk_rows, axis=0)
    dk_row = beta * unscaled
    dk_col = dk_cols + jnp.concatenate(col_rows, axis=0)
    return (
        dq, dk_row + dk_col, k * (dk_row - dk_col) + q * dq,
        jnp.sum(k * unscaled, axis=1, keepdims=True),
    )


def _each_chunk(ref, body, reverse=False):
    """``body(rows, c)`` for each of a visit's chunks, ``rows`` the
    chunk's tokens of a block [1, chunks * C, D] and ``c`` its place on
    a block [1, chunks, 1, ..]: first to last, or last to first."""
    per = ref.shape[1] // CHUNK

    def one(step, carry):
        c = per - 1 - step if reverse else step
        body(pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK), c)
        return carry

    jax.lax.fori_loop(0, per, one, 0)


def _pairs_kernel(q_ref, k_ref, g_ref, beta_ref, a_ref, m_ref):
    # q_ref, k_ref [1, P C, Dk]; g_ref [1, P C, Dk] f32; beta_ref
    # [1, P, 1, 1, C] f32; a_ref, m_ref [1, P, 1, C, C] f32
    dtype = k_ref.dtype

    def chunk(rows, c):
        gamma = _running(g_ref[0, rows], _lower(CHUNK))
        kk, m = _pairs(
            q_ref[0, rows].astype(F32), k_ref[0, rows].astype(F32), gamma,
            dtype,
        )
        eye, strict = _eye(CHUNK)
        a_ref[0, c, 0] = jax.lax.select(
            strict, _turned(beta_ref[0, c, 0], eye) * kk,
            jnp.zeros(kk.shape, F32),
        )
        m_ref[0, c, 0] = m

    _each_chunk(k_ref, chunk)


def _pairs_bwd_kernel(
    q_ref, k_ref, g_ref,  # [1, P C, Dk]
    beta_ref,  # [1, P, 1, 1, C] f32
    da_ref, dm_ref,  # [1, P, 1, C, C] f32
    dq_in, dk_in, dgamma_in,  # [1, P C, Dk]: the walk's parts (dγ f32)
    dbeta_in,  # [1, P, 1, 1, C] f32: the walk's part
    dq_ref, dk_ref, dg_ref,  # [1, P C, Dk]
    dbeta_ref,  # [1, P, 1, 1, C] f32
):
    dtype = k_ref.dtype

    def chunk(rows, c):
        lower = _lower(CHUNK)
        eye, _ = _eye(CHUNK)
        gamma = _running(g_ref[0, rows], lower)
        dq, dk, dgamma, dbeta = _pairs_pull(
            q_ref[0, rows].astype(F32), k_ref[0, rows].astype(F32), gamma,
            _turned(beta_ref[0, c, 0], eye), da_ref[0, c, 0],
            dm_ref[0, c, 0], dtype,
        )
        dq_ref[0, rows] = (
            dq + dq_in[0, rows].astype(F32)
        ).astype(dq_ref.dtype)
        dk_ref[0, rows] = (
            dk + dk_in[0, rows].astype(F32)
        ).astype(dk_ref.dtype)
        # dg_t = Σ_{i >= t} dγ_i
        dg_ref[0, rows] = _by_ones(
            _ones(lower), dgamma + dgamma_in[0, rows], _TN
        )
        dbeta_ref[0, c, 0] = _turned(dbeta, eye) + dbeta_in[0, c, 0]

    _each_chunk(k_ref, chunk)


def _chunk(k, g, op):
    """A chunk's decays and decayed keys from k and g [C, Dk]: (γ's last
    row [1, Dk], e^γ, e^{γ_C − γ} [C, Dk] float32, k float32, the pieces
    of K ⊙ e^γ)."""
    gamma = _running(g, _lower(CHUNK))
    last = gamma[CHUNK - 1:]
    grown, to_end = jnp.exp(gamma), jnp.exp(last - gamma)
    k = k.astype(F32)
    return last, grown, to_end, k, op(k * grown)


def _fwd_kernel(
    *refs,
    # q_ref (not with ``starts``), k_ref [1, P C, Dk]; v_ref [1, P C, Dv];
    # g_ref [1, P C, Dk] f32; beta_ref [1, P, 1, 1, C] f32; t_ref
    # [1, P, 1, C, C] f32: T; m_ref (not with ``starts``) the same;
    # out_ref: o [1, P C, Dv], or with ``starts`` the state each chunk
    # starts from, transposed [1, P, 1, Dv, Dk] f32; s_scr [Dv, Dk] f32
    starts,
):
    if starts:
        k_ref, v_ref, g_ref, beta_ref, t_ref, out_ref, s_scr = refs
    else:
        (q_ref, k_ref, v_ref, g_ref, beta_ref, t_ref, m_ref, out_ref,
         s_scr) = refs
    dtype = v_ref.dtype
    op = functools.partial(_pieces, dtype=dtype)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    def chunk(rows, c):
        last, grown, to_end, k, k_grown = _chunk(
            k_ref[0, rows], g_ref[0, rows], op
        )
        state = s_scr[...]
        if starts:
            out_ref[0, c, 0] = state
        # β on T's columns: W = Tβ (K ⊙ e^γ), U = Tβ V
        s_op, t = op(state), op(t_ref[0, c, 0] * beta_ref[0, c, 0])
        w = _dot(t, k_grown, _NN).astype(dtype)
        u = _dot(t, op(v_ref[0, rows]), _NN).astype(dtype)
        fresh = op(u.astype(F32) - _dot(op(w), s_op, _NT))  # V' [C, Dv]
        if not starts:
            out_ref[0, rows] = (
                _dot(op(q_ref[0, rows].astype(F32) * grown), s_op, _NT)
                + _dot(op(m_ref[0, c, 0]), fresh, _NN)
            ).astype(dtype)
        s_scr[...] = jnp.exp(last) * state + _dot(
            fresh, op(k * to_end), _TN
        )

    _each_chunk(k_ref, chunk)


def _bwd_kernel(
    q_ref, k_ref,  # [1, P C, Dk]
    v_ref, do_ref,  # [1, P C, Dv]
    g_ref,  # [1, P C, Dk] f32
    beta_ref,  # [1, P, 1, 1, C] f32
    t_ref, m_ref,  # [1, P, 1, C, C] f32: T, M
    start_ref,  # [1, P, 1, Dv, Dk] f32: the state each chunk started from
    dq_ref, dk_ref,  # [1, P C, Dk]
    dv_ref,  # [1, P C, Dv]
    dgamma_ref,  # [1, P C, Dk] f32
    dbeta_ref,  # [1, P, 1, 1, C] f32: T's columns' part
    dt_ref, dm_ref,  # [1, P, 1, C, C] f32
    ds_scr,  # [Dv, Dk] f32: d state at the chunk's end, transposed
):
    dtype = v_ref.dtype
    op = functools.partial(_pieces, dtype=dtype)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    def chunk(rows, c):
        last, grown, to_end, k, k_grown = _chunk(
            k_ref[0, rows], g_ref[0, rows], op
        )
        keep = jnp.exp(last)
        state, d_end = start_ref[0, c, 0], ds_scr[...]
        s_op, d_end_op = op(state), op(d_end)
        # the chunk's operands again
        plain, beta = t_ref[0, c, 0], beta_ref[0, c, 0]
        t, v, m = op(plain * beta), op(v_ref[0, rows]), op(m_ref[0, c, 0])
        q_grown = q_ref[0, rows].astype(F32) * grown
        k_end = k * to_end
        w = op(_dot(t, k_grown, _NN))
        u = _dot(t, v, _NN).astype(dtype).astype(F32)
        fresh = op(u - _dot(w, s_op, _NT))  # V'
        d_out = op(do_ref[0, rows])
        # back through the update, the read-out and V'
        d_fresh = op(
            _dot(m, d_out, _TN) + _dot(op(k_end), d_end_op, _NT)
        )  # [C, Dv]
        dm_ref[0, c, 0] = _dot(d_out, fresh, _NT)
        d_q_grown = _dot(d_out, s_op, _NN)  # [C, Dk]
        d_k_end = _dot(fresh, d_end_op, _NN)
        d_w = op(-_dot(d_fresh, s_op, _NN))
        ds_scr[...] = (
            keep * d_end + _dot(d_out, op(q_grown), _TN)
            - _dot(d_fresh, w, _TN)
        )
        # back through W and U
        d_t = _dot(d_w, k_grown, _NT) + _dot(d_fresh, v, _NT)  # dTβ
        dt_ref[0, c, 0] = d_t * beta
        dbeta_ref[0, c, 0] = jnp.sum(d_t * plain, axis=0, keepdims=True)
        d_k_grown = _dot(t, d_w, _TN)
        dv_ref[0, rows] = _dot(t, d_fresh, _TN).astype(dtype)
        dq_ref[0, rows] = (d_q_grown * grown).astype(dq_ref.dtype)
        dk_ref[0, rows] = (
            d_k_end * to_end + d_k_grown * grown
        ).astype(dk_ref.dtype)
        # the decays' cotangents: a row a token, and the chunk's last
        # row for what decays to the chunk's end
        handed = d_k_end * k_end
        d_last = jnp.sum(handed, axis=0, keepdims=True) + keep * jnp.sum(
            d_end * state, axis=0, keepdims=True
        )
        last_token = jax.lax.broadcasted_iota(
            jnp.int32, handed.shape, 0
        ) == CHUNK - 1
        dgamma_ref[0, rows] = (
            d_q_grown * q_grown + d_k_grown * (k * grown) - handed
            + jax.lax.select(
                last_token, jnp.broadcast_to(d_last, handed.shape),
                jnp.zeros(handed.shape, F32),
            )
        )

    _each_chunk(k_ref, chunk, reverse=True)


# chunks a grid step holds where the sequence has them: one layer's
# forward and backward 48.1 ms at 1, 44.9 at 4, 44.7 at 8 (my chip run,
# PR 66)
VISIT = 4


def _specs(per, dk, dv, steps, reverse=False):
    """The block specs of ``per`` chunks' operands on the grid (batch,
    head, step): the chunks are the step's, or the last minus it going
    back."""

    def at(i):
        return steps - 1 - i if reverse else i

    def by_chunk(*block):
        return pl.BlockSpec(
            (1, per, 1) + block, lambda b, h, i: (b, at(i), h, 0, 0)
        )

    def tokens(width):
        return pl.BlockSpec(
            (1, per * CHUNK, width), lambda b, h, i: (b, at(i), h)
        )

    return dict(
        key=tokens(dk), value=tokens(dv),
        square=by_chunk(CHUNK, CHUNK), state=by_chunk(dv, dk),
        row=by_chunk(1, CHUNK),
    )


def _sizes(k, dk):
    """(batch, heads, chunks, chunks a grid step, grid steps) of k
    [B, S, H * Dk]."""
    bsz, s, wide = k.shape
    n_chunks = s // CHUNK
    per = math.gcd(VISIT, n_chunks)
    return bsz, wide // dk, n_chunks, per, n_chunks // per


def _parallel(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * 3,
        vmem_limit_bytes=VMEM_LIMIT,
    )


@functools.partial(_traced_once, static=("dk", "interpret"))
def _pairs_forward(q, k, g, beta, *, dk, interpret):
    """(A, M) [B, N, H, C, C] float32 — ``strict_lower(β_i kk)`` and the
    decayed ``Q Kᵀ`` — of q, k [B, S, H*Dk], g [B, S, H*Dk] float32 and
    beta a row a chunk [B, N, H, 1, C] float32."""
    bsz, heads, n_chunks, per, steps = _sizes(k, dk)
    spec = _specs(per, dk, dk, steps)
    square = pallas_attention._out_struct(
        (bsz, n_chunks, heads, CHUNK, CHUNK), F32, k
    )
    return pl.pallas_call(
        _pairs_kernel,
        grid=(bsz, heads, steps),
        in_specs=[spec["key"]] * 3 + [spec["row"]],
        out_specs=[spec["square"]] * 2,
        out_shape=[square, square],
        compiler_params=_parallel(interpret),
        interpret=interpret,
        name="kda_pairs",
    )(q, k, g, beta)


@functools.partial(_traced_once, static=("dk", "interpret"))
def _pairs_backward(q, k, g, beta, da, dm, dq, dk_walk, dgamma, dbeta, *,
                    dk, interpret):
    """(dq, dk, dg [B, S, H*Dk], dβ [B, N, H, 1, C]), the rule's whole:
    the pairs' cotangents pulled back and added to the walk's parts,
    whose buffers the results take."""
    bsz, heads, _, per, steps = _sizes(k, dk)
    spec = _specs(per, dk, dk, steps)
    like = pallas_attention._out_struct
    return pl.pallas_call(
        _pairs_bwd_kernel,
        grid=(bsz, heads, steps),
        in_specs=[spec["key"]] * 3 + [spec["row"]] + [spec["square"]] * 2
        + [spec["key"]] * 3 + [spec["row"]],
        out_specs=[spec["key"]] * 3 + [spec["row"]],
        out_shape=[
            like(q.shape, q.dtype, k), like(k.shape, k.dtype, k),
            like(g.shape, F32, k), like(beta.shape, F32, k),
        ],
        input_output_aliases={6: 0, 7: 1, 8: 2, 9: 3},
        compiler_params=_parallel(interpret),
        interpret=interpret,
        name="kda_pairs_bwd",
    )(q, k, g, beta, da, dm, dq, dk_walk, dgamma, dbeta)


@functools.partial(_traced_once, static=("dk", "dv", "interpret", "starts"))
def _forward(q, k, v, g, beta, t, m, *, dk, dv, interpret, starts=False):
    """o [B, S, H*Dv] — or, with ``starts``, the state each chunk starts
    from, transposed, [B, N, H, Dv, Dk] float32 — of q, k [B, S, H*Dk],
    v [B, S, H*Dv], g [B, S, H*Dk] float32, beta [B, N, H, 1, C], T and
    M [B, N, H, C, C] float32."""
    bsz, heads, n_chunks, per, steps = _sizes(k, dk)
    spec = _specs(per, dk, dv, steps)
    out_shape = (bsz, n_chunks, heads, dv, dk) if starts else v.shape
    operands = (k, v, g, beta, t) if starts else (q, k, v, g, beta, t, m)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, starts=starts),
        grid=(bsz, heads, steps),
        in_specs=[spec["key"]] * (1 if starts else 2) + [
            spec["value"], spec["key"], spec["row"], spec["square"]
        ] + [spec["square"]] * (not starts),
        out_specs=spec["state" if starts else "value"],
        out_shape=pallas_attention._out_struct(
            out_shape, F32 if starts else v.dtype, v
        ),
        scratch_shapes=[pltpu.VMEM((dv, dk), F32)],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="kda_states" if starts else "kda_fwd",
    )(*operands)


@functools.partial(_traced_once, static=("dk", "dv", "interpret"))
def _backward(q, k, v, g, beta, t, m, do, *, dk, dv, interpret):
    """(dq, dk, dv, dγ, dβ, dT, dM) of the walk from its operands and
    o's cotangent (dq, dk, dγ and dβ its parts of them). The state each
    chunk started from comes from a pass of the forward kernel that
    makes nothing else, held behind ``do``
    (``pallas_gated_delta._backward`` says why)."""
    q, k, v, g, beta, t, m, do = jax.lax.optimization_barrier(
        (q, k, v, g, beta, t, m, do)
    )
    starts = _forward(
        q, k, v, g, beta, t, m, dk=dk, dv=dv, interpret=interpret,
        starts=True,
    )
    bsz, heads, _, per, steps = _sizes(k, dk)
    spec = _specs(per, dk, dv, steps, reverse=True)
    like = pallas_attention._out_struct
    return pl.pallas_call(
        _bwd_kernel,
        grid=(bsz, heads, steps),
        in_specs=[spec["key"], spec["key"], spec["value"], spec["value"],
                  spec["key"], spec["row"], spec["square"], spec["square"],
                  spec["state"]],
        out_specs=[spec["key"], spec["key"], spec["value"], spec["key"],
                   spec["row"], spec["square"], spec["square"]],
        out_shape=[
            like(q.shape, q.dtype, v), like(k.shape, k.dtype, v),
            like(v.shape, v.dtype, v), like(g.shape, F32, v),
            like(beta.shape, F32, v), like(t.shape, F32, v),
            like(m.shape, F32, v),
        ],
        scratch_shapes=[pltpu.VMEM((dv, dk), F32)],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="kda_bwd",
    )(q, k, v, do, g, beta, t, m, starts)


def pairs(q, k, g, beta, dk):
    """(A, M) of whole chunks: see ``_pairs_forward``."""
    return _pairs_forward(
        q, k, g, beta, dk=dk, interpret=pallas_attention.INTERPRET
    )


def pairs_backward(q, k, g, beta, da, dm, dq, dk_walk, dgamma, dbeta, dk):
    """(dq, dk, dg, dβ): see ``_pairs_backward``."""
    return _pairs_backward(
        q, k, g, beta, da, dm, dq, dk_walk, dgamma, dbeta, dk=dk,
        interpret=pallas_attention.INTERPRET,
    )


def forward(q, k, v, g, beta, t, m, dk, dv):
    """o [B, S, H*Dv] of whole chunks: see ``_forward``."""
    return _forward(
        q, k, v, g, beta, t, m, dk=dk, dv=dv,
        interpret=pallas_attention.INTERPRET,
    )


def backward(q, k, v, g, beta, t, m, do, dk, dv):
    """(dq, dk, dv, dγ, dβ, dT, dM): see ``_backward``."""
    return _backward(
        q, k, v, g, beta, t, m, do, dk=dk, dv=dv,
        interpret=pallas_attention.INTERPRET,
    )
