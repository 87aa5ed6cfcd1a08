"""The gated delta rule (Gated DeltaNet; Yang, Kautz & Hatamizadeh 2024)
in its chunked matmul form: a recurrence whose transition is NOT
diagonal, so no argument of ``ssd.ssd_scan`` expresses it.

For every value head h (key head h // R serves the R value heads
``[R j, R j + R)``: repeat-interleave), with the log-decay g_t <= 0
(α_t = exp(g_t)) and the write strength β_t in (0, 1), a state
``S`` [key channels, value channels], float32, ``S_0 = 0``:

    S'  = α_t S_{t-1}
    S_t = S' + β_t k_t (v_t − S'ᵀ k_t)ᵀ
        = α_t (I − β_t k_t k_tᵀ) S_{t-1} + β_t k_t v_tᵀ
    o_t = S_tᵀ q_t

The transition ``α_t (I − β_t k_t k_tᵀ)`` is a rank-one change of the
identity. Cut into chunks of C tokens, with γ_i the running sum of g
inside a chunk (the WY / UT form):

    A = strict_lower(β_i (k_i · k_j) e^{γ_i − γ_j})      T = (I + A)^{-1}
    W = T (β e^{γ} ⊙ K)      U = T (β ⊙ V)               (a chunk's own)
    V' = U − W S_prev
    O  = e^{γ} ⊙ (Q S_prev) + lower(Q Kᵀ ⊙ e^{γ_i − γ_j}) V'
    S_next = e^{γ_C} S_prev + Kᵀ (e^{γ_C − γ} ⊙ V')

The per-token recurrence is the definition; this form agrees with it
(``tests/test_gated_delta.py``, values and gradients; with a decay a
key channel — the last section below — ``tests/test_kda_rule.py``).

What is float32 whatever the compute dtype: g, β, the running sums γ,
every difference ``γ_i − γ_j`` (formed BEFORE the exponential: γ itself
passes −100 inside a chunk of a fast-forgetting head, and e^{γ_i} /
e^{γ_j} is 0 / 0 there), A, the triangular inverse T and the carried
state. The products (``K Kᵀ``, ``Q Kᵀ``, ``T ·``, ``W S``, ``Q S``,
``· V'``, ``Kᵀ ·``) multiply operands of q's, k's and v's dtype and sum
in float32, so on the chip they are MXU matmuls: one pass on bf16
operands, three passes of bf16 pieces on float32 operands
(``_products``), which is what the model's mixer hands in
(``decoder._gdn_block`` says why: the rule rounds a dozen operands a
chunk, each a product of the last, and a mixer's error is amplified by
every part behind it); the scales a value head has of its own (β e^{γ},
e^{γ_C − γ}) go on T's columns, on the products' float32 results and on
V', never on K or Q, which stay one array a KEY head.

The inverse of the unit lower-triangular ``I + A`` is by substitution,
which is backward stable (a product of ``I + (−A)^{2^j}`` is matmuls
only, and cancels catastrophically where a chunk's keys repeat): rows
one at a time inside diagonal blocks of 16, then blocks merged two by
two, ``T21 = −T22 A21 T11``, all of it float32 multiply-adds with the
batch of chunks on the lanes. Its derivative is by hand and needs
that inverse's transpose: ``dA = −strict_lower(Tᵀ dT Tᵀ)``.

Two bodies, one algorithm, chosen from what the call sees
(``in_kernels``: no argument, knob or model name). The XLA body
(``jnp``, one ``lax.scan`` over the chunks, differentiated by JAX) runs
on the CPU, at widths off the 128 lanes and on a mesh of several
devices: what the CPU tests hold to the recurrence, the fallback, and
the oracle of the other. The Pallas kernels
(``ops/pallas_gated_delta.py``, PR 64, ROADMAP S16(a)) run on a TPU, on
one device, with key and value channels on the 128-lane grid and chunks
of 64: XLA makes ``K Kᵀ`` and ``A`` of whole chunks
(``_chunk_inverse``, parallel over the chunks), the kernel
``tri_inverse`` inverts them (``pallas_gated_delta.inverse``, PR 71: the
same substitution and merges, a grid step 128 rows of the batch turned
to the lanes in VMEM) and the kernels walk the chunks with the state in
VMEM, making W, U, the decay block and ``Q Kᵀ`` in each visit, behind
one ``jax.custom_vjp`` (``_kernel_rule``). Whichever body runs, the
caller runs it under the scope ``gdn.rule``.

WHAT CROSSES HBM BETWEEN XLA AND THE KERNELS IS AS WIDE AS THE LANES
(PR 71, ROADMAP S16(d)). A float32 array that ends in [64, 64] is 268 MB
where its numbers are 134 (8,192 chunk-heads a layer at 16,384 tokens
and 32 value heads): an (8, 128) tile pads the 64 columns to 128 lanes,
and every pass over it moves the padding. A key head's R value heads
are one visit's, so ``A``, ``T`` and T's cotangent lie R matrices SIDE
BY SIDE on the last axis, ``[B, N, Hk, C, R C]`` (value head r is
columns ``[C r, C r + C)``; [.., 64, 128] at R = 2, nothing padded),
made in that order from the start (``_chunk_inverse(side_by_side=
True)``), inverted in that form and read by a lane slice in the visit;
the hand derivative takes them so too (``_inverse_pullback``: products over
all the lanes against T's blocks on a diagonal). R = 1 is the same code
at one matrix a row. The XLA body keeps ``[.., R, C, C]``: one
algorithm, the layout derived from the shapes.

What is kept and what is remade. ON THE XLA BODY the sequence goes
through in STRETCHES of 2,048 tokens (32 chunks of 64), one after
another, each under its own ``jax.checkpoint``: what a stretch keeps
for its backward is its operands and the state it starts from (2 MB a
stretch), and its own forward is remade when its backward comes. Whole,
the chunks' operands and the scan's residuals — A, T, the decays,
``Q Kᵀ`` (float32 [chunks, heads, C, C], each 268 MB at 16,384 tokens
and 32 heads, the 64 of C padded to 128 lanes), W, U, V' and the state
every chunk starts from (S/C × heads × 128 × 128 float32: 537 MB) — are
3.6 GB forward and backward of ONE layer (the compiler's count for a
described v5e), and the cell's step then needs 15.98 GB of the chip's
15.75; a stretch at a time they are an eighth of that. Under ``remat:
full`` nothing of the rule is kept across layers either: the boundary
states of six layers (3.2 GB) do not fit beside 9.4 GB of train state,
and keeping ``o`` alone would save nothing, since the backward needs
the states and they come from a forward pass whichever way. So a step
on the XLA body runs the rule's forward THREE times (the layer's
forward, the layer's remade forward, each stretch's remade forward) and
its backward once. ON THE KERNELS there is no stretch and no
checkpoint: the residuals are the five operands (q, k, v, g, β), the
backward rule makes A and T again (``K Kᵀ`` and A XLA's, T the inverse
kernel's: the compiler shares them with the layer's remade forward, the
same work on the same operands), takes every chunk's starting state
from a pass of its own (``gdn_states``: 537 MB a layer, alive inside
that layer's backward alone) and walks back remaking each chunk's
operands in the visit; T's cotangent comes out of the walk and goes
through the inverse's hand derivative and A's pull-back to k, g and β
(XLA's). A step runs the rule's forward TWICE (the layer's and ``remat:
full``'s remade one) and its backward once, with 0.82 GB of temporaries
forward and 2.03 GB going back (the compiler's count for a described
v5e; 0.95 and 2.3 before PR 71, when A, T and dT were [.., R, 64, 64]
and the substitution XLA's).

A length that is no multiple of the chunk is PADDED at its end with
tokens of g = 0, β = 0 and k = 0, which leave every state as it was and
whose outputs are cut off again: exact, since the rule is causal.

A DECAY A KEY CHANNEL (Kimi Delta Attention, arXiv:2510.26692; PR 65):
``g`` [B, S, Hv, Dk], ``S' = Diag(α_t) S_{t-1}``, row d of the state by
its own ``α_td``. The same function takes it and the shape of ``g`` is
what chooses. With γ the running sum of g inside a chunk, a vector a
token:

    A = strict_lower(β_i Σ_d k_id k_jd e^{γ_id − γ_jd})   T = (I + A)^{-1}
    W = T (β ⊙ K ⊙ e^{γ})      U = T (β ⊙ V)      V' = U − W S_prev
    O  = (Q ⊙ e^{γ}) S_prev + lower(Σ_d q_id k_jd e^{γ_id − γ_jd}) V'
    S_next = Diag(e^{γ_C}) S_prev + (K ⊙ e^{γ_C − γ})ᵀ V'

which with g equal over a head's channels is the form above, line for
line. The decay now sits INSIDE the sums over channels, so ``A`` and the
``Q Kᵀ`` block are no product of a key matrix and a decay block, and the
difference must still come before the exponential: ``_channel_pairs``
makes them in sub-blocks of 16 tokens, the diagonal blocks from explicit
[16, 16, Dk] differences and the rest as matmuls of ``K ⊙ e^{γ − r}`` by
``K ⊙ e^{r − γ}`` with r the running sum at the LATER block's first
token, so that every exponent formed is <= 0 and no [C, C, Dk] array of
a whole chunk exists. A head is a VALUE head here (shared keys are
repeated: the decayed keys are a value head's own).

Two bodies here too, one algorithm, chosen as above (``in_kernels``: the
same conditions). THE XLA BODY (``_channel_stretch``) runs on the CPU,
off the 128 lanes and on several devices, in stretches of
``CHANNEL_STRETCH`` = 1,024 tokens, each under its own checkpoint. What
a stretch holds at 32 heads of 128 channels: 16 chunks' operands (q, k,
v, g, W, U, ``Q ⊙ e^{γ}``, ``K ⊙ e^{γ_C − γ}``: 17 MB each, float32),
the earlier keys once a block row (38 MB), 16 chunk states (34 MB), and,
where XLA keeps them for the backward, the diagonal sub-blocks' decayed
keys [16, 32, 4, 16, 16, 128] (8 KB a token and head, 268 MB): forward
and backward of ONE layer are 0.53 GB of temporaries by the compiler's
count for a described v5e, 1.03 GB at stretches of 2,048 and 0.29 at
512. As on the scalar XLA body, a step under ``remat: full`` runs the
rule's forward three times and its backward once.

THE KERNELS (``ops/pallas_kda.py``, PR 66, ROADMAP S16(e); on a TPU, one
device, heads of 128, chunks of 64) share no arithmetic with the scalar
rule's: the decay inside the sums over channels is another algorithm in
the chunk, and equal channels are NOT routed to the scalar kernels by a
test of values. A pass is: ``kda_pairs`` (chunks in parallel; a visit
makes γ from g — a triangle of ones times g in three bf16 pieces —, the
four diagonal sub-blocks from explicit differences with the sums over
the 128 channels on the vector unit's lanes, the three block rows left
of them as one product each, and writes ``A = strict_lower(β_i kk)`` and
the decayed ``Q Kᵀ``, M) → the inverse of all chunk-heads at once
(the kernel ``tri_inverse`` since PR 71, XLA's ``unit_lower_inverse``
before: the substitution wants the batch of chunks on the lanes; in a
visit it is 16 dependent sublane steps) → the walk
(``kda_fwd``: one head a visit, the state [Dv, Dk] float32 in VMEM from
the first chunk to the last, γ, ``K ⊙ e^γ``, ``Q ⊙ e^γ``,
``K ⊙ e^{γ_C − γ}``, W, U and V' made in the visit and never written).
One ``jax.custom_vjp`` (``_channel_kernel_rule``) whose residuals are
the five operands (q, k, v, g: 268 MB each at 16,384 tokens and 32
heads, float32; β 2 MB); no stretch and no checkpoint. The backward rule
makes A, M and T again (the compiler shares them with the layer's remade
forward), takes every chunk's starting state from ``kda_states`` (537 MB
a layer, alive inside that layer's backward alone, behind a barrier),
walks back (``kda_bwd``: the chunk's operands remade in the visit; dv,
its parts of dq, dk, dγ and dβ, and dT and dM), takes dT through the
inverse's hand derivative (XLA's) and the pairs' cotangents back through
``kda_pairs_bwd``, which adds the walk's parts and writes dq, dk, dg and
dβ whole. A, M, T, dT, dM and dA are still ``[B, N, H, 64, 64]`` between
these kernels, 268 MB each as tiles pad them: two chunks of a head side
by side were built and measured slower (PR 71: a visit's chunks are a
rolled loop, so a chunk's half of a row is chosen and kept by selects;
``kda_pairs`` +0.8 ms a call, ``kda_pairs_bwd`` +1.5, ``kda_bwd`` +0.3:
ROADMAP S16(d)). A step under ``remat: full`` runs the rule's forward
TWICE and its backward once, with 1.61 GB of temporaries forward and
2.96 GB going back by the compiler's count for a described v5e (the
Kimi-Linear cell's step: 14.12 GB by that count, of the chip's 16.91).
"""

import functools

import jax
import jax.numpy as jnp

from dlrover_tpu.ops import pallas_gated_delta, pallas_kda

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# rows the substitution takes one at a time before blocks are merged
_BASE = 16


def recurrence(q, k, v, g, beta):
    """The definition, token by token (``lax.scan`` over t), float32:
    q, k [B, S, Hk, Dk], v [B, S, Hv, Dv], beta [B, S, Hv], g
    [B, S, Hv] (one decay a head) or [B, S, Hv, Dk] (one a key channel:
    ``S' = Diag(α_t) S_{t-1}``). Returns o [B, S, Hv, Dv] float32. For
    tests and small shapes."""
    rep = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(t.astype(F32), rep, axis=2) for t in (q, k))
    cells = (None,) * (5 - g.ndim)  # a state's axes a decay is spread over

    def token(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[(...,) + cells] * state
        seen = jnp.einsum("bhde,bhd->bhe", state, k_t, precision=_HIGHEST)
        state = state + (b_t[..., None] * k_t)[..., None] * (
            (v_t - seen)[:, :, None, :]
        )
        return state, jnp.einsum(
            "bhde,bhd->bhe", state, q_t, precision=_HIGHEST
        )

    b, _, hv, dv = v.shape
    start = jnp.zeros((b, hv, k.shape[-1], dv), F32)
    _, o = jax.lax.scan(
        token, start,
        jax.tree.map(
            lambda t: jnp.moveaxis(t, 1, 0),
            (q, k, v.astype(F32), g.astype(F32), beta.astype(F32)),
        ),
    )
    return jnp.moveaxis(o, 0, 1)


def _substitute(a):
    """(I + a)^{-1} of strictly lower blocks a [m, m, N] (m <= 16), row
    by row: ``T_i = e_i − Σ_{l<i} a_il T_l``."""
    m = a.shape[0]
    eye = jnp.eye(m, dtype=F32)[:, :, None]
    rows = [jnp.broadcast_to(eye[0], a.shape[1:])]
    for i in range(1, m):
        done = jnp.stack(rows)                       # [i, m, N]
        rows.append(eye[i] - jnp.sum(a[i, :i, None, :] * done, axis=0))
    return jnp.stack(rows)


def _product(a, b):
    """a b of matrices whose batch is the LAST axis: a [.., i, j, N],
    b [.., j, k, N], as j multiply-adds of [.., i, k, N] arrays."""
    return sum(
        a[..., :, j, None, :] * b[..., j, None, :, :]
        for j in range(a.shape[-2])
    )


def _two_by_two(t11, t21, t22):
    """[[T11, 0], [T21, T22]] of blocks [.., m, m, ..] whose rows are
    axis 1 and columns axis 2."""
    return jnp.concatenate(
        [
            jnp.concatenate([t11, jnp.zeros_like(t11)], axis=2),
            jnp.concatenate([t21, t22], axis=2),
        ],
        axis=1,
    )


def _inverse_of(a):
    """(I + a)^{-1}, a [N, C, C] strictly lower, float32, C a power of
    two (or under 16). Blocks of 16 by substitution and their merges
    worked with the batch N on the LANES, products and all, as
    multiply-adds: blocks of 16 or 32 rows as the trailing dimensions of
    an array are padded to the 128 lanes of a tile (a [8192, 4, 16, 4,
    16] float32 view of 134 MB of chunks took 1 GB of the chip). The
    LAST merge of a chunk of 64 or more, two halves of 32 rows or more,
    is two batched matmuls at ``HIGHEST`` with the batch first: as
    multiply-adds its 2 x 32 steps each read and wrote the halves whole,
    a column of them a strided copy — 9 of the 13 ms a pass and layer
    that XLA spent on a chunk's operands at Qwen3-Next's widths (my chip
    runs, PR 64) — and T is wanted batch first anyway."""
    n, c, _ = a.shape
    size = min(c, _BASE)
    on_mxu = c >= 4 * _BASE  # the last merge: see above
    lanes_last = jnp.moveaxis(a, 0, -1)              # [C, C, N]

    def blocks(of):
        # [P, of, P, of, N]: block row, row, block column, column
        return lanes_last.reshape(c // of, of, c // of, of, n)

    diag = blocks(size)
    inv = jnp.stack([
        _substitute(diag[i, :, i]) for i in range(c // size)
    ])                                               # [P, m, m, N]
    while size < (c // 2 if on_mxu else c):
        # [[T11, 0], [T21, T22]] of each pair of neighbours,
        # T21 = −T22 A21 T11
        pair = blocks(2 * size)
        a21 = jnp.stack([
            pair[i, size:, i, :size] for i in range(c // (2 * size))
        ])
        t11, t22 = inv[0::2], inv[1::2]
        t21 = -_product(t22, _product(a21, t11))
        inv = _two_by_two(t11, t21, t22)
        size *= 2
    if not on_mxu:
        return jnp.moveaxis(inv[0], -1, 0)
    t11, t22 = jnp.moveaxis(inv[0], -1, 0), jnp.moveaxis(inv[1], -1, 0)
    t21 = -jnp.matmul(
        t22, jnp.matmul(a[:, size:, :size], t11, precision=_HIGHEST),
        precision=_HIGHEST,
    )
    return _two_by_two(t11, t21, t22)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _inverse(a, in_kernel):
    """``(I + a)^{-1}`` of every strictly lower [C, C] matrix of a
    [..., C, P C] float32, P of them side by side on the last axis
    (matrix p is columns ``[C p, C p + C)``). ``in_kernel``: by
    ``pallas_gated_delta.inverse``, which takes any P (what the rules'
    kernel paths call, where ``in_kernels`` holds); else by
    ``_inverse_of``, one matrix a row. One hand-written derivative
    serves both."""
    if in_kernel:
        return pallas_gated_delta.inverse(a)
    return _inverse_of(a.reshape((-1,) + a.shape[-2:])).reshape(a.shape)


def _inverse_fwd(a, in_kernel):
    t = _inverse(a, in_kernel)
    return t, t


def _inverse_pullback(t, dt):
    """A's cotangent from T's: d(I + a)^{-1} = −T da T, so the cotangent
    takes T's transpose, ``dA = −strict_lower(Tᵀ dT Tᵀ)`` matrix by
    matrix. With P matrices side by side ([..., C, P C]): dT Tᵀ of all P
    in one product over the lanes, against T's blocks on a diagonal
    (rows (p, c), matrix p's lanes, zeros elsewhere); then Tᵀ · of every
    pair of matrices, of which a matrix's own columns are kept. No array
    is narrower than T."""
    c, wide = t.shape[-2:]
    p = wide // c
    own = jnp.arange(wide) // c == jnp.arange(p)[:, None, None]
    on_diagonal = jnp.where(own, t[..., None, :, :], 0.0).reshape(
        t.shape[:-2] + (wide, wide)
    )
    dt_tt = jnp.einsum(
        "...il,...cl->...ic", dt, on_diagonal, precision=_HIGHEST
    )
    every = jnp.einsum(
        "...ia,...ic->...ac", t, dt_tt, precision=_HIGHEST
    ).reshape(t.shape[:-2] + (p, c, wide))
    strict = jnp.tile(jnp.tril(jnp.ones((c, c), bool), -1), (1, p))
    return jnp.sum(jnp.where(own & strict, -every, 0.0), axis=-3)


def _inverse_bwd(_, t, dt):
    return (_inverse_pullback(t, dt),)


_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def unit_lower_inverse(a):
    """``(I + a)^{-1}`` for a [..., C, C] float32 that is STRICTLY lower
    triangular (what lies on or above the diagonal is the caller's to
    have zeroed), C a power of two or under 16."""
    return _inverse(a, False)


def _products(dtype):
    """``einsum`` for the rule's products on operands of ``dtype``,
    summed in float32: one MXU pass on bf16 operands; on float32
    operands three passes of bf16 pieces (``Precision.HIGH``), since one
    pass would round them to bf16 first."""
    precision = jax.lax.Precision.HIGH if dtype == F32 else None
    return functools.partial(
        jnp.einsum, preferred_element_type=F32, precision=precision
    )


def _heads_side_by_side(kk, rows):
    """``A = strict_lower(β_i (k_i · k_j) e^{γ_i − γ_j})`` of a key
    head's R value heads SIDE BY SIDE on the lanes: kk [B, N, Hk, C, C]
    and rows [B, N, Hk, 2 R, C] (γ a row a token of each head, then β)
    float32 give [B, N, Hk, C, R C], value head r as columns
    ``[C r, C r + C)``, made in that order from the start (an array
    that ends in [C, C] is padded to the 128 lanes of a tile, twice its
    bytes at C = 64; one the R heads share a row of is not). What the
    kernel ``tri_inverse`` makes for itself going forward
    (``pallas_gated_delta.gated_inverse``); here for its pull-back to
    kk, γ and β."""
    chunk = kk.shape[-1]
    n_heads = rows.shape[3] // 2
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    heads = []
    for r in range(n_heads):
        gamma, beta = rows[..., r, :], rows[..., n_heads + r, :]
        # the difference first, then the exponential
        decay = jnp.exp(jnp.where(
            lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf
        ))
        heads.append(jnp.where(strict, beta[..., :, None] * kk * decay, 0.0))
    return jnp.concatenate(heads, axis=-1)


@jax.custom_vjp
def _gated_inverse(kk, rows):
    """``(I + A)^{-1}`` of ``_heads_side_by_side``'s A, in its form, by
    the kernel that makes A as it goes; the derivative by hand through
    the inverse (``_inverse_pullback``), then A's pull-back by JAX."""
    return pallas_gated_delta.gated_inverse(kk, rows)


def _gated_inverse_fwd(kk, rows):
    t = _gated_inverse(kk, rows)
    return t, (kk, rows, t)


def _gated_inverse_bwd(kept, dt):
    kk, rows, t = kept
    _, pull = jax.vjp(_heads_side_by_side, kk, rows)
    return pull(_inverse_pullback(t, dt))


_gated_inverse.defvjp(_gated_inverse_fwd, _gated_inverse_bwd)


def _chunk_gates(k, g, beta):
    """What a chunk's keys and gates are multiplied and summed to before
    anything is inverted: k [B, N, C, Hk, Dk], g and beta
    [B, N, C, Hk, R] float32 give (``K Kᵀ`` [B, N, Hk, C, C], gamma
    [B, N, C, Hk, R], then a token last, gamma and beta
    [B, N, Hk, R, C])."""
    gamma = jnp.cumsum(g, axis=2)
    kk = _products(k.dtype)("bnikd,bnjkd->bnkij", k, k)
    return kk, gamma, jnp.moveaxis(gamma, 2, -1), jnp.moveaxis(beta, 2, -1)


def _chunk_inverse(k, g, beta, chunk):
    """What a chunk's keys and gates alone decide, from whole chunks: k
    [B, N, C, Hk, Dk], g and beta [B, N, C, Hk, R] float32. Returns (the
    triangular inverse T [B, N, Hk, R, C, C] float32, gamma
    [B, N, C, Hk, R], then a token last, gamma and beta [B, N, Hk, R, C],
    and the decay block on and under the diagonal [B, N, Hk, R, C, C])."""
    kk, gamma, gamma_t, beta_t = _chunk_gates(k, g, beta)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # the difference first, then the exponential
    decay = jnp.exp(jnp.where(
        lower, gamma_t[..., :, None] - gamma_t[..., None, :], -jnp.inf
    ))                                               # [B, N, Hk, R, C, C]
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a = jnp.where(
        strict, beta_t[..., :, None] * kk[:, :, :, None] * decay, 0.0
    )
    return unit_lower_inverse(a), gamma, gamma_t, beta_t, decay


def _chunk_operands(q, k, v, g, beta, chunk):
    """What a chunk brings to the scan, from whole chunks. q, k
    [B, N, C, Hk, Dk], v [B, N, C, Hk, R, Dv], g and beta
    [B, N, C, Hk, R] float32. Returns (w [B, N, C, Hk, R, Dk], u
    [B, N, C, Hk, R, Dv], attn [B, N, Hk, R, C, C]) in v's dtype and
    gamma [B, N, C, Hk, R] float32."""
    dtype = v.dtype
    dot = _products(dtype)
    t, gamma, gamma_t, beta_t, decay = _chunk_inverse(k, g, beta, chunk)
    qk = dot("bnikd,bnjkd->bnkij", q, k)
    # a value head's own scales go on T's COLUMNS: K stays a key head's
    w = dot(
        "bnkrij,bnjkd->bnikrd",
        (t * (beta_t * jnp.exp(gamma_t))[..., None, :]).astype(dtype), k,
    )
    u = dot(
        "bnkrij,bnjkre->bnikre", (t * beta_t[..., None, :]).astype(dtype), v
    )
    attn = (qk[:, :, :, None] * decay).astype(dtype)
    return w.astype(dtype), u.astype(dtype), attn, gamma


def _stretch(state, q, k, v, g, beta, chunk):
    """One stretch of whole chunks from ``state`` [B, Hk, R, Dk, Dv]
    float32: q, k [B, S, Hk, Dk], v [B, S, Hk, R, Dv], g and beta
    [B, S, Hk, R] float32. Returns (the state it leaves, o
    [B, S, Hk, R, Dv] in v's dtype)."""
    b, s, hk, _ = k.shape
    r, dv = v.shape[3:]
    n = s // chunk
    dtype = v.dtype
    dot = _products(dtype)

    def cut(t):
        return t.reshape((b, n, chunk) + t.shape[2:])

    q, k, v, g, beta = (cut(t) for t in (q, k, v, g, beta))
    w, u, attn, gamma = _chunk_operands(q, k, v, g, beta, chunk)

    def one(state, inp):
        q_c, k_c, w_c, u_c, attn_c, gamma_c = inp
        s_op = state.astype(dtype)
        fresh = u_c.astype(F32) - dot(
            "bikrd,bkrde->bikre", w_c, s_op
        )                                            # V' [B, C, Hk, R, Dv]
        o = jnp.exp(gamma_c)[..., None] * dot(
            "bikd,bkrde->bikre", q_c, s_op
        ) + dot("bkrij,bjkre->bikre", attn_c, fresh.astype(dtype))
        last = gamma_c[:, -1]                        # [B, Hk, R]
        state = jnp.exp(last)[..., None, None] * state + dot(
            "bjkd,bjkre->bkrde", k_c,
            (fresh * jnp.exp(last[:, None] - gamma_c)[..., None]).astype(
                dtype
            ),
        )
        return state, o.astype(dtype)

    state, o = jax.lax.scan(
        one, state,
        jax.tree.map(
            lambda t: jnp.moveaxis(t, 1, 0), (q, k, w, u, attn, gamma)
        ),
    )
    return state, jnp.moveaxis(o, 0, 1).reshape(b, s, hk, r, dv)


def _chunked(q, k, v, g, beta, chunk, stretch, body=_stretch):
    """Whole stretches of ``stretch`` tokens, each of whole chunks, one
    after another (``lax.scan``), each under its own
    ``jax.checkpoint``: see the module's docstring. ``body`` is
    ``_stretch`` or ``_channel_stretch``, shapes as its own: the state
    starts as zeros [B, v's head axes, Dk, Dv]."""
    b, s = k.shape[:2]
    start = jnp.zeros((b,) + v.shape[2:-1] + (k.shape[-1], v.shape[-1]), F32)
    if s == stretch:
        return body(start, q, k, v, g, beta, chunk)[1]

    def lead(t):
        # [B, S, ...] -> [stretches, B, stretch, ...]
        return jnp.moveaxis(
            t.reshape((b, s // stretch, stretch) + t.shape[2:]), 1, 0
        )

    one = jax.checkpoint(
        lambda state, operands: body(state, *operands, chunk)
    )
    _, o = jax.lax.scan(one, start, tuple(map(lead, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1).reshape(v.shape)


def _channel_pairs(q, k, gamma):
    """A chunk's decayed products under a decay a key CHANNEL: q, k
    [..., C, Dk] of one dtype, gamma [..., C, Dk] float32, the running
    sums of g inside the chunk (<= 0 and falling). Returns (kk, qk)
    [..., C, C] float32, ``Σ_d k_id k_jd e^{γ_id − γ_jd}`` and
    ``Σ_d q_id k_jd e^{γ_id − γ_jd}``, on and under the diagonal and 0
    above it. The decay sits INSIDE the sum over channels, so neither is
    a product of a key matrix and a decay block, and ``K ⊙ e^{−γ}``
    overflows float32 (γ passes −100 inside a chunk of a fast channel).
    In SUB-BLOCKS of ``_BASE`` tokens:

    - a diagonal block from explicit differences [16, 16, Dk], the
      exponential of each (<= 0 under the diagonal) and float32
      multiply-adds over the channels: 8 KB a token and head, where the
      differences of a whole chunk of 64 would be 32;
    - the blocks LEFT of block I as ONE matmul a block row, of
      ``K_I ⊙ e^{γ_i − r_I}`` (and ``Q_I ⊙`` the same) by
      ``K_j ⊙ e^{r_I − γ_j}`` over the earlier tokens j, with the
      reference r_I the running sum at block I's FIRST token: γ falls,
      so ``γ_i <= r_I <= γ_j`` and both exponents are <= 0. The right
      operand is the chunk's earlier keys once a block row."""
    dtype = k.dtype
    dot = _products(dtype)
    lead, (c, dk) = k.shape[:-2], k.shape[-2:]
    sub = min(c, _BASE)
    p = c // sub

    def blocks(t):
        return t.astype(F32).reshape(lead + (p, sub, dk))

    qb, kb, gb = blocks(q), blocks(k), blocks(gamma)
    on = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    # the difference first, then the exponential: [.., P, a, b, Dk]
    seen = kb[..., None, :, :] * jnp.exp(jnp.where(
        on, gb[..., :, None, :] - gb[..., None, :, :], -jnp.inf
    ))
    diag = [
        jnp.sum(t[..., :, None, :] * seen, axis=-1) for t in (kb, qb)
    ]
    # block (I, I) of a [C, C] array
    eye = jnp.eye(p, dtype=F32)[:, None, :, None]
    kk, qk = (
        (t[..., :, :, None, :] * eye).reshape(lead + (c, c)) for t in diag
    )
    if p == 1:
        return kk, qk
    ref = gb[..., 1:, :1, :]                         # [.., P-1, 1, Dk]
    into = jnp.exp(gb[..., 1:, :, :] - ref)          # e^{γ_i − r_I} <= 1
    cols = c - sub  # the last block stands left of none
    earlier = (
        jnp.arange(cols) < sub * jnp.arange(1, p)[:, None]
    )[..., None]                                     # [P-1, cols, 1]
    k_then = kb.reshape(lead + (1, c, dk))[..., :cols, :]
    out_of = k_then * jnp.exp(jnp.where(
        earlier, ref - gamma[..., None, :cols, :], -jnp.inf
    ))                                               # [.., P-1, cols, Dk]
    left = jnp.concatenate(
        [kb[..., 1:, :, :] * into, qb[..., 1:, :, :] * into], axis=-2
    )
    off = dot(
        "...pad,...pjd->...paj", left.astype(dtype), out_of.astype(dtype)
    )                                                # [.., P-1, 2 sub, cols]
    none = ((0, 0),) * len(lead)

    def placed(t):
        # block rows 1.., columns before the last block, of [C, C]
        return jnp.pad(t, none + ((1, 0), (0, 0), (0, sub))).reshape(
            lead + (c, c)
        )

    return kk + placed(off[..., :sub, :]), qk + placed(off[..., sub:, :])


def _channel_stretch(state, q, k, v, g, beta, chunk):
    """``_stretch`` under a decay a key channel, a head a VALUE head
    (the caller repeats shared keys): ``state`` [B, H, Dk, Dv] float32,
    q, k [B, S, H, Dk], v [B, S, H, Dv], g [B, S, H, Dk] and beta
    [B, S, H] float32. Returns (the state it leaves, o [B, S, H, Dv] in
    v's dtype). The decays a chunk's operands carry are all <= 1:
    ``K ⊙ e^{γ}`` into W, ``Q ⊙ e^{γ}`` on the state it starts from,
    ``K ⊙ e^{γ_C − γ}`` and ``e^{γ_C}`` into the state it leaves."""
    b, s, h, dk = k.shape
    dv = v.shape[-1]
    n = s // chunk
    dtype = v.dtype
    dot = _products(dtype)

    def cut(t):
        # [B, S, H, ...] -> [B, N, H, C, ...]
        return jnp.moveaxis(
            t.reshape((b, n, chunk) + t.shape[2:]), 2, 3
        )

    q, k, v, g, beta = (cut(t) for t in (q, k, v, g, beta))
    gamma = jnp.cumsum(g, axis=3)
    kk, qk = _channel_pairs(q, k, gamma)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    beta = beta[..., None]
    t = unit_lower_inverse(jnp.where(strict, beta * kk, 0.0)).astype(dtype)
    k32, grown = k.astype(F32), jnp.exp(gamma)
    w = dot("bnhij,bnhjd->bnhid", t, (beta * grown * k32).astype(dtype))
    u = dot("bnhij,bnhje->bnhie", t, (beta * v.astype(F32)).astype(dtype))
    last = gamma[..., -1:, :]
    operands = (
        (q.astype(F32) * grown).astype(dtype),
        (k32 * jnp.exp(last - gamma)).astype(dtype),
        w.astype(dtype), u.astype(dtype), qk.astype(dtype),
        jnp.exp(last[..., 0, :]),
    )

    def one(state, inp):
        q_c, k_c, w_c, u_c, attn_c, kept = inp
        s_op = state.astype(dtype)
        fresh = u_c.astype(F32) - dot("bhid,bhde->bhie", w_c, s_op)
        o = dot("bhid,bhde->bhie", q_c, s_op) + dot(
            "bhij,bhje->bhie", attn_c, fresh.astype(dtype)
        )
        state = kept[..., None] * state + dot(
            "bhjd,bhje->bhde", k_c, fresh.astype(dtype)
        )
        return state, o.astype(dtype)

    state, o = jax.lax.scan(
        one, state, jax.tree.map(lambda t: jnp.moveaxis(t, 1, 0), operands)
    )
    # [N, B, H, C, Dv] -> [B, S, H, Dv]
    return state, jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, s, h, dv)


def _kernel_operands(k, g, beta):
    """What is made of whole chunks for the kernels' walk
    (``ops/pallas_gated_delta.py``), parallel over the chunks: k
    [B, S, Hk, Dk], g and beta [B, S, Hk, R] float32. Returns (T a key
    head's R value heads side by side [B, N, Hk, C, R C], gamma a column
    a token [B, N, Hk, C, R], gamma then beta a row a token
    [B, N, Hk, 2 R, C]) float32: ``K Kᵀ`` and the running sums XLA's, T
    the kernel ``tri_inverse``'s."""
    chunk = pallas_gated_delta.CHUNK
    b, s = k.shape[:2]

    def cut(t):
        return t.reshape((b, s // chunk, chunk) + t.shape[2:])

    kk, gamma, gamma_t, beta_t = _chunk_gates(cut(k), cut(g), cut(beta))
    rows = jnp.concatenate([gamma_t, beta_t], axis=3)
    return _gated_inverse(kk, rows), jnp.moveaxis(gamma, 2, 3), rows


def _flat(q, k, v):
    """q, k [B, S, Hk * Dk] and v [B, S, Hv * Dv], as the kernels take
    them: a head a run of columns. A VIEW where the caller computed in
    this form and reshaped to heads at the rule's door (the models'
    mixers do: ``decoder._l2_heads``, ``_kda_block``'s g); arithmetic
    done on ``[B, S, H, D]`` pins that tiling (8 of H by 128 of D
    against 8 of S by 128 of H * D) and makes this reshape, and each
    cotangent's on the way back, a copy of the whole array."""
    b, s = k.shape[:2]
    return q.reshape(b, s, -1), k.reshape(b, s, -1), v.reshape(b, s, -1)


@jax.custom_vjp
def _kernel_rule(q, k, v, g, beta):
    """The rule over whole chunks of 64 by the Pallas kernels: q, k
    [B, S, Hk, Dk] and v [B, S, Hk, R, Dv] of one dtype, g and beta
    [B, S, Hk, R] float32. Returns o [B, S, Hk, R, Dv]. What it keeps
    for its backward is its five operands."""
    return pallas_gated_delta.forward(
        *_flat(q, k, v), *_kernel_operands(k, g, beta), k.shape[-1],
        v.shape[-1],
    ).reshape(v.shape)


def _kernel_rule_fwd(q, k, v, g, beta):
    return _kernel_rule(q, k, v, g, beta), (q, k, v, g, beta)


def _kernel_rule_bwd(operands, do):
    q, k, v, g, beta = operands
    made, pull = jax.vjp(_kernel_operands, k, g, beta)
    dq, dk_walk, dv, dt, dcol, drow = pallas_gated_delta.backward(
        *_flat(q, k, v), *made, do.reshape(do.shape[:2] + (-1,)),
        k.shape[-1], v.shape[-1],
    )
    # T's, gamma's and beta's cotangents through what XLA made of them
    dk_made, dg, dbeta = pull((dt, dcol, drow))
    return (
        dq.reshape(q.shape), dk_walk.reshape(k.shape) + dk_made,
        dv.reshape(v.shape), dg, dbeta,
    )


_kernel_rule.defvjp(_kernel_rule_fwd, _kernel_rule_bwd)


def _channel_operands(q, k, v, g, beta):
    """The vector rule's operands as its kernels take them: q, k, v and
    g a head a run of columns ([B, S, H * D]), beta a row a chunk
    [B, N, H, 1, C]."""
    b, s, h = beta.shape
    chunk = pallas_gated_delta.CHUNK
    rows = jnp.moveaxis(beta.reshape(b, s // chunk, chunk, h), 2, 3)
    return _flat(q, k, v) + (g.reshape(b, s, -1), rows[..., None, :])


@jax.custom_vjp
def _channel_kernel_rule(q, k, v, g, beta):
    """The rule with a decay a key channel over whole chunks of 64 by
    the Pallas kernels (``ops/pallas_kda.py``): q, k [B, S, H, Dk] and v
    [B, S, H, Dv] of one dtype, g [B, S, H, Dk] and beta [B, S, H]
    float32. Returns o [B, S, H, Dv]. XLA makes the triangular inverse
    between the pairs and the walk and nothing else. What it keeps for
    its backward is its five operands."""
    dk, dv = k.shape[-1], v.shape[-1]
    qf, kf, vf, gf, rows = _channel_operands(q, k, v, g, beta)
    a, scores = pallas_kda.pairs(qf, kf, gf, rows, dk)
    return pallas_kda.forward(
        qf, kf, vf, gf, rows, _inverse(a, True), scores, dk, dv
    ).reshape(v.shape)


def _channel_kernel_rule_fwd(q, k, v, g, beta):
    return _channel_kernel_rule(q, k, v, g, beta), (q, k, v, g, beta)


def _channel_kernel_rule_bwd(operands, do):
    q, k, v, g, beta = operands
    dk, dv = k.shape[-1], v.shape[-1]
    qf, kf, vf, gf, rows = _channel_operands(*operands)
    a, scores = pallas_kda.pairs(qf, kf, gf, rows, dk)
    t, pull = jax.vjp(lambda a: _inverse(a, True), a)
    dq, dk_walk, dval, dgamma, dbeta, dt, dscores = pallas_kda.backward(
        qf, kf, vf, gf, rows, t, scores,
        do.reshape(do.shape[:2] + (-1,)), dk, dv,
    )
    # T's cotangent through the inverse, then the pairs' back
    dq, dkey, dg, dbeta = pallas_kda.pairs_backward(
        qf, kf, gf, rows, *pull(dt), dscores, dq, dk_walk, dgamma, dbeta,
        dk,
    )
    return (
        dq.reshape(q.shape), dkey.reshape(k.shape), dval.reshape(v.shape),
        dg.reshape(g.shape),
        jnp.moveaxis(dbeta[..., 0, :], 2, 3).reshape(beta.shape),
    )


_channel_kernel_rule.defvjp(
    _channel_kernel_rule_fwd, _channel_kernel_rule_bwd
)


def in_kernels(dk: int, dv: int, chunk: int = 64, mesh=None,
               per_channel: bool = False) -> bool:
    """Whether ``gated_delta_rule`` runs the Pallas kernels at these
    widths (``pallas_gated_delta.tile``): what the counters
    ``gdn.kernel_layers`` and ``kda.kernel_layers`` count by. The two
    rules' kernels (``per_channel``: ``ops/pallas_kda.py``'s) take the
    same calls: a TPU or interpreted, one device, channels on the
    128-lane grid, chunks of 64."""
    del per_channel
    return pallas_gated_delta.tile(dk, dv, chunk, mesh)


# tokens a stretch of the rule with a decay a key channel holds: see
# the module's docstring
CHANNEL_STRETCH = 1024


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64,
                     stretch: int = 0, mesh=None):
    """The rule over a sequence. q, k [B, S, Hk, Dk] (the caller's to
    have normed and scaled), v [B, S, Hv, Dv] with Hv a multiple of Hk
    (key head j serves value heads R j .. R j + R − 1), beta [B, S, Hv]
    float32 in (0, 1), and g float32, the log-decay (<= 0): [B, S, Hv],
    one a value head, or [B, S, Hv, Dk], one a KEY CHANNEL of each value
    head (``S' = Diag(e^{g_t}) S_{t-1}``; KDA). The shape of g is what
    chooses, and nothing else does. Returns o [B, S, Hv, Dv] in v's
    dtype. ``chunk`` is a power of two (or under 16) and ``stretch`` a
    multiple of it (0: 2,048 tokens, ``CHANNEL_STRETCH`` under a decay a
    channel); neither is a size of the model. One decay a head: one
    function, two bodies, chosen from what it sees (``in_kernels``;
    ``mesh`` is the mesh the operands live on, if any): on a TPU (or
    interpreted), on one device, with key and value channels on the
    128-lane grid and chunks of 64, the Pallas kernels ``gdn_fwd`` /
    ``gdn_states`` / ``gdn_bwd``, which take no stretch; anywhere else —
    the CPU, other widths, a mesh of several devices — the XLA body,
    stretches and all. One decay a channel: the same choice between
    ``ops/pallas_kda.py``'s kernels (``kda_pairs``, ``kda_fwd`` /
    ``kda_states`` / ``kda_bwd``, ``kda_pairs_bwd``) and the XLA body in
    sub-blocks (``_channel_pairs``, ``_channel_stretch``)."""
    b, s, hk, dk = k.shape
    hv, dv = v.shape[2:]
    if hv % hk:
        raise ValueError(f"{hv} value heads are not shared by {hk} key heads")
    if chunk & (chunk - 1) and chunk > _BASE:
        raise ValueError(f"a chunk of {chunk} tokens is no power of two")
    per_channel = g.ndim == 4
    if g.shape != (b, s, hv) + (dk,) * per_channel:
        raise ValueError(
            f"g {g.shape} is one decay neither a value head [{b}, {s}, "
            f"{hv}] nor a key channel of each [{b}, {s}, {hv}, {dk}]"
        )
    stretch = stretch or (CHANNEL_STRETCH if per_channel else 2048)
    if stretch % chunk:
        raise ValueError(
            f"a stretch of {stretch} tokens is not whole chunks of {chunk}"
        )
    r = hv // hk
    dtype = v.dtype  # the products' operands: see ``_products``
    q, k = q.astype(dtype), k.astype(dtype)
    g, beta = g.astype(F32), beta.astype(F32)
    kernels = in_kernels(dk, dv, chunk, mesh, per_channel)
    # whole chunks, and on the XLA body whole stretches where there are
    # several
    stretch = chunk if kernels else min(stretch, s + -s % chunk)
    pad = -s % stretch
    if pad:
        # g = 0, β = 0, k = 0: see the module's docstring
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta)
        )
    if per_channel:
        # a head a value head: shared keys are repeated
        if r > 1:
            q, k = (jnp.repeat(t, r, axis=2) for t in (q, k))
        if kernels:
            o = _channel_kernel_rule(q, k, v, g, beta)
        else:
            o = _chunked(
                q, k, v, g, beta, chunk, stretch, body=_channel_stretch
            )
    else:
        operands = (
            q, k, v.reshape(b, s + pad, hk, r, dv),
            g.reshape(b, s + pad, hk, r), beta.reshape(b, s + pad, hk, r),
        )
        if kernels:
            o = _kernel_rule(*operands)
        else:
            o = _chunked(*operands, chunk, stretch)
        o = o.reshape(b, s + pad, hv, dv)
    return o[:, :s] if pad else o
