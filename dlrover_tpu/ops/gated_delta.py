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
(``tests/test_gated_delta.py``, values and gradients).

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

This XLA body (``jnp``, one ``lax.scan`` over the chunks, differentiated
by JAX) is the op's only body today: what the chip runs, what the CPU
tests hold to the recurrence, and the oracle of the Pallas kernels to
come (ROADMAP S13). Whichever body runs, the caller runs it under the
scope ``gdn.rule``.

What is kept and what is remade. The sequence goes through in
STRETCHES of 2,048 tokens (32 chunks of 64), one after another, each
under its own ``jax.checkpoint``: what a stretch keeps for its backward
is its operands and the state it starts from (2 MB a stretch), and its
own forward is remade when its backward comes. Whole, the chunks'
operands and the scan's residuals — A, T, the decays, ``Q Kᵀ`` (float32
[chunks, heads, C, C], each 268 MB at 16,384 tokens and 32 heads, the
64 of C padded to 128 lanes), W, U, V' and the state every chunk starts
from (S/C × heads × 128 × 128 float32: 537 MB) — are 3.6 GB forward
and backward of ONE layer (the compiler's count for a described v5e),
and the cell's step then needs 15.98 GB of the chip's 15.75; a stretch
at a time they are an eighth of that. Under ``remat: full`` nothing of
the rule is kept across layers either: the boundary states of six
layers (3.2 GB) do not fit beside 9.4 GB of train state, and keeping
``o`` alone would save nothing, since the backward needs the states and
they come from a forward pass whichever way. So a step runs the rule's
forward three times (the layer's forward, the layer's remade forward,
each stretch's remade forward) and its backward once.

A length that is no multiple of the chunk is PADDED at its end with
tokens of g = 0, β = 0 and k = 0, which leave every state as it was and
whose outputs are cut off again: exact, since the rule is causal.
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# rows the substitution takes one at a time before blocks are merged
_BASE = 16


def recurrence(q, k, v, g, beta):
    """The definition, token by token (``lax.scan`` over t), float32:
    q, k [B, S, Hk, Dk], v [B, S, Hv, Dv], g and beta [B, S, Hv].
    Returns o [B, S, Hv, Dv] float32. For tests and small shapes."""
    rep = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(t.astype(F32), rep, axis=2) for t in (q, k))

    def token(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[..., None, None] * state
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


def _inverse_of(a):
    """(I + a)^{-1}, a [N, C, C] strictly lower, float32, C a power of
    two (or under 16). Worked with the batch N on the LANES throughout,
    products and all, as multiply-adds: blocks of 16 or 32 rows as the
    trailing dimensions of an array are padded to the 128 lanes of a
    tile (a [8192, 4, 16, 4, 16] float32 view of 134 MB of chunks took
    1 GB of the chip), and these products are a thousandth of the
    rule's work."""
    n, c, _ = a.shape
    size = min(c, _BASE)
    a = jnp.moveaxis(a, 0, -1)                       # [C, C, N]

    def blocks(of):
        # [P, of, P, of, N]: block row, row, block column, column
        return a.reshape(c // of, of, c // of, of, n)

    diag = blocks(size)
    inv = jnp.stack([
        _substitute(diag[i, :, i]) for i in range(c // size)
    ])                                               # [P, m, m, N]
    while size < c:
        # [[T11, 0], [T21, T22]] of each pair of neighbours,
        # T21 = −T22 A21 T11
        pair = blocks(2 * size)
        a21 = jnp.stack([
            pair[i, size:, i, :size] for i in range(c // (2 * size))
        ])
        t11, t22 = inv[0::2], inv[1::2]
        t21 = -_product(t22, _product(a21, t11))
        inv = jnp.concatenate(
            [
                jnp.concatenate([t11, jnp.zeros_like(t11)], axis=2),
                jnp.concatenate([t21, t22], axis=2),
            ],
            axis=1,
        )
        size *= 2
    return jnp.moveaxis(inv[0], -1, 0)


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^{-1}`` for a [..., C, C] float32 that is STRICTLY lower
    triangular (what lies on or above the diagonal is the caller's to
    have zeroed), C a power of two or under 16."""
    return _inverse_of(a.reshape((-1,) + a.shape[-2:])).reshape(a.shape)


def _inverse_fwd(a):
    t = unit_lower_inverse(a)
    return t, t


def _inverse_bwd(t, dt):
    # d(I + a)^{-1} = −T da T: the cotangent takes T's transpose
    tt = jnp.swapaxes(t, -1, -2)
    da = -jnp.matmul(
        jnp.matmul(tt, dt, precision=_HIGHEST), tt, precision=_HIGHEST
    )
    c = t.shape[-1]
    return (jnp.where(jnp.tril(jnp.ones((c, c), bool), -1), da, 0.0),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _products(dtype):
    """``einsum`` for the rule's products on operands of ``dtype``,
    summed in float32: one MXU pass on bf16 operands; on float32
    operands three passes of bf16 pieces (``Precision.HIGH``), since one
    pass would round them to bf16 first."""
    precision = jax.lax.Precision.HIGH if dtype == F32 else None
    return functools.partial(
        jnp.einsum, preferred_element_type=F32, precision=precision
    )


def _chunk_operands(q, k, v, g, beta, chunk):
    """What a chunk brings to the scan, from whole chunks. q, k
    [B, N, C, Hk, Dk], v [B, N, C, Hk, R, Dv], g and beta
    [B, N, C, Hk, R] float32. Returns (w [B, N, C, Hk, R, Dk], u
    [B, N, C, Hk, R, Dv], attn [B, N, Hk, R, C, C]) in v's dtype and
    gamma [B, N, C, Hk, R] float32."""
    dtype = v.dtype
    dot = _products(dtype)
    gamma = jnp.cumsum(g, axis=2)
    kk = dot("bnikd,bnjkd->bnkij", k, k)
    qk = dot("bnikd,bnjkd->bnkij", q, k)
    gamma_t = jnp.moveaxis(gamma, 2, -1)             # [B, N, Hk, R, C]
    beta_t = jnp.moveaxis(beta, 2, -1)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # the difference first, then the exponential
    decay = jnp.exp(jnp.where(
        lower, gamma_t[..., :, None] - gamma_t[..., None, :], -jnp.inf
    ))                                               # [B, N, Hk, R, C, C]
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a = jnp.where(
        strict, beta_t[..., :, None] * kk[:, :, :, None] * decay, 0.0
    )
    t = unit_lower_inverse(a)
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


def _chunked(q, k, v, g, beta, chunk, stretch):
    """Whole stretches of ``stretch`` tokens, each of whole chunks, one
    after another (``lax.scan``), each under its own
    ``jax.checkpoint``: see the module's docstring. Shapes as
    ``_stretch``'s."""
    b, s, hk, dk = k.shape
    r, dv = v.shape[3:]
    start = jnp.zeros((b, hk, r, dk, dv), F32)
    if s == stretch:
        return _stretch(start, q, k, v, g, beta, chunk)[1]

    def lead(t):
        # [B, S, ...] -> [stretches, B, stretch, ...]
        return jnp.moveaxis(
            t.reshape((b, s // stretch, stretch) + t.shape[2:]), 1, 0
        )

    one = jax.checkpoint(
        lambda state, operands: _stretch(state, *operands, chunk)
    )
    _, o = jax.lax.scan(one, start, tuple(map(lead, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1).reshape(b, s, hk, r, dv)


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64,
                     stretch: int = 2048):
    """The rule over a sequence. q, k [B, S, Hk, Dk] (the caller's to
    have normed and scaled), v [B, S, Hv, Dv] with Hv a multiple of Hk
    (key head j serves value heads R j .. R j + R − 1), g [B, S, Hv]
    float32, the log-decay (<= 0), beta [B, S, Hv] float32 in (0, 1).
    Returns o [B, S, Hv, Dv] in v's dtype. ``chunk`` is a power of two
    (or under 16) and ``stretch`` a multiple of it; neither is a size of
    the model."""
    b, s, hk, _ = k.shape
    hv, dv = v.shape[2:]
    if hv % hk:
        raise ValueError(f"{hv} value heads are not shared by {hk} key heads")
    if chunk & (chunk - 1) and chunk > _BASE:
        raise ValueError(f"a chunk of {chunk} tokens is no power of two")
    if stretch % chunk:
        raise ValueError(
            f"a stretch of {stretch} tokens is not whole chunks of {chunk}"
        )
    r = hv // hk
    dtype = v.dtype  # the products' operands: see ``_products``
    q, k = q.astype(dtype), k.astype(dtype)
    g, beta = g.astype(F32), beta.astype(F32)
    # whole chunks, and whole stretches where there are several
    stretch = min(stretch, s + -s % chunk)
    pad = -s % stretch
    if pad:
        # g = 0, β = 0, k = 0: see the module's docstring
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta)
        )
    o = _chunked(
        q, k, v.reshape(b, s + pad, hk, r, dv),
        g.reshape(b, s + pad, hk, r), beta.reshape(b, s + pad, hk, r), chunk,
        stretch,
    ).reshape(b, s + pad, hv, dv)
    return o[:, :s] if pad else o
