"""Mamba-1's selective scan: a state of N numbers a channel whose decay
depends on the channel AND the state,

    s_t[c, n] = exp(Δ_t[c] A[c, n]) s_{t-1}[c, n] + Δ_t[c] u_t[c] B_t[n]
    y_t[c]    = Σ_n C_t[n] s_t[c, n]                        (s_0 = 0)

with Δ_t > 0 and A < 0. Mamba-2's matmul form (``ops/ssd.py``: one score
block ``C Bᵀ`` a group, one scalar decay a head) does not exist here: the
quadratic form would need a decay ``[T, T, C, N]``. The recurrence is
vector work, C · N multiply-adds a token.

The sequence goes in chunks of ``chunk`` tokens and the state ``[B, N,
C]`` (channels on the lanes) is carried from chunk to chunk; inside a
chunk the tokens go one after another (``lax.scan``) on that carried
tile. No array ``[B, S, C, N]`` exists at any point: the largest is one
chunk's states ``[chunk, B, N, C]``, in the backward. A length no chunk
divides is PADDED at its end with tokens of Δ = 0 and u = 0, which leave
the state as it was (decay 1, input 0) and whose outputs are cut off
again: exact, since the scan is causal.

Δ, A, every decay, the state and every sum are float32 whatever the
compute dtype: a decay multiplies thousands of times.

The derivative is written by hand (``jax.custom_vjp``). Its residuals
are the operands and the state each chunk STARTS from (``[S / chunk, B,
N, C]``: 64 × 327 KB a layer at 8,192 tokens, chunks of 128, 5,120
channels of 16 states). The backward walks the chunks last to first;
in each it remakes the chunk's states from its start (one forward of
the chunk) and then walks the chunk's tokens last to first with the
state's cotangent ``g_t = C_t dy_t + a_{t+1} g_{t+1}`` carried:

    dC_t[n] = Σ_c dy_t[c] s_t[c, n]
    dB_t[n] = Σ_c g_t[c, n] Δ_t[c] u_t[c]
    du_t[c] = Δ_t[c] Σ_n g_t[c, n] B_t[n]
    dΔ_t[c] = Σ_n g_t[c, n] (a_t s_{t-1} A)[c, n] + u_t[c] Σ_n g_t[c, n] B_t[n]
    dA[c, n] = Σ_t g_t[c, n] (a_t s_{t-1})[c, n] Δ_t[c]

so a step under ``remat: full`` runs the recurrence four times (forward,
the layer's remade forward, the backward's remade chunk, the walk back),
not the three forwards a backward that differentiating the loop would.

One function, two bodies of this one algorithm, chosen by what the code
sees (``pallas_selective_scan.tile``): on a TPU (or interpreted), on one
device, with channels in whole blocks of 1,024, states in eights and a
chunk of whole eights, the Pallas kernels ``sscan_fwd`` / ``sscan_bwd``
(``ops/pallas_selective_scan.py``: the state in VMEM from a chunk's
first token to its last, the backward's chunk of states remade into
VMEM) — what the Jamba cell's thirteen layers run since PR 54; anywhere
else — the CPU, tier-1's small widths, a mesh of several devices — the
XLA body below, through this module's own ``jnp``. Both keep the same
residuals and the same float32 mathematics; ``ssm1.scan_in_kernel``
says which one a program took.
"""

import functools

import jax
import jax.numpy as jnp

from dlrover_tpu.observability.tracing import set_counter
from dlrover_tpu.ops import pallas_selective_scan

F32 = jnp.float32

# Tokens a chunk (both bodies), and tokens unrolled into one iteration of
# the XLA body's inner loop (XLA fuses their updates). The XLA body's
# sweep, which is the fallback's since PR 54 (the kernels' own is in
# ``ops/pallas_selective_scan.py``), on a v5e at the Jamba2-3B cell's
# size, one call at [1, 8192, 5120] x 16 states, float32 operands (my chip
# runs, PR 53; ms forward / forward and backward, the smallest of five;
# both sweeps read the same to 0.1 ms):
#   chunk  64: unroll 1  7.19 / 20.47   unroll 4  6.20 / 20.16
#   chunk 128: unroll 1  7.00 / 19.71   unroll 4  5.94 / 19.50
#   chunk 256: unroll 1  6.85 / 33.08   unroll 2  6.80 / 37.87
#              unroll 4  5.91 / 19.73   unroll 8  6.71 / 21.75
#              unroll 16 7.18 / 32.24
#   chunk 512: unroll 1  6.78 / 32.48
# A token costs 0.7-0.9 us forward (the 327 KB tile read and written at
# the memory's rate), so the chunk moves the backward alone: its remade
# states ([chunk, B, N, C] float32, 42 MB at 128) and how the compiler
# lays the walk back out. The residual is one state a chunk, 21 MB a
# layer at 128.
SCAN_CHUNK = 128
SCAN_UNROLL = 4


def _token(inp, a):
    """One token's operands in float32, its decay ``exp(Δ A)`` [B, N,
    C] and its input ``Δ u Bᵀ`` [B, N, C] among them: (u [B, C], Δ [B,
    C], B [B, N, 1], C [B, N, 1], decay, input)."""
    u_t, d_t, b_t, c_t = (t.astype(F32) for t in inp)
    b_t, c_t = b_t[:, :, None], c_t[:, :, None]
    return (
        u_t, d_t, b_t, c_t, jnp.exp(d_t[:, None] * a),
        (d_t * u_t)[:, None] * b_t,
    )


def _chunk_forward(state, chunk_in, a):
    """One chunk from ``state`` [B, N, C]. ``chunk_in`` = (u, Δ, B, C)
    with time leading: [T, B, C], [T, B, C], [T, B, N], [T, B, N]; ``a``
    [N, C]. Returns (the state after it, y [T, B, C] float32)."""

    def token(s, inp):
        *_, c_t, decay, new = _token(inp, a)
        s = decay * s + new
        return s, jnp.sum(c_t * s, axis=1)

    return jax.lax.scan(token, state, chunk_in, unroll=SCAN_UNROLL)


def _chunk_states(state, chunk_in, a):
    """The state every token of the chunk STARTS from, [T, B, N, C]."""

    def token(s, inp):
        *_, decay, new = _token(inp, a)
        return decay * s + new, s

    return jax.lax.scan(token, state, chunk_in, unroll=SCAN_UNROLL)[1]


def _chunk_backward(carry, chunk_in, a):
    """One chunk's cotangents. ``carry`` = (the cotangent of the state
    the chunk ENDS in, already through the next token's decay, [B, N,
    C]; dA so far [B, N, C]); ``chunk_in`` = (u, Δ, B, C, dy, the
    chunk's starting state)."""
    *operands, dy, start = chunk_in
    before = _chunk_states(start, tuple(operands), a)

    def token(carry, inp):
        later, d_a = carry
        *ops, dy_t, s_prev = inp
        u_t, d_t, b_t, c_t, decay, new = _token(ops, a)
        dy_t = dy_t.astype(F32)[:, None]
        kept = decay * s_prev                      # a_t s_{t-1}
        g = c_t * dy_t + later
        gb = jnp.sum(g * b_t, axis=1)              # [B, C]
        g_kept = g * kept
        out = (
            gb * d_t,                                    # du [B, C]
            jnp.sum(g_kept * a, axis=1) + gb * u_t,      # dΔ [B, C]
            jnp.sum(g * (d_t * u_t)[:, None], axis=2),   # dB [B, N]
            jnp.sum(dy_t * (kept + new), axis=2),        # dC [B, N]
        )
        return (decay * g, d_a + g_kept * d_t[:, None]), out

    return jax.lax.scan(
        token, carry, (*operands, dy, before), reverse=True,
        unroll=SCAN_UNROLL,
    )


def _chunked(t, chunk):
    """[B, S, X] -> [S / chunk, chunk, B, X]: time leading, in chunks."""
    t = jnp.moveaxis(t, 1, 0)
    return t.reshape(t.shape[0] // chunk, chunk, *t.shape[1:])


def _whole(t):
    """``_chunked``'s inverse."""
    return jnp.moveaxis(t.reshape(-1, *t.shape[2:]), 0, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(u, delta, a, b, c, chunk):
    return _scan_fwd(u, delta, a, b, c, chunk)[0]


def _scan_fwd(u, delta, a, b, c, chunk):
    bsz, _, ch = u.shape
    a_t = a.T                                     # [N, C]: C on the lanes
    operands = tuple(_chunked(t, chunk) for t in (u, delta, b, c))

    def one(state, chunk_in):
        after, y = _chunk_forward(state, chunk_in, a_t)
        return after, (y.astype(u.dtype), state)

    _, (y, starts) = jax.lax.scan(
        one, jnp.zeros((bsz, a.shape[1], ch), F32), operands
    )
    return _whole(y), (u, delta, a, b, c, starts)


def _scan_bwd(chunk, residuals, dy):
    u, delta, a, b, c, starts = residuals
    a_t = a.T
    operands = tuple(_chunked(t, chunk) for t in (u, delta, b, c, dy))
    zero = jnp.zeros(starts.shape[1:], F32)
    (_, d_a), (du, d_delta, db, dc) = jax.lax.scan(
        lambda carry, chunk_in: _chunk_backward(carry, chunk_in, a_t),
        (zero, zero), (*operands, starts), reverse=True,
    )
    return (
        _whole(du).astype(u.dtype), _whole(d_delta).astype(delta.dtype),
        jnp.sum(d_a, axis=0).T.astype(a.dtype),
        _whole(db).astype(b.dtype), _whole(dc).astype(c.dtype),
    )


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(u, delta, a, b, c, chunk: int = SCAN_CHUNK, mesh=None):
    """The scan over a sequence. u [B, S, C]; delta [B, S, C] float32,
    positive (after the softplus); a [C, N] float32, negative; b and c
    [B, S, N]. Returns y [B, S, C] in u's dtype (the skip ``D u`` and
    the gate are the caller's). ``chunk``: tokens between two carried
    states (at most the sequence). One function, two bodies, chosen from
    what it sees (``pallas_selective_scan.tile``; ``mesh`` is the mesh
    the operands live on, if any): the Pallas kernels, or the XLA body
    above."""
    s = u.shape[1]
    chunk = min(chunk, s)
    pad = -s % chunk
    in_kernel = pallas_selective_scan.tile(
        s + pad, u.shape[2], a.shape[1], chunk, mesh
    )
    # the chunk the scan runs and which body took it. Trace time, values
    set_counter("ssm1.scan_chunk", chunk)
    set_counter("ssm1.scan_in_kernel", int(in_kernel))
    scan = pallas_selective_scan.sscan if in_kernel else _scan
    if pad:
        # Δ = 0: a decay of 1 and no input; see the module's docstring
        u, delta, b, c = (
            jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (u, delta, b, c)
        )
    with jax.named_scope("ssm1.scan"):
        y = scan(u, delta.astype(F32), a.astype(F32), b, c, chunk)
    return y[:, :s] if pad else y
