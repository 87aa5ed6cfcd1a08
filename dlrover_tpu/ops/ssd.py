"""Mamba-2's selective state-space scan in its chunked
(state-space-duality) form: as Pallas kernels where they fit, else out
of matmuls that XLA schedules.

For every head h (of a group g(h) that shares B and C), with the time
step Δ_t > 0 and A_h < 0:

    a_t = exp(A_h Δ_t)
    S_t = a_t S_{t-1} + Δ_t x_t B_tᵀ        (head_dim × state)
    y_t = S_t C_t

Cut into chunks of Q tokens (Dao & Gu 2024, section 6), with
``cum_t`` the running sum of A_h Δ within a chunk:

- within a chunk ``Y = (L ∘ C Bᵀ)(Δ ⊙ x)``, ``L_ts = exp(cum_t −
  cum_s)`` for s ≤ t and 0 above: one [Q, Q] score block a group, one
  decay block a head;
- each chunk leaves ONE state ``Σ_s exp(cum_Q − cum_s) Δ_s x_s B_sᵀ``;
  the state a chunk starts from is the earlier chunks' states, each
  decayed by the chunks between (a [chunks, chunks] matrix of decays
  times the stacked states: the recurrence over chunks, unrolled into
  one small matmul in float32);
- that state is read out by ``exp(cum_t) C_t``.

Δ, A, the cumulative log-decays, L and the chunk decays are float32
whatever the compute dtype: decays multiply thousands of times, and a
bf16 ``cum`` is off by whole percents at the end of a chunk. The
products run on operands of the compute dtype and sum in float32.

One ``ssd_scan``, two bodies, chosen from what the call sees
(``kernel_chunk``): on a TPU (or interpreted), on one device, at shapes
the tiles fit — a group's heads filling 128-lane slabs, a state on the
128 grid — the Pallas kernels of ``ops/pallas_ssd.py``; everywhere else
the XLA body below, ``_block_scan``.

The kernels keep in VMEM what the XLA body writes to memory and reads
back — the decay and masked score blocks, Δ ⊙ x, the chunk states — and
carry the state (and, going back, its cotangent) in scratch from chunk
to chunk. The backward remakes a chunk's decay blocks from ``cum`` and
takes the chunks' starting states from a pass of its own; nothing but
the operands is a residual, so the scan runs a forward, a remade
forward, a state pass and a backward a step under ``remat: full`` where
the XLA body runs five forwards' worth (forward, the layer's remat, each
head block's own checkpoint, the backward's two). Their chunk is their
own tile (256 at Nemotron-3's widths); ``chunk`` and ``head_block`` are
the XLA body's. On a v5e at 1 x 8,192 tokens, 128 heads of 64 in 8
groups, state 128, bf16 (ms a call, the whole ``ssd_scan`` with its
layout copies, forward / forward and backward; my chip runs, PR 50):
the XLA body 5.44 / 12.22; the kernels 3.20 / 5.83, of which the
kernels themselves 1.24 / 0.63 / 2.55 (forward / states / backward) and
XLA's cumulative sum, layout copies and the test's own loss the rest. ``pallas_ssd``'s docstring has the
sweeps, and what a kernel costs to trace and lower before it runs —
the budget that chose its form.

In the XLA body ``head_block`` heads go through at a time (``lax.map``,
each block under its own ``jax.checkpoint``), so that L and the masked
scores, [chunks, heads, Q, Q] float32 (512 MiB each for 128 heads at
8,192 tokens), are never whole, forward or backward: a block of 16 heads
(one B/C group of Nemotron-3's) holds 64 MiB of each. The kernels never
hold a block outside VMEM, so ``head_block`` matters to the XLA body
only.

A length that is no multiple of the chunk is PADDED at its end with
tokens of Δ = 0 and x = 0, which leave every state as it was and whose
outputs are cut off again: exact, since the scan is causal.
"""

import dataclasses

import jax
import jax.numpy as jnp

from dlrover_tpu.observability.tracing import set_counter
from dlrover_tpu.ops import pallas_conv, pallas_ssd

F32 = jnp.float32


def _block_scan(x, dt, a, b_mat, c_mat, chunk):
    """One block of heads, whole chunks. x [B, S, G, R, P] (G groups of
    R heads of P channels), dt [B, S, G, R] float32, a [G, R] float32
    (negative), b_mat and c_mat [B, S, G, N]. Returns y [B, S, G, R, P]
    in x's dtype."""
    bsz, s, g, r, p = x.shape
    n = b_mat.shape[-1]
    nc = s // chunk
    dtype = x.dtype
    x = x.reshape(bsz, nc, chunk, g, r, p)
    dt = dt.reshape(bsz, nc, chunk, g, r)
    b_mat = b_mat.reshape(bsz, nc, chunk, g, n)
    c_mat = c_mat.reshape(bsz, nc, chunk, g, n)

    # log-decays: the running sum within each chunk, float32
    cum = jnp.cumsum(dt * a, axis=2)                       # [B,C,Q,G,R]
    last = cum[:, :, -1]                                   # [B,C,G,R]
    xdt = (x.astype(F32) * dt[..., None]).astype(dtype)

    # within a chunk
    scores = jnp.einsum(
        "bcqgn,bcsgn->bcgqs", c_mat, b_mat, preferred_element_type=F32
    )
    cum_t = jnp.moveaxis(cum, 2, -1)                       # [B,C,G,R,Q]
    seg = cum_t[..., :, None] - cum_t[..., None, :]        # [B,C,G,R,Q,Q]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    mixed = (decay * scores[:, :, :, None]).astype(dtype)
    y = jnp.einsum(
        "bcgrqs,bcsgrp->bcqgrp", mixed, xdt, preferred_element_type=F32
    )

    # the state each chunk leaves, and the one each starts from
    to_end = jnp.exp(last[:, :, None] - cum)               # [B,C,Q,G,R]
    left = jnp.einsum(
        "bcsgn,bcsgrp->bcgrpn", b_mat,
        (xdt.astype(F32) * to_end[..., None]).astype(dtype),
        preferred_element_type=F32,
    )                                                      # [B,C,G,R,P,N]
    # between[z, c] = the decay from the end of chunk c to the start of
    # chunk z > c: exp of the sum of the chunks' totals between them
    total = jnp.cumsum(last, axis=1)                       # [B,C,G,R]
    before = total - last                                  # up to z's start
    span = before[:, :, None] - total[:, None, :]          # [B,Z,C,G,R]
    earlier = jnp.tril(jnp.ones((nc, nc), bool), -1)[None, :, :, None, None]
    between = jnp.exp(jnp.where(earlier, span, -jnp.inf))
    start = jnp.einsum(
        "bzcgr,bcgrpn->bzgrpn", between, left,
        precision=jax.lax.Precision.HIGHEST,
    )
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bcqgn,bcgrpn->bcqgrp", c_mat, start.astype(dtype),
        preferred_element_type=F32,
    )
    return y.astype(dtype).reshape(bsz, s, g, r, p)


def _kernel_scan(x, dt, a, b_mat, c_mat, q):
    """The Pallas body (``ops/pallas_ssd.py``) on whole chunks of ``q``
    tokens: XLA makes the running log-decay (float32, within a chunk),
    the kernels the rest, on the operands as they lie — no array with
    heads of 64 channels as its last dimensions is made, which the
    compiler would pad to 128 lanes and copy. The derivative reaches A
    through ``cum``, Δ through it and by its own term."""
    bsz, sp, h, p = x.shape
    cum = jnp.cumsum((dt * a).reshape(bsz, sp // q, q, h), axis=2)
    y = pallas_ssd.scan(
        x.reshape(bsz, sp, h * p), dt, cum.reshape(bsz, sp, h),
        b_mat.reshape(bsz, sp, -1), c_mat.reshape(bsz, sp, -1), q, p,
        b_mat.shape[-1],
    )
    return y.reshape(bsz, sp, h, p)


def kernel_chunk(s: int, heads: int, channels: int, groups: int, state: int,
                 chunk: int, mesh=None):
    """The chunk of the Pallas body for a sequence of ``s`` tokens
    (padded to whole chunks of ``chunk``), or None where ``ssd_scan``
    takes the XLA body: ``pallas_ssd.tile``'s conditions."""
    return pallas_ssd.tile(
        s + -s % chunk, heads // groups, channels, state, mesh
    )


def ssd_scan(x, dt, a, b_mat, c_mat, chunk: int, head_block: int = 0,
             mesh=None):
    """The scan over a sequence. x [B, S, H, P]; dt [B, S, H] float32,
    positive (after the softplus); a [H] float32, negative; b_mat and
    c_mat [B, S, G, N] with H a multiple of G. Returns y [B, S, H, P] in
    x's dtype (the skip ``D x`` is the caller's). One function, two
    bodies, chosen from what it sees (``kernel_chunk``): the Pallas
    kernels at their own chunk, or the XLA body at ``chunk`` with
    ``head_block`` heads at a time (0 = all), a multiple or a divisor of
    H / G."""
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2:]
    per_group = h // g
    pad = -s % chunk
    if pad:
        # Δ = 0: a decay of 1 and no input; see the module's docstring
        widths = ((0, 0), (0, pad))
        x = jnp.pad(x, widths + ((0, 0), (0, 0)))
        dt = jnp.pad(dt, widths + ((0, 0),))
        b_mat = jnp.pad(b_mat, widths + ((0, 0), (0, 0)))
        c_mat = jnp.pad(c_mat, widths + ((0, 0), (0, 0)))
    sp = s + pad
    q = kernel_chunk(s, h, p, g, n, chunk, mesh)
    # which body the program took. Trace time, a value
    set_counter("ssm.scan_in_kernel", int(q is not None))
    if q is not None:
        with jax.named_scope("ssm.scan"):
            y = _kernel_scan(x, dt, a, b_mat, c_mat, q)
        return y[:, :s] if pad else y
    block = head_block or h
    if h % block or (block % per_group and per_group % block):
        raise ValueError(
            f"a block of {block} heads neither divides nor is made of "
            f"the groups of {per_group} of {h} heads"
        )
    if block < per_group:
        # several blocks share a group: each gets its group's B and C
        b_mat = jnp.repeat(b_mat, per_group // block, axis=2)
        c_mat = jnp.repeat(c_mat, per_group // block, axis=2)
        per_group = block
    n_block = h // block
    gb = block // per_group                  # groups in a block
    with jax.named_scope("ssm.scan"):
        if n_block == 1:
            y = _block_scan(
                x.reshape(bsz, sp, gb, per_group, p),
                dt.reshape(bsz, sp, gb, per_group),
                a.reshape(gb, per_group), b_mat, c_mat, chunk,
            )
            y = y.reshape(bsz, sp, h, p)
        else:
            def lead(t, shape):
                # [B, S, blocks, ...] -> blocks first
                return jnp.moveaxis(t.reshape(shape), 2, 0)

            blocks = (
                lead(x, (bsz, sp, n_block, gb, per_group, p)),
                lead(dt, (bsz, sp, n_block, gb, per_group)),
                a.reshape(n_block, gb, per_group),
                lead(b_mat, (bsz, sp, n_block, gb, -1)),
                lead(c_mat, (bsz, sp, n_block, gb, -1)),
            )
            one = jax.checkpoint(
                lambda args: _block_scan(*args, chunk)
            )
            y = jax.lax.map(one, blocks)     # [blocks, B, S, gb, R, P]
            y = jnp.moveaxis(y, 0, 2).reshape(bsz, sp, h, p)
    return y[:, :s] if pad else y


def _conv(x, weight, bias):
    """``causal_conv``'s XLA body: K shifted copies of x padded at its
    start, in float32."""
    k = weight.shape[0]
    s = x.shape[1]
    padded = jnp.pad(x.astype(F32), ((0, 0), (k - 1, 0), (0, 0)))
    w = weight.astype(F32)
    out = bias.astype(F32)
    for j in range(k):
        out = out + padded[:, j:j + s] * w[j]
    return out.astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class Columns:
    """The channels of ``of`` [B, S, wider] from column ``start`` on, as
    many as the taps have: what a mixer hands ``causal_conv`` for x in
    place of the slice ``of[..., start:start + C]``, which on the chip is
    a copy of the array each way before a kernel can take it (168 MB a
    pass at both cells' sizes). The kernels read the in-projection where
    it lies; the XLA body takes the slice."""
    of: jax.Array
    start: int


def causal_conv(x, weight, bias, mesh=None):
    """Depthwise causal conv along the sequence: ``y_t = Σ_j w_j ⊙
    x_{t-K+1+j} + b`` with x before the first token 0. x [B, S, C] or
    ``Columns`` of a wider array, weight [K, C], bias [C]; the taps, the
    bias and every product and sum float32, y [B, S, C] in x's dtype.
    One function, two bodies, chosen from what it sees
    (``pallas_conv.tile``; ``mesh`` is the mesh the operands live on, if
    any): on a TPU (or interpreted), on one device, with the channels
    and their first column on the 128-lane grid, a length of whole token
    blocks and at most 9 taps, the Pallas kernels ``conv_fwd`` /
    ``conv_bwd`` (``ops/pallas_conv.py``: x read where it lies, once a
    pass) — what the Nemotron and Jamba cells' mixers run; anywhere else
    — the CPU, tier-1's small widths, a mesh of several devices — the
    XLA body above. ``ssm.conv_in_kernel`` says which one a program
    took."""
    taps, channels = weight.shape
    wide, start = (x.of, x.start) if isinstance(x, Columns) else (x, 0)
    block = pallas_conv.tile(wide.shape[1], channels, taps, start, mesh)
    # which body the program took. Trace time, a value
    set_counter("ssm.conv_in_kernel", int(block is not None))
    with jax.named_scope("ssm.conv"):
        if block is not None:
            return pallas_conv.conv(wide, weight, bias, block, start)
        if wide is not x:
            x = wide[..., start:start + channels]
        return _conv(x, weight, bias)


def _gated_conv(proj, weight):
    """``gated_conv``'s XLA body: ``_conv`` between two multiplies, in
    float32, one rounding."""
    ch = weight.shape[1]
    gate_in, gate_out, x = (
        proj[..., i * ch:(i + 1) * ch].astype(F32) for i in range(3)
    )
    c = _conv(gate_in * x, weight, jnp.zeros((ch,), F32))
    return (gate_out * c).astype(proj.dtype)


def gated_conv(proj, weight, mesh=None):
    """A GATED depthwise causal conv along the sequence (LFM2's short
    conv): with proj [B, S, 3 C] = [B | C | x], a mixer's in-projection
    as it lies, and weight [K, C],

        z = B ⊙ x;  c_t = Σ_j w_j ⊙ z_{t-K+1+j};  y = C ⊙ c

    z before the first token 0, each row of the batch by itself, no
    bias and no activation; the gates, the taps and every product and
    sum float32, y [B, S, C] in proj's dtype. One function, two bodies,
    chosen from what it sees (``pallas_conv.gated_tile``, as
    ``causal_conv``): the kernels ``gated_conv_fwd`` /
    ``gated_conv_bwd`` (``ops/pallas_conv.py``: the three windows read
    once where they lie, y written once; going back the three
    cotangents written as one array), or the XLA body above. Which one
    a model's trunk took, ``conv.kernel_layers`` says
    (``gated_conv_in_kernel``)."""
    chunk = gated_conv_in_kernel(
        proj.shape[1], *weight.shape, proj.dtype, mesh
    )
    with jax.named_scope("conv.gate"):
        if chunk is not None:
            return pallas_conv.gated_conv(proj, weight, chunk)
        return _gated_conv(proj, weight)


def gated_conv_in_kernel(s: int, taps: int, channels: int, dtype, mesh=None):
    """``pallas_conv.gated_tile``'s answer for ``gated_conv`` over ``s``
    tokens of [B | C | x] in ``dtype``: the kernels' chunk, or None
    where the XLA body runs."""
    return pallas_conv.gated_tile(
        s, channels, taps, jnp.dtype(dtype).itemsize, mesh
    )


def gated_group_norm(y, z, scale, groups: int, eps: float,
                     norm_before_gate: bool = False, gate=jax.nn.silu):
    """``RMSNorm over each of `groups` groups of (y ⊙ silu(z)) ⊙ scale``:
    the gate first, then the norm (Mamba-2's ``norm_before_gate=False``);
    or, ``norm_before_gate``, ``RMSNorm(y) ⊙ scale ⊙ silu(z)`` (the
    gated-delta-rule mixer's order). y and z [B, S, C]; ``scale`` [C],
    or one group's [C / groups] that every group shares; float32
    inside. ``gate`` is the gate's function where it is not silu (the
    KDA mixer's is a sigmoid)."""
    shape = y.shape
    gate = gate(z.astype(F32))
    v = y.astype(F32)
    if not norm_before_gate:
        v = v * gate
    v = v.reshape(shape[:-1] + (groups, shape[-1] // groups))
    v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
    if scale.shape[-1] != shape[-1]:
        v = v * scale.astype(F32)  # one group's, shared
        v = v.reshape(shape)
    else:
        v = v.reshape(shape) * scale.astype(F32)
    if norm_before_gate:
        v = v * gate
    return v.astype(y.dtype)
