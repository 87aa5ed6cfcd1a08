"""The delta rule with a decay a key CHANNEL (KDA;
``ops/gated_delta.py``, ``g`` [B, S, Hv, Dk]) against its definition,
the per-token recurrence: values and gradients in all of q, k, v, g and
β, at a chunk that is and is not whole sub-blocks of 16, at a length
that is no multiple of the chunk, with channels whose running sum
passes -100 inside a chunk; a ``g`` equal over a head's channels against
the rule with one decay a head; that no exponent above 0 is taken and
no [C, C, Dk] array of a whole chunk formed; and that the shape of ``g``
alone chooses. Then the rule's other body, the Pallas kernels
(``ops/pallas_kda.py``), interpreted, against the XLA body and the
recurrence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import gated_delta as gd
from dlrover_tpu.ops import pallas_attention

rule = jax.jit(gd.gated_delta_rule, static_argnames=("chunk", "stretch"))
recurrence = jax.jit(gd.recurrence)


def _inputs(s, b=2, hk=2, r=1, dk=8, dv=8, fast=4.5):
    """Unit keys, queries over sqrt(Dk), a decay a channel from slow
    (e^-0.05 a token) to fast (e^-``fast``: at 4.5 its running sum
    passes -100 inside a chunk of 32 and -280 inside one of 64)."""
    ks = jax.random.split(jax.random.key(3), 5)
    q = jax.random.normal(ks[0], (b, s, hk, dk))
    k = jax.random.normal(ks[1], (b, s, hk, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / dk ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, hk * r, dv))
    rates = jnp.exp(jnp.linspace(-3.0, np.log(fast), hk * r * dk))
    g = -jax.nn.softplus(
        jax.random.normal(ks[3], (b, s, hk * r, dk))
    ) * rates.reshape(hk * r, dk)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hk * r)))
    return q, k, v, g, beta


# (tokens, chunk): a chunk under the sub-block (one diagonal block and
# no matmul), one of a sub-block exactly, chunks of two and four
# sub-blocks, and lengths padded with tokens of g = 0, β = 0
CASES = [(32, 8), (24, 12), (32, 16), (64, 32), (50, 16), (128, 64), (70, 32)]
IDS = [
    "four-of-8", "two-of-12", "two-of-16", "two-of-32", "padded-16",
    "two-of-64", "padded-32",
]


@pytest.mark.parametrize("seq,chunk", CASES, ids=IDS)
def test_chunked_vector_rule_is_the_recurrence(seq, chunk):
    args = _inputs(seq)
    # (a stretch is whole chunks: a chunk of 12 does not divide 1,024)
    got = rule(*args, chunk=chunk, stretch=8 * chunk)
    want = recurrence(*args)
    assert got.shape == want.shape == args[2].shape
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize(
    "seq,chunk", [(24, 12), (64, 32), (50, 16), (128, 64)],
    ids=["two-of-12", "two-of-32", "padded-16", "two-of-64"],
)
def test_chunked_vector_rule_gradient_is_the_recurrences(seq, chunk):
    args = _inputs(seq)
    w = jax.random.normal(jax.random.key(9), args[2].shape)
    got = jax.jit(jax.grad(
        lambda *a: (rule(*a, chunk=chunk, stretch=8 * chunk) * w).sum(),
        range(5),
    ))(*args)
    want = jax.jit(
        jax.grad(lambda *a: (recurrence(*a) * w).sum(), range(5))
    )(*args)
    for name, a, b in zip("qkvgβ", got, want):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, name
        assert a.shape == b.shape, name
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-5,
            err_msg=name,
        )


def test_fast_channels_pass_minus_100_inside_a_chunk_and_stay_finite():
    """The fastest channel forgets e^-4.5 a token: its running sum is
    under -100 at a chunk of 64's 23rd token, where e^{-γ} is past
    float32. Values and the five gradients are finite and the
    recurrence's."""
    args = _inputs(128, b=1)
    gamma = jnp.cumsum(args[3].reshape(1, 2, 64, 2, 8), axis=2)
    assert float(gamma.min()) < -100.0
    w = jax.random.normal(jax.random.key(2), args[2].shape)
    got, grads = jax.jit(jax.value_and_grad(
        lambda *a: (rule(*a, chunk=64) * w).sum(), range(5)
    ))(*args)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda *a: (recurrence(*a) * w).sum(), range(5)
    ))(*args)
    assert np.isfinite(float(got))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name, a, b in zip("qkvgβ", grads, want_grads):
        assert bool(jnp.all(jnp.isfinite(a))), name
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-5,
            err_msg=name,
        )


@pytest.mark.parametrize("seq,chunk,stretch", [(64, 16, 32), (70, 16, 32)],
                         ids=["two-stretches", "three-padded"])
def test_stretches_carry_the_vector_rules_state(seq, chunk, stretch):
    args = _inputs(seq)
    w = jax.random.normal(jax.random.key(9), args[2].shape)
    got, grads = jax.jit(jax.value_and_grad(
        lambda *a: (rule(*a, chunk=chunk, stretch=stretch) * w).sum(),
        range(5),
    ))(*args)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda *a: (recurrence(*a) * w).sum(), range(5)
    ))(*args)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name, a, b in zip("qkvgβ", grads, want_grads):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-5,
            err_msg=name,
        )


@pytest.mark.parametrize("chunk", [8, 64])
def test_equal_channels_are_the_rule_with_one_decay_a_head(chunk):
    """``g`` broadcast over a head's channels: the module's docstring,
    line for line. Held to the scalar path to 1e-6."""
    q, k, v, g, beta = _inputs(128, hk=2, r=2, fast=1.0)
    one = g[..., 0]
    got = rule(q, k, v, jnp.broadcast_to(one[..., None], g.shape), beta,
               chunk=chunk)
    want = rule(q, k, v, one, beta, chunk=chunk)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
    )


def test_shared_keys_are_repeated_for_their_value_heads():
    q, k, v, g, beta = _inputs(32, hk=2, r=2)
    got = rule(q, k, v, g, beta, chunk=16)
    each = rule(
        jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), v, g, beta,
        chunk=16,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(each))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(recurrence(q, k, v, g, beta)),
        rtol=2e-5, atol=2e-5,
    )


def _equations(fn, *args):
    """Every equation of ``fn``'s traced program, those inside scans,
    checkpoints and custom derivatives among them."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


def test_no_exponent_above_zero_and_no_whole_chunk_of_differences():
    """Every ``exp`` of the vector rule's program, forward and backward,
    takes an operand that is <= 0 (checked on the values, fast channels
    included), and no array holds a chunk's [C, C, Dk] differences: the
    largest with two token axes and the channels is a sub-block's
    [16, 16, Dk]."""
    args = _inputs(128, b=1)
    c, dk = 64, args[0].shape[-1]
    loss = lambda *a: gd.gated_delta_rule(*a, chunk=c).sum()  # noqa: E731
    fn = jax.grad(loss, range(5))
    eqns = _equations(fn, *args)
    exps = [e for e in eqns if e.primitive.name == "exp"]
    assert exps
    for e in eqns:
        for var in e.outvars:
            shape = tuple(var.aval.shape)
            assert not (
                shape[-1:] == (dk,) and shape.count(c) >= 2
            ), (e.primitive.name, shape)
    # the operands' values: evaluate the forward's exponents
    seen = []
    real_exp = jnp.exp

    def watched(x):
        seen.append(jnp.max(x))
        return real_exp(x)

    gd.jnp.exp, keep = watched, gd.jnp.exp
    try:
        gd.gated_delta_rule(*args, chunk=c)
    finally:
        gd.jnp.exp = keep
    assert seen and max(float(m) for m in seen) <= 0.0


def test_the_shape_of_g_alone_chooses_and_a_wrong_one_is_refused():
    q, k, v, g, beta = _inputs(32)
    text = str(jax.make_jaxpr(
        lambda *a: gd.gated_delta_rule(*a, chunk=16)
    )(q, k, v, g[..., 0], beta))
    vector = str(jax.make_jaxpr(
        lambda *a: gd.gated_delta_rule(*a, chunk=16)
    )(q, k, v, g, beta))
    assert text != vector
    with pytest.raises(ValueError, match="key channel"):
        gd.gated_delta_rule(q, k, v, g[..., :4], beta)
    # (the plain CPU: no kernel takes the call)
    assert not gd.in_kernels(128, 128, per_channel=True)


# --- the Pallas kernels, interpreted -----------------------------------

F32, BF16 = jnp.float32, jnp.bfloat16
KERNELS = ("kda_pairs", "kda_fwd", "kda_states", "kda_bwd", "kda_pairs_bwd")


@pytest.fixture
def interpreted(monkeypatch):
    """The kernels where a TPU would run them, by the Pallas interpreter
    (a trace made before the switch is no trace of the kernels: every
    test under it jits its own functions)."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)


def _wide(s, **kw):
    """Two heads of 128 key and 128 value channels, the smallest the
    kernels take; the second head's fastest channels forget e^-4.5 a
    token."""
    return _inputs(s, **{"b": 1, "dk": 128, "dv": 128, **kw})


# two chunks, so that the state and its cotangent cross a visit (an even
# count: A, M, T and their cotangents go two chunks to a row of 128
# lanes); three with a length that is no multiple of the chunk (an odd
# count: a chunk a row); two sequences; four chunks a visit, two rows;
# six, three visits of one row
KERNEL_CASES = {
    "two-chunks": lambda: _wide(128),
    "padded-to-three": lambda: _wide(150),
    "two-batches-one-head": lambda: _wide(128, b=2, hk=1),
    "four-chunks-a-visit": lambda: _wide(256, hk=1),
    "six-chunks": lambda: _wide(384, hk=1),
}


def _value_and_grads(fn, args, w):
    def loss(*a):
        o = fn(*a).astype(F32)
        return (o * w).sum(), o

    (_, o), grads = jax.jit(
        jax.value_and_grad(loss, range(5), has_aux=True)
    )(*args)
    return o, [g.astype(F32) for g in grads]


def _held(got, others, tol, names="o"):
    for name, a, *rest in zip(names, got, *others):
        scale = float(jnp.max(jnp.abs(rest[-1])))
        assert scale > 0, name
        assert bool(jnp.all(jnp.isfinite(a))), name
        for other in rest:
            np.testing.assert_allclose(
                np.asarray(a) / scale, np.asarray(other) / scale, atol=tol,
                err_msg=name,
            )


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernels_are_the_xla_body_and_the_recurrence(interpreted, case):
    """Values and all five gradients on float32 operands, at the
    tolerance the XLA body is held to the recurrence (on the CPU that
    body's products are float32 whole; the kernels' are three passes of
    bf16 pieces, as on the chip). The fast channels' running sum passes
    -100 inside every chunk: finite, values and gradients."""
    args = KERNEL_CASES[case]()
    b, s, h, dk = args[3].shape
    pad = -s % 64
    gamma = jnp.cumsum(
        jnp.pad(args[3], ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
            b, -1, 64, h, dk
        ), axis=2,
    )
    assert float(gamma.min()) < -100.0
    w = jax.random.normal(jax.random.key(9), args[2].shape)
    assert gd.in_kernels(128, 128, per_channel=True)
    got, got_grads = _value_and_grads(
        lambda *a: gd.gated_delta_rule(*a), args, w
    )
    # the XLA body through its own entry, as the fallback runs it
    xla, xla_grads = _value_and_grads(
        lambda *a: gd.gated_delta_rule(
            *a, mesh=jax.make_mesh((2,), ("dp",))
        ), args, w,
    )
    want, want_grads = _value_and_grads(gd.recurrence, args, w)
    assert got.shape == args[2].shape
    _held([got], [[xla], [want]], 2e-5)
    _held(got_grads, [xla_grads, want_grads], 2e-5, "qkvgβ")


def test_kernels_on_bf16_operands_are_the_xla_bodys(interpreted):
    """One pass on bf16 operands (g and β stay float32), held to the
    XLA body on the same operands at bf16's tolerance."""
    q, k, v, g, beta = _wide(128, fast=1.0)
    args = (q.astype(BF16), k.astype(BF16), v.astype(BF16), g, beta)
    w = jax.random.normal(jax.random.key(9), v.shape)
    got, got_grads = _value_and_grads(
        lambda *a: gd.gated_delta_rule(*a), args, w
    )
    xla, xla_grads = _value_and_grads(
        lambda *a: gd.gated_delta_rule(
            *a, mesh=jax.make_mesh((2,), ("dp",))
        ), args, w,
    )
    _held([got], [[xla]], 2e-2)
    _held(got_grads, [xla_grads], 2e-2, "qkvgβ")


def test_equal_channels_in_the_kernels_are_the_scalar_rules(interpreted):
    """``g`` equal over a head's channels goes through the VECTOR rule's
    kernels (the shape chooses, never the values) and agrees with the
    scalar rule's, values and the gradient in v."""
    q, k, v, g, beta = _wide(128, fast=1.0)
    one = g[..., 0]
    spread = jnp.broadcast_to(one[..., None], g.shape)
    text = str(jax.make_jaxpr(gd.gated_delta_rule)(q, k, v, spread, beta))
    assert "name=kda_fwd" in text and "name=gdn_fwd" not in text
    got = jax.jit(gd.gated_delta_rule)(q, k, v, spread, beta)
    want = jax.jit(gd.gated_delta_rule)(q, k, v, one, beta)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(
        np.asarray(got) / scale, np.asarray(want) / scale, atol=2e-5
    )


@pytest.mark.parametrize(
    "why", ["interpreted", "off-the-lanes", "several-devices",
            "another-chunk"],
)
def test_what_the_call_sees_chooses_the_vector_rules_body(interpreted, why):
    """``in_kernels(..., per_channel=True)`` is ``pallas_gated_delta.
    tile``'s answer: true interpreted at heads of 128 on one device,
    false off the 128 lanes, on a mesh of several devices and at a chunk
    that is not the kernels' — and the traced program holds the five
    kernels by name and no stretch under a checkpoint, or the XLA body's
    stretches and no kernel."""
    dk, kw = 128, {}
    if why == "off-the-lanes":
        dk = 64
    elif why == "several-devices":
        kw = {"mesh": jax.make_mesh((2,), ("dp",))}
    elif why == "another-chunk":
        kw = {"chunk": 32}
    args = _inputs(128, b=1, hk=1, dk=dk, dv=dk)
    taken = why == "interpreted"
    assert gd.in_kernels(dk, dk, per_channel=True, **kw) == taken
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: gd.gated_delta_rule(*a, stretch=64, **kw).sum(),
        range(5),
    ))(*args))
    for name in KERNELS:
        assert (f"name={name}" in text) == taken, name
    assert ("remat2[" in text) != taken
    assert ("pallas_call" in text) == taken
