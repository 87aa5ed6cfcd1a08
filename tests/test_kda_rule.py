"""The delta rule with a decay a key CHANNEL (KDA;
``ops/gated_delta.py``, ``g`` [B, S, Hv, Dk]) against its definition,
the per-token recurrence: values and gradients in all of q, k, v, g and
β, at a chunk that is and is not whole sub-blocks of 16, at a length
that is no multiple of the chunk, with channels whose running sum
passes -100 inside a chunk; a ``g`` equal over a head's channels against
the rule with one decay a head; that no exponent above 0 is taken and
no [C, C, Dk] array of a whole chunk formed; and that the shape of ``g``
alone chooses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import gated_delta as gd

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
    assert not gd.in_kernels(128, 128, per_channel=True)
