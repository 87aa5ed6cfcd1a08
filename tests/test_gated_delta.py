"""The chunked gated delta rule (``ops/gated_delta.py``) against its
definition, the per-token recurrence: values and gradients in all of q,
k, v, g and β, at several chunk sizes, at a length that is no multiple
of the chunk, with fast-forgetting heads; the triangular inverse and
its hand-written derivative against ``jnp.linalg``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import gated_delta as gd

# jitted: eager, the inverse's row-by-row substitution is a dispatch an
# operation
rule = jax.jit(gd.gated_delta_rule, static_argnames=("chunk", "stretch"))
inverse = jax.jit(gd.unit_lower_inverse)
recurrence = jax.jit(gd.recurrence)


def _inputs(s, b=2, hk=2, r=2, dk=8, dv=8):
    """Unit keys, queries over sqrt(Dk), a decay a head from slow
    (e^-0.05 a token) to fast (e^-4.5: its running sum passes -100
    inside a chunk of 32)."""
    ks = jax.random.split(jax.random.key(3), 5)
    q = jax.random.normal(ks[0], (b, s, hk, dk))
    k = jax.random.normal(ks[1], (b, s, hk, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / dk ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, hk * r, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, s, hk * r))) * (
        jnp.exp(jnp.linspace(-3.0, 1.5, hk * r))
    )
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hk * r)))
    return q, k, v, g, beta


CASES = [(32, 8), (64, 32), (50, 16), (128, 64), (70, 32)]
IDS = ["four-of-8", "two-of-32", "padded-16", "two-of-64", "padded-32"]


@pytest.mark.parametrize("seq,chunk", CASES, ids=IDS)
def test_chunked_rule_is_the_recurrence(seq, chunk):
    """A chunk under the inverse's base block, chunks that merge blocks
    once and twice, and lengths padded with tokens of g = 0, β = 0."""
    args = _inputs(seq)
    got = rule(*args, chunk=chunk)
    want = recurrence(*args)
    assert got.shape == want.shape == args[2].shape
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("seq,chunk", [(64, 32), (50, 16)],
                         ids=["two-of-32", "padded-16"])
def test_chunked_rule_gradient_is_the_recurrences(seq, chunk):
    args = _inputs(seq)
    w = jax.random.normal(jax.random.key(9), args[2].shape)
    got = jax.jit(jax.grad(
        lambda *a: (rule(*a, chunk=chunk) * w).sum(), range(5)
    ))(*args)
    want = jax.jit(
        jax.grad(lambda *a: (recurrence(*a) * w).sum(), range(5))
    )(*args)
    for name, a, b in zip("qkvgβ", got, want):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, name
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-5,
            err_msg=name,
        )


@pytest.mark.parametrize("seq,chunk,stretch", [(64, 16, 32), (70, 16, 32)],
                         ids=["two-stretches", "three-padded"])
def test_stretches_carry_the_state_and_its_gradient(seq, chunk, stretch):
    """Several stretches, each under its own checkpoint: the state is
    handed from one to the next, forward and backward; a length that is
    no whole stretch is padded to one."""
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


def test_key_heads_are_repeated_for_their_neighbours():
    """Value heads 2j and 2j + 1 read key head j: the rule on repeated
    q and k, one key head a value head, gives the same."""
    q, k, v, g, beta = _inputs(32)
    got = rule(q, k, v, g, beta, chunk=16)
    each = rule(
        jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), v, g, beta,
        chunk=16,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(each), rtol=1e-6, atol=1e-6
    )


def test_identical_keys_stay_finite_and_exact():
    """A chunk whose keys all repeat, β near 1, no decay: I + A is ones
    below the diagonal, where a product of I + (−A)^(2^j) cancels
    binomials; substitution does not."""
    s, dk = 64, 8
    k = jnp.broadcast_to(jnp.eye(dk)[0], (1, s, 1, dk))
    v = jax.random.normal(jax.random.key(5), (1, s, 1, dk))
    g = jnp.zeros((1, s, 1))
    beta = jnp.full((1, s, 1), 0.999)
    got = rule(k, k, v, g, beta, chunk=64)
    want = recurrence(k, k, v, g, beta)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("c", [8, 16, 32, 128])
def test_unit_lower_inverse_and_its_derivative(c):
    a = jnp.tril(jax.random.normal(jax.random.key(c), (3, 2, c, c)), -1)
    a = a * (0.8 / c ** 0.5)
    want = jnp.linalg.inv(jnp.eye(c) + a)
    np.testing.assert_allclose(
        np.asarray(inverse(a)), np.asarray(want),
        rtol=1e-4, atol=1e-4,
    )
    w = jax.random.normal(jax.random.key(1), a.shape)
    got = jax.jit(jax.grad(lambda a: (inverse(a) * w).sum()))(a)
    by_jax = jax.grad(
        lambda a: (jnp.linalg.inv(jnp.eye(c) + jnp.tril(a, -1)) * w).sum()
    )(a)
    scale = float(jnp.max(jnp.abs(by_jax)))
    np.testing.assert_allclose(
        np.asarray(got) / scale, np.asarray(by_jax) / scale, atol=1e-4
    )
    assert not np.asarray(jnp.triu(got)).any()


def test_shapes_that_fit_nothing_are_refused_by_name():
    q, k, v, g, beta = _inputs(32, hk=2, r=2)
    with pytest.raises(ValueError, match="no power of two"):
        rule(q, k, v, g, beta, chunk=48)
    with pytest.raises(ValueError, match="not whole chunks"):
        gd.gated_delta_rule(q, k, v, g, beta, chunk=16, stretch=40)
    with pytest.raises(ValueError, match="not shared by"):
        rule(q, k, v[:, :, :3], g[..., :3], beta[..., :3])
