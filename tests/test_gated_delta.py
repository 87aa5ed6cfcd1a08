"""The chunked gated delta rule (``ops/gated_delta.py``) against its
definition, the per-token recurrence: values and gradients in all of q,
k, v, g and β, at several chunk sizes, at a length that is no multiple
of the chunk, with fast-forgetting heads; the triangular inverse and
its hand-written derivative against ``jnp.linalg``. And the op's second
body, the Pallas kernels (``ops/pallas_gated_delta.py``), interpreted:
held to the XLA body AND to the recurrence, values and the five
gradients, on float32 and on bf16 operands; the counter that says which
body a model's linear layers took."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import gated_delta as gd
from dlrover_tpu.ops import pallas_attention

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


# (C, matrices side by side, rows of the batch): the XLA body's inverse,
# one matrix a row, at a chunk under the base block, of a block exactly,
# of one merge, of two and of three; then the kernel's
# (``pallas_gated_delta.inverse``, interpreted; 0 rows: the XLA body's),
# chunks of 64 with R = 1, 2 and 3 matrices side by side on the lanes,
# the batch padded to a grid step's 128 rows and over two grid steps
INVERSE_CASES = [
    (8, 1, 0), (16, 1, 0), (32, 1, 0), (128, 1, 0), (64, 1, 0),
    (64, 1, 6), (64, 2, 6), (64, 3, 5), (64, 2, 130),
]


def _side_by_side(x):
    """[N, P, C, C] -> [N, C, P C]: matrix p as columns [C p, C p + C)."""
    n, p, c, _ = x.shape
    return jnp.moveaxis(x, 1, 2).reshape(n, c, p * c)


@pytest.mark.parametrize(
    "c,side,rows", INVERSE_CASES,
    ids=[f"{c}x{p}" + f"-kernel-{n}" * bool(n) for c, p, n in INVERSE_CASES],
)
def test_unit_lower_inverse_and_its_derivative(c, side, rows, monkeypatch):
    """Against ``jnp.linalg``. The kernel's inverse — P matrices side by
    side on the last axis ([N, C, P C]: how the rules' kernels take T
    and hand back its cotangent) — also against ``unit_lower_inverse``
    of the same matrices one a row, inverse and derivative."""
    n = rows or 6
    a = jnp.tril(jax.random.normal(jax.random.key(c), (n, side, c, c)), -1)
    a = a * (0.8 / c ** 0.5)
    w = jax.random.normal(jax.random.key(1), a.shape)
    want = jnp.linalg.inv(jnp.eye(c) + a)
    by_jax = jax.grad(
        lambda a: (jnp.linalg.inv(jnp.eye(c) + jnp.tril(a, -1)) * w).sum()
    )(a)
    scale = float(jnp.max(jnp.abs(by_jax)))
    got = inverse(a)
    got_grad = jax.jit(jax.grad(lambda a: (inverse(a) * w).sum()))(a)
    if rows:
        monkeypatch.setattr(pallas_attention, "INTERPRET", True)
        in_kernel = jax.jit(lambda a: gd._inverse(a, True))
        assert "name=tri_inverse" in str(
            jax.make_jaxpr(in_kernel)(_side_by_side(a))
        )
        each, each_grad = _side_by_side(got), _side_by_side(got_grad)
        want, by_jax = _side_by_side(want), _side_by_side(by_jax)
        got = in_kernel(_side_by_side(a))
        got_grad = jax.jit(jax.grad(
            lambda a: (in_kernel(a) * _side_by_side(w)).sum()
        ))(_side_by_side(a))
        assert got.shape == (n, c, side * c)
        # the same substitution, the same merges: float32's last bits
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(each), rtol=2e-6, atol=2e-6
        )
        np.testing.assert_allclose(
            np.asarray(got_grad) / scale, np.asarray(each_grad) / scale,
            atol=2e-6,
        )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(got_grad) / scale, np.asarray(by_jax) / scale, atol=1e-4
    )
    # nothing on or above a matrix's diagonal
    upper = jnp.tile(jnp.triu(jnp.ones((c, c), bool)), (1, side))
    if not rows:
        upper = upper[:, :c]
    assert not np.asarray(jnp.where(upper, got_grad, 0.0)).any()


def test_shapes_that_fit_nothing_are_refused_by_name():
    q, k, v, g, beta = _inputs(32, hk=2, r=2)
    with pytest.raises(ValueError, match="no power of two"):
        rule(q, k, v, g, beta, chunk=48)
    with pytest.raises(ValueError, match="not whole chunks"):
        gd.gated_delta_rule(q, k, v, g, beta, chunk=16, stretch=40)
    with pytest.raises(ValueError, match="not shared by"):
        rule(q, k, v[:, :, :3], g[..., :3], beta[..., :3])


# --- the Pallas kernels, interpreted -----------------------------------

F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture
def interpreted(monkeypatch):
    """The kernels where a TPU would run them, by the Pallas interpreter
    (a trace made before the switch is no trace of the kernels: every
    test under it jits its own functions)."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)


def _identical_keys(s):
    """``test_identical_keys_stay_finite_and_exact``'s chunk at the
    kernels' widths: one key, β near 1, no decay."""
    k = jnp.broadcast_to(jnp.eye(128)[0], (1, s, 1, 128))
    v = jax.random.normal(jax.random.key(5), (1, s, 1, 128))
    return k, k, v, jnp.zeros((1, s, 1)), jnp.full((1, s, 1), 0.999)


def _wide(s, **kw):
    return _inputs(s, b=1, dk=128, dv=128, **kw)


# R = 2 value heads a key head (T [.., 64, 128]: the two side by side on
# the lanes), whose fourth head's γ passes −100 inside a chunk of 64;
# three chunks, so that the state and its cotangent cross visits twice; a
# length that is no multiple of the chunk
KERNEL_CASES = {
    "three-chunks": lambda: _wide(192),
    "padded": lambda: _wide(150, hk=1),
    "one-chunk-two-batches": lambda: _inputs(64, hk=1, dk=128, dv=128),
    "identical-keys": lambda: _identical_keys(128),
    # one value head a key head (T a matrix a row, the form before the
    # heads lay side by side) and four (two tiles of lanes)
    "one-value-head": lambda: _wide(192, hk=2, r=1),
    "four-value-heads": lambda: _wide(128, hk=1, r=4),
}


def _value_and_grads(fn, args, w):
    """One program: the value is the forward the gradients' rule runs
    (``_kernel_rule_fwd`` IS the rule), not a second compile of it."""

    def loss(*a):
        o = fn(*a).astype(F32)
        return (o * w).sum(), o

    (_, o), grads = jax.jit(
        jax.value_and_grad(loss, range(5), has_aux=True)
    )(*args)
    return o, [g.astype(F32) for g in grads]


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernels_are_the_xla_body_and_the_recurrence(
    interpreted, case, dtype
):
    """Values and all five gradients. Float32 operands at the tolerance
    the XLA body is held to the recurrence (on the CPU that body's
    products are float32 whole; the kernels' are three passes of bf16
    pieces, as on the chip); bf16 operands at bf16's."""
    q, k, v, g, beta = KERNEL_CASES[case]()
    args = (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)
    w = jax.random.normal(jax.random.key(9), v.shape)
    assert gd.in_kernels(128, 128)
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
    tol = 2e-5 if dtype == F32 else 2e-2
    if case == "identical-keys":
        # a chunk of ones under the diagonal amplifies a product's
        # rounding (the XLA body is held at 1e-4 there), and g's
        # cotangent is what is left of terms a thousand times its size:
        # it is held on β's scale
        tol = max(tol, 5e-4)
    assert got.shape == v.shape and np.isfinite(np.asarray(got)).all()
    if case == "identical-keys" and dtype == BF16:
        # rounded to bf16 a dozen times, that chunk is no number to hold
        # anything to (the XLA body stands 0.1 from the recurrence)
        assert all(np.isfinite(np.asarray(g)).all() for g in got_grads)
        return
    scale = float(jnp.max(jnp.abs(want)))
    for other in (xla, want):
        np.testing.assert_allclose(
            np.asarray(got) / scale, np.asarray(other) / scale, atol=tol
        )
    for name, a, b, c in zip("qkvgβ", got_grads, xla_grads, want_grads):
        scale = float(jnp.max(jnp.abs(c)))
        assert scale > 0, name
        if case == "identical-keys" and name == "g":
            scale = float(jnp.max(jnp.abs(want_grads[4])))
        for other in (b, c):
            np.testing.assert_allclose(
                np.asarray(a) / scale, np.asarray(other) / scale, atol=tol,
                err_msg=name,
            )


@pytest.mark.parametrize(
    "why", ["other-widths", "several-devices", "another-chunk"]
)
def test_fallback_is_the_xla_body_bit_for_bit(interpreted, why):
    """Where the kernels do not fit — channels off the 128-lane grid, a
    mesh of several devices, a chunk that is not theirs — the rule is
    the XLA body, stretches and all: no kernel in the program, and the
    result of ``_chunked`` to the bit."""
    kw = {}
    if why == "other-widths":
        args = _inputs(128)
    else:
        args = _wide(128, hk=1)
        kw = (
            {"mesh": jax.make_mesh((2,), ("dp",))}
            if why == "several-devices" else {"chunk": 32}
        )
    dk, dv = args[0].shape[-1], args[2].shape[-1]
    assert not gd.in_kernels(dk, dv, **kw)
    fn = lambda *a: gd.gated_delta_rule(*a, stretch=64, **kw)  # noqa: E731
    assert "pallas_call" not in str(jax.make_jaxpr(fn)(*args))
    q, k, v, g, beta = args
    b, s, hk = k.shape[:3]
    body = jax.jit(lambda: gd._chunked(
        q, k, v.reshape(b, s, hk, -1, dv), g.reshape(b, s, hk, -1),
        beta.reshape(b, s, hk, -1), kw.get("chunk", 64), 64,
    ).reshape(v.shape))()
    np.testing.assert_array_equal(
        np.asarray(jax.jit(fn)(*args)), np.asarray(body)
    )


def _outside_kernels(jaxpr):
    """The primitives of a traced program, those inside scans,
    checkpoints and custom derivatives among them, a kernel's body
    not."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _outside_kernels(sub)


def test_kernel_path_has_no_scan_over_chunks(interpreted):
    """The 256-step dependence is the kernels' grid: the traced program
    of the kernel path holds the walk's three kernels and the inverse's
    by name and, outside their bodies, no ``scan`` or ``while``; the XLA
    body's holds both of its scans."""
    args = _wide(256, hk=1)
    loss = lambda *a: gd.gated_delta_rule(*a, stretch=128).sum()  # noqa: E731
    traced = jax.make_jaxpr(jax.grad(loss, range(5)))(*args)
    for name in ("gdn_fwd", "gdn_states", "gdn_bwd", "tri_inverse"):
        assert f"name={name}" in str(traced), name
    assert not {"scan", "while"} & set(_outside_kernels(traced.jaxpr))
    mesh = jax.make_mesh((2,), ("dp",))
    xla = str(jax.make_jaxpr(jax.grad(
        lambda *a: gd.gated_delta_rule(*a, stretch=128, mesh=mesh).sum(),
        range(5),
    ))(*args))
    assert "scan[" in xla and "pallas_call" not in xla
