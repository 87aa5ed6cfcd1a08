"""Interpret-mode parity for the fused norm kernels (ops/pallas_norm.py).

The kernels only compile on TPU; ``interpret=True`` runs the same
kernel bodies through the pallas interpreter on the CPU mesh, so the
grid/BlockSpec plumbing, the in-kernel f32 statistics, the fused
residual add, and both custom_vjp backward kernels are exercised here
— against the jnp reference that IS the production fallback (and the
decoder's ``_norm`` math).

Tolerances: f32 cases compare at a few ulp (the kernel reduces by
sum/d where the reference uses mean — same value, different op order);
bf16 cases at 1-2 bf16 ulp. The fused-residual summed stream is pinned
BITWISE: it is an input-dtype add in both implementations.

``l2_heads`` (PR 69: every head's L2 norm on the flat layout, what
``decoder._l2_heads`` runs on the chip) is held to that function's jnp
body the same way, as cases of one test.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import pallas_norm


def _ref(x, scale, bias, kind, residual=None):
    eps = pallas_norm.RMS_EPS if kind == "rmsnorm" else pallas_norm.LN_EPS
    return pallas_norm._reference(
        x, scale, bias if kind == "layernorm" else None, kind, eps, residual
    )


def _make(kind, dt, d, with_res, seed=0):
    # GPT-2 XL's width at 1,024 rows (four row blocks of 256: twelve
    # whole lane tiles and a thirteenth half empty), else 32 rows
    lead = (1, 1024) if d == 1600 else (2, 16)
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], lead + (d,), dt)
    s = (1.0 + 0.1 * jax.random.normal(ks[1], (d,))).astype(dt)
    b = (
        (0.1 * jax.random.normal(ks[2], (d,))).astype(dt)
        if kind == "layernorm"
        else None
    )
    res = jax.random.normal(ks[3], lead + (d,), dt) if with_res else None
    return x, s, b, res


@pytest.mark.parametrize("kind", ["rmsnorm", "layernorm"])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
# 256 = clean lanes; 192/100/1600 are widths off the 128 lanes, which
# the kernels take as they are (a block's last tile part empty; 100 is
# the odd case: one tile, 28 lanes of it empty)
@pytest.mark.parametrize("d", [256, 192, 100, 1600])
@pytest.mark.parametrize("with_res", [False, True])
def test_forward_parity(kind, dt, d, with_res):
    tol = 2e-5 if dt == jnp.float32 else 2e-2
    x, s, b, res = _make(kind, dt, d, with_res)
    out_k = pallas_norm.norm(x, s, b, kind, residual=res, interpret=True)
    out_r = _ref(x, s, b, kind, residual=res)
    if with_res:
        np.testing.assert_allclose(
            np.asarray(out_k[0], np.float32),
            np.asarray(out_r[0], np.float32),
            rtol=tol, atol=tol,
        )
        # the summed stream is an input-dtype add in both paths: bitwise
        np.testing.assert_array_equal(
            np.asarray(out_k[1]), np.asarray(out_r[1])
        )
    else:
        np.testing.assert_allclose(
            np.asarray(out_k, np.float32),
            np.asarray(out_r, np.float32),
            rtol=tol, atol=tol,
        )


@pytest.mark.parametrize("kind", ["rmsnorm", "layernorm"])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d", [256, 100, 1600])
@pytest.mark.parametrize("with_res", [False, True])
def test_grad_parity(kind, dt, d, with_res):
    """Backward kernels vs jnp autodiff: dx, dscale, dbias, dres —
    with distinct cotangents on the normed output and the summed
    stream, so the in-kernel gh fold is actually exercised."""
    x, s, b, res = _make(kind, dt, d, with_res, seed=3)

    def loss(fn):
        def go(x, s, b, res):
            o = fn(x, s, b, res)
            if with_res:
                return (o[0] * 1.3).sum() + (o[1] * 0.7).sum()
            return (o * 1.3).sum()

        return go

    k_fn = loss(
        lambda x, s, b, res: pallas_norm.norm(
            x, s, b, kind, residual=res, interpret=True
        )
    )
    r_fn = loss(lambda x, s, b, res: _ref(x, s, b, kind, residual=res))
    argn = [0, 1]
    if kind == "layernorm":
        argn.append(2)
    if with_res:
        argn.append(3)
    gk = jax.grad(k_fn, argnums=tuple(argn))(x, s, b, res)
    gr = jax.grad(r_fn, argnums=tuple(argn))(x, s, b, res)
    tol = 5e-5 if dt == jnp.float32 else 6e-2
    for a, (u, v) in zip(argn, zip(gk, gr)):
        np.testing.assert_allclose(
            np.asarray(u, np.float32),
            np.asarray(v, np.float32),
            rtol=tol, atol=tol,
            err_msg=f"grad argnum {a}",
        )


def _walk(jaxpr, calls, outside):
    """The ``pallas_call`` equations of a jaxpr and the names of every
    primitive outside them, sub-jaxprs included."""
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            calls.append(e)
            continue
        outside.add(e.primitive.name)
        for sub in jax.core.jaxprs_in_params(e.params):
            _walk(sub, calls, outside)


def _blocks(call):
    return [
        tuple(getattr(dim, "block_size", dim) for dim in bm.block_shape)
        for bm in call.params["grid_mapping"].block_mappings
    ]


@pytest.mark.parametrize("d", [1600, 2048])
def test_kernels_take_the_width_as_it_is(d):
    """Forward and ``jax.grad``: nothing but reshapes around the two
    kernels — no ``pad`` before a call and no ``slice`` after it — and
    every block's last dimension the array's own. At 2,048 that is the
    form the kernels always had (rows of 256 by the VMEM budget, the
    vectors ``(1, d)``, the partials ``(1, 1, d)``, the summed stream's
    cotangent an operand of the backward kernel); at 1,600 the budget
    goes by the 1,664 lanes a block occupies and gives the same rows,
    and the stream's cotangent is added outside the kernel."""
    x = res = jax.ShapeDtypeStruct((1, 1024, d), jnp.bfloat16)
    s = b = jax.ShapeDtypeStruct((d,), jnp.bfloat16)

    def fwd(x, s, b, res):
        return pallas_norm.norm(
            x, s, b, "layernorm", residual=res, interpret=True
        )

    def loss(*a):
        out, h = fwd(*a)
        return (out.astype(jnp.float32) * 1.3).sum() + (
            h.astype(jnp.float32) * 0.7
        ).sum()

    for fn, names in (
        (fwd, ["norm_fwd"]),
        (jax.grad(loss, argnums=(0, 1, 2, 3)), ["norm_fwd", "norm_bwd"]),
    ):
        calls, outside = [], set()
        _walk(jax.make_jaxpr(fn)(x, s, b, res).jaxpr, calls, outside)
        assert [c.params["name"] for c in calls] == names
        assert not outside & {"pad", "slice", "dynamic_slice", "gather"}
        for call in calls:
            assert call.params["grid_mapping"].grid == (4,)
            assert all(a.aval.shape[-1] == d for a in call.invars)
            assert all(a.aval.shape[-1] == d for a in call.outvars)
        row, vec, part = (256, d), (1, d), (1, 1, d)
        # x, scale, bias, residual -> out, summed stream
        assert _blocks(calls[0]) == [row, vec, vec, row, row, row]
        if len(calls) == 2:
            # g, h, scale, h's cotangent -> dx, dscale's and dbias's
            # parts; at a width off the lanes h's cotangent is no
            # operand: XLA adds it to dx
            stream = [row] if d % 128 == 0 else []
            assert _blocks(calls[1]) == (
                [row, row, vec] + stream + [row, part, part]
            )


@pytest.mark.parametrize("with_res", [False, True])
def test_lanes_behind_the_width_do_not_reach_the_sums(with_res):
    """The operand as a window of a [n, 1664] array of ones — what lies
    behind column 1,600 is not zero — against the plain operand, values
    and gradients to the bit: a reduction that read a block's last tile
    whole would fail here and not on the chip."""
    x, s, b, res = _make("layernorm", jnp.bfloat16, 1600, with_res, seed=5)

    def windowed(a):
        wide = jnp.ones(a.shape[:-1] + (1664,), a.dtype)
        return wide.at[..., :1600].set(a)[..., :1600]

    def run(x, s, b, res):
        def loss(x, s, b, res):
            o = pallas_norm.norm(
                x, s, b, "layernorm", residual=res, interpret=True
            )
            o = o if with_res else (o,)
            return sum((t.astype(jnp.float32) * 1.3).sum() for t in o), o

        argnums = (0, 1, 2, 3) if with_res else (0, 1, 2)
        return jax.jit(jax.grad(loss, argnums=argnums, has_aux=True))(
            x, s, b, res
        )

    plain = run(x, s, b, res)
    wide = run(
        windowed(x), windowed(s), windowed(b),
        windowed(res) if with_res else None,
    )
    for u, v in zip(jax.tree.leaves(plain), jax.tree.leaves(wide)):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))
    # and the divisor is the true width: a row of ones but for one
    # column has the statistics of 1,600 columns, not of 1,664
    row = jnp.ones((16, 1600), jnp.float32).at[:, 0].set(41.0)
    out = pallas_norm.norm(
        row, jnp.ones((1600,)), jnp.zeros((1600,)), "layernorm",
        interpret=True,
    )
    mean, var = 1.0 + 40.0 / 1600, 40.0**2 / 1600 - (40.0 / 1600) ** 2
    np.testing.assert_allclose(
        np.asarray(out[:, 1]), (1.0 - mean) / np.sqrt(var + 1e-5), rtol=1e-5
    )


def test_unaligned_calls_are_counted_where_they_are_traced():
    """``norm.unaligned_calls``: the call sites traced at a width off
    the 128 lanes — one a ``norm`` call whatever is derived from it,
    none at a whole number of lane tiles, none on the jnp fallback."""
    from dlrover_tpu.observability import tracing

    def count():
        return tracing.counters().get("norm.unaligned_calls")

    def call(d, interpret=True, grad=False):
        x = jnp.ones((2, 16, d), jnp.bfloat16)

        def fn(x):
            return pallas_norm.norm(
                x, jnp.ones((d,)), None, "rmsnorm", interpret=interpret
            ).astype(jnp.float32).sum()

        jax.make_jaxpr(jax.grad(fn) if grad else fn)(x)

    call(2048)
    before = count()
    assert before is not None
    call(2048, grad=True)
    call(1600, interpret=False)  # off the chip: the jnp body
    assert count() == before
    call(1600)
    assert count() == before + 1
    call(100, grad=True)
    assert count() == before + 2


def test_untileable_rows_fall_back():
    """Row counts below the dtype's min sublane tile can't grid — the
    public entry must return the jnp reference, not crash."""
    x = jax.random.normal(jax.random.key(0), (1, 3, 128), jnp.bfloat16)
    s = jnp.ones((128,), jnp.bfloat16)
    out = pallas_norm.norm(x, s, None, "rmsnorm", interpret=True)
    ref = _ref(x, s, None, "rmsnorm")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_cpu_default_is_reference():
    """Without interpret and off-TPU, norm() must be the exact jnp
    reference — the gate that keeps untouched configs bitwise stable."""
    assert not pallas_norm.kernels_available(interpret=False)
    x = jax.random.normal(jax.random.key(1), (2, 8, 64), jnp.float32)
    s = jnp.ones((64,), jnp.float32)
    out = pallas_norm.norm(x, s, None, "rmsnorm", interpret=False)
    ref = _ref(x, s, None, "rmsnorm")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_unknown_kind_raises():
    x = jnp.ones((2, 2, 8))
    with pytest.raises(ValueError, match="unknown norm kind"):
        pallas_norm.norm(x, jnp.ones((8,)), None, "batchnorm")


# --- every head's L2 norm on the flat layout (``l2_heads``) -------------

# what differs from the base case (float32, 2 x 24 tokens of 2 heads of
# 128, scale 1, the default tile): a token count the tile does not
# divide is met by making the block small, since a block of 2 MB holds
# more rows than an interpreted test should
L2_CASES = {
    "scale-1": {},
    "scale-rsqrt-d": {"scale": 128 ** -0.5},
    "rows-no-multiple-of-the-tile": {"tokens": 44, "block_bytes": 16384},
    "a-row-of-zeros": {"zero_row": True},
    "bf16": {"dt": jnp.bfloat16, "scale": 128 ** -0.5},
    "bf16-rows-no-multiple-of-the-tile": {
        "dt": jnp.bfloat16, "tokens": 44, "block_bytes": 16384,
    },
    "eps-1e-3": {"eps": 1e-3, "zero_row": True},
}


def _l2_body(t, scale=1.0, eps=1e-6):
    """``decoder._l2_heads``' jnp body, as it stood before the kernel."""
    t32 = t.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.sum(t32 * t32, -1, keepdims=True) + eps)
    return (t32 * (inv * scale)).astype(t.dtype)


@pytest.mark.parametrize("case", sorted(L2_CASES))
def test_l2_heads_is_the_jnp_body(monkeypatch, case):
    """``l2_heads`` interpreted, on [B, S, H * D], against the jnp body
    on [B, S, H, D]: values and gradients. A row of zeros is the ``eps``
    case (the inverse norm is eps ** -0.5 and the derivative the
    cotangent times it)."""
    how = dict(
        dt=jnp.float32, tokens=24, scale=1.0, eps=1e-6, zero_row=False,
        block_bytes=None,
    )
    how.update(L2_CASES[case])
    if how["block_bytes"]:
        monkeypatch.setattr(
            pallas_norm, "_ROW_BLOCK_BYTES", how["block_bytes"]
        )
    b, s, h, d = 2, how["tokens"], 2, 128
    rows, cols, _ = pallas_norm._fit_heads(b * s, h * d, d, how["dt"])
    assert cols == h * d
    assert bool((b * s) % rows) == bool(how["block_bytes"])
    kx, kc = jax.random.split(jax.random.key(7))
    x = (3.0 * jax.random.normal(kx, (b, s, h * d))).astype(how["dt"])
    if how["zero_row"]:
        x = x.at[1, 5].set(0.0)
    ct = jax.random.normal(kc, (b, s, h * d), jnp.float32)

    def kernel(x):
        return pallas_norm.l2_heads(
            x, d, how["scale"], how["eps"], interpret=True
        )

    def body(x):
        return _l2_body(
            x.reshape(b, s, h, d), how["scale"], how["eps"]
        ).reshape(x.shape)

    def pulled(fn):
        return jax.grad(lambda x: jnp.sum(fn(x).astype(jnp.float32) * ct))(x)

    got, want = kernel(x), body(x)
    assert got.dtype == want.dtype == how["dt"]
    dgot, dwant = pulled(kernel), pulled(body)
    assert dgot.dtype == how["dt"]
    # float32: a few ulp (the sums' order); bf16: one rounding of it
    tol = 1e-6 if how["dt"] == jnp.float32 else 1e-2
    for a, w in ((got, want), (dgot, dwant)):
        a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, w, rtol=tol, atol=tol * np.abs(w).max())


def test_l2_heads_off_the_lanes_is_the_jnp_body_bit_for_bit():
    """``decoder._l2_heads`` on the CPU, and at heads off the 128 lanes
    wherever it runs, is the jnp body to the bit; heads that are no
    whole lanes are refused by the kernel's entry."""
    from dlrover_tpu.models import decoder

    assert not decoder._l2_in_kernel(8) and not decoder._l2_in_kernel(128)
    t = jax.random.normal(jax.random.key(2), (2, 12, 3, 8), jnp.float32)
    for scale in (1.0, 8 ** -0.5):
        np.testing.assert_array_equal(
            np.asarray(decoder._l2_heads(t, scale)),
            np.asarray(_l2_body(t, scale)),
        )
        got = jax.grad(lambda t: jnp.sum(decoder._l2_heads(t, scale) ** 3))(t)
        want = jax.grad(lambda t: jnp.sum(_l2_body(t, scale) ** 3))(t)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="no heads of 8 on the lanes"):
        pallas_norm.l2_heads(t.reshape(2, 12, 24), 8, interpret=True)


def test_l2_heads_in_the_decoder_is_a_view_around_the_kernel(monkeypatch):
    """Interpreted at heads of 128 ``decoder._l2_heads`` keeps its 4-D
    door and runs the kernel on the flat form: the jaxpr holds the two
    Pallas calls and no norm of its own."""
    from dlrover_tpu.models import decoder

    monkeypatch.setattr(pallas_norm, "INTERPRET", True)
    assert decoder._l2_in_kernel(128) and not decoder._l2_in_kernel(64)
    t = jax.random.normal(jax.random.key(4), (1, 16, 2, 128), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(decoder._l2_heads(t, 0.5)), np.asarray(_l2_body(t, 0.5)),
        rtol=1e-6, atol=1e-6,
    )
    jaxpr = jax.make_jaxpr(
        jax.value_and_grad(lambda t: jnp.sum(decoder._l2_heads(t, 0.5) * t))
    )(t)
    calls, primitives = [], set()
    _walk(jaxpr.jaxpr, calls, primitives)
    assert [c.params["name"] for c in calls] == [
        "l2_heads_fwd", "l2_heads_bwd"
    ]
    assert "rsqrt" not in primitives


@pytest.mark.slow
def test_decoder_fused_norm_matches_unfused():
    """End-to-end: a tiny decoder forward+grad with cfg.fused_norm=True
    (kernels in interpret mode) matches the default jnp build within
    f32 tolerance — the wiring in _layer_body/_norm_block, including
    the fused ln2 residual add, agrees with the reference program."""
    from dlrover_tpu.models import decoder, get_config

    prev = pallas_norm.INTERPRET
    pallas_norm.INTERPRET = True
    try:
        cfg_f = get_config("tiny", fused_norm=True, dtype="float32",
                           param_dtype="float32")
        cfg_r = get_config("tiny", fused_norm=False, dtype="float32",
                           param_dtype="float32")
        params = decoder.init(jax.random.key(0), cfg_f)
        tokens = jax.random.randint(jax.random.key(1), (2, 16), 0,
                                    cfg_f.vocab_size)
        batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}

        def loss(cfg):
            def f(p):
                return decoder.loss_fn(p, batch, cfg)[0]

            return f

        lf, gf = jax.value_and_grad(loss(cfg_f))(params)
        lr, gr = jax.value_and_grad(loss(cfg_r))(params)
        np.testing.assert_allclose(float(lf), float(lr), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gr)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
            )
    finally:
        pallas_norm.INTERPRET = prev
