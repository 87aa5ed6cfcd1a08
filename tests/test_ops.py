"""Kernel numerics tests (Pallas interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.attention import mha_reference
from dlrover_tpu.ops.quant import dequantize, quantize, quantize_optimizer_state


def _qkv(key, b=2, s=256, h=4, hkv=None, d=64, dtype=jnp.float32):
    hkv = hkv or h
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype)
    k = jax.random.normal(kk, (b, s, hkv, d), dtype)
    v = jax.random.normal(kv, (b, s, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_matches_reference(causal):
    from dlrover_tpu.ops.pallas_attention import _flash_fwd, _stat_rows

    q, k, v = _qkv(jax.random.key(0))
    scale = q.shape[-1] ** -0.5
    out, lse = _flash_fwd(
        q, k, v, causal, scale, block_q=128, block_k=128, interpret=True
    )
    ref = mha_reference(q, k, v, causal=causal, softmax_scale=scale)
    # the kernel's own tiles, a head's value in lane 0; [B, H, S] by view
    assert lse.shape == (q.shape[0] * q.shape[2], q.shape[1], 8)
    lse = _stat_rows(lse, q.shape[0], q.shape[2], 1)
    assert lse.shape == (q.shape[0], q.shape[2], q.shape[1])
    assert np.isfinite(np.asarray(lse)).all()
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_flash_kernel_gqa():
    from dlrover_tpu.ops.pallas_attention import _flash_fwd

    q, k, v = _qkv(jax.random.key(1), h=8, hkv=2)
    scale = q.shape[-1] ** -0.5
    out, _ = _flash_fwd(
        q, k, v, True, scale, block_q=128, block_k=128, interpret=True
    )
    ref = mha_reference(q, k, v, causal=True, softmax_scale=scale)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


@pytest.mark.parametrize("causal,hkv", [(True, 4), (False, 4), (True, 2)])
def test_pallas_backward_matches_reference(causal, hkv):
    """FA2 pallas backward (interpret) == vjp through plain attention,
    including GQA group-summed dk/dv."""
    from dlrover_tpu.ops import pallas_attention as pa

    q, k, v = _qkv(jax.random.key(2), s=256, h=4, hkv=hkv)
    scale = q.shape[-1] ** -0.5
    out, lse = pa._flash_fwd(
        q, k, v, causal, scale, block_q=128, block_k=128, interpret=True
    )
    g = jax.random.normal(jax.random.key(3), out.shape)
    dq, dk, dv, _ = pa._pallas_backward(
        q, k, v, out, lse, g, causal, scale, 128, 128, interpret=True
    )
    ref = lambda q, k, v: jnp.vdot(  # noqa: E731
        mha_reference(q, k, v, causal=causal, softmax_scale=scale), g
    )
    rq, rk, rv = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv),
                               rtol=2e-3, atol=2e-3)


def test_pallas_backward_unequal_seq_lens():
    """Regression: causal sk > sq must not clamp the dkv q-block index
    out of range (jnp.maximum alone could exceed nq-1). Compared against
    the chunked backward, which shares the kernel's mask convention."""
    from dlrover_tpu.ops import pallas_attention as pa

    ks = jax.random.split(jax.random.key(6), 4)
    b, sq, sk, h, d = 2, 128, 256, 2, 32
    q = jax.random.normal(ks[0], (b, sq, h, d))
    k = jax.random.normal(ks[1], (b, sk, h, d))
    v = jax.random.normal(ks[2], (b, sk, h, d))
    scale = d**-0.5
    out, lse = pa._flash_fwd(
        q, k, v, True, scale, block_q=128, block_k=128, interpret=True
    )
    g = jax.random.normal(ks[3], out.shape)
    dq, dk, dv, _ = pa._pallas_backward(
        q, k, v, out, lse, g, True, scale, 128, 128, interpret=True
    )
    rq, rk, rv = pa._chunked_backward(
        q, k, v, out, pa._stat_rows(lse, b, h, 1), g, True, scale, chunk=128
    )
    for a, r in zip((dq, dk, dv), (rq, rk, rv)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), rtol=2e-3, atol=2e-3
        )


def test_pallas_backward_via_custom_vjp(monkeypatch):
    """The full _flash_attention custom_vjp routes through the pallas
    backward when INTERPRET is on."""
    from dlrover_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "INTERPRET", True)
    q, k, v = _qkv(jax.random.key(4), s=256)
    scale = q.shape[-1] ** -0.5
    g = jax.random.normal(jax.random.key(5), q.shape)
    f = lambda q, k, v: jnp.vdot(  # noqa: E731
        pa._flash_attention(q, k, v, None, None, True, scale, 128, 128), g
    )
    fr = lambda q, k, v: jnp.vdot(  # noqa: E731
        mha_reference(q, k, v, causal=True, softmax_scale=scale), g
    )
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3
        )


def test_quant_roundtrip():
    x = jax.random.normal(jax.random.key(0), (333, 57)) * 3.0
    qa = quantize(x)
    assert qa.q.dtype == jnp.int8
    out = dequantize(qa)
    assert out.shape == x.shape and out.dtype == x.dtype
    # blockwise int8: ~1% relative error on the block max scale
    err = np.abs(np.asarray(out - x)).max()
    assert err <= float(jnp.abs(x).max()) / 127.0 + 1e-6


def test_quantized_optimizer_trains():
    import optax

    opt = quantize_optimizer_state(optax.adam(1e-2))
    params = {"w": jnp.ones((128, 64)), "b": jnp.zeros((4,))}
    state = opt.init(params)
    # large leaf quantized, small leaf untouched
    from dlrover_tpu.ops.quant import QuantizedArray

    leaves = jax.tree.leaves(
        state, is_leaf=lambda x: isinstance(x, QuantizedArray)
    )
    assert any(isinstance(leaf, QuantizedArray) for leaf in leaves)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + jnp.sum(p["b"] ** 2)

    for _ in range(3):
        g = jax.grad(loss)(params)
        updates, state = opt.update(g, state, params)
        params = optax.apply_updates(params, updates)
    assert float(loss(params)) < 128 * 64  # moved toward the minimum


def test_quant4_roundtrip():
    x = jax.random.normal(jax.random.key(1), (200, 33)) * 2.0
    qa = quantize(x, bits=4)
    # packed: half the bytes of the 8-bit payload
    assert qa.q.shape[-1] == 128  # BLOCK // 2
    out = dequantize(qa)
    assert out.shape == x.shape and out.dtype == x.dtype
    # blockwise int4: error bounded by scale/2 = blockmax/14
    err = np.abs(np.asarray(out - x)).max()
    assert err <= float(jnp.abs(x).max()) / 14.0 + 1e-6


def test_quant4_exact_levels():
    # values on the int4 grid survive the roundtrip exactly
    x = jnp.array([-7.0, -3.0, 0.0, 1.0, 5.0, 7.0] * 100)
    out = dequantize(quantize(x, bits=4))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=1e-5)


def test_quantized4_optimizer_trains():
    import optax

    opt = quantize_optimizer_state(optax.adam(1e-2), bits=4)
    params = {"w": jnp.ones((128, 64))}
    state = opt.init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2)

    for _ in range(5):
        g = jax.grad(loss)(params)
        updates, state = opt.update(g, state, params)
        params = optax.apply_updates(params, updates)
    assert float(loss(params)) < 128 * 64


@pytest.mark.slow
def test_lowbit_adamw_chunking_is_exact():
    """Streaming in many chunks must be bit-identical to one big chunk."""
    from dlrover_tpu.ops.quant import BLOCK, lowbit_adamw

    params = {"w": jax.random.normal(jax.random.key(0), (40, 512))}
    g = {"w": jax.random.normal(jax.random.key(1), (40, 512))}
    small = lowbit_adamw(1e-2, weight_decay=0.01, chunk_elems=BLOCK * 2)
    big = lowbit_adamw(1e-2, weight_decay=0.01, chunk_elems=1 << 30)
    s1, s2 = small.init(params), big.init(params)
    for _ in range(3):
        u1, s1 = small.update(g, s1, params)
        u2, s2 = big.update(g, s2, params)
    np.testing.assert_array_equal(np.asarray(u1["w"]), np.asarray(u2["w"]))
    np.testing.assert_array_equal(
        np.asarray(s1["m"]["w"].q), np.asarray(s2["m"]["w"].q)
    )
    np.testing.assert_array_equal(
        np.asarray(s1["v"]["w"].scale), np.asarray(s2["v"]["w"].scale)
    )


def test_lowbit_adamw_matches_generic_wrapper():
    """Fused streaming AdamW ≡ dequant-everything wrapper around
    optax.adamw (same blockwise scheme, bounded memory instead)."""
    import optax

    from dlrover_tpu.ops.quant import lowbit_adamw, quantize_optimizer_state

    wd, lr = 0.05, 3e-3
    params = {"w": jax.random.normal(jax.random.key(2), (64, 128))}
    fused = lowbit_adamw(lr, weight_decay=wd)
    ref = quantize_optimizer_state(optax.adamw(lr, weight_decay=wd))
    pf, pr = params, params
    sf, sr = fused.init(pf), ref.init(pr)

    def loss(p):
        return jnp.sum((p["w"] - 1.0) ** 2)

    for _ in range(5):
        uf, sf = fused.update(jax.grad(loss)(pf), sf, pf)
        ur, sr = ref.update(jax.grad(loss)(pr), sr, pr)
        pf = optax.apply_updates(pf, uf)
        pr = optax.apply_updates(pr, ur)
    np.testing.assert_allclose(
        np.asarray(pf["w"]), np.asarray(pr["w"]), rtol=1e-4, atol=1e-6
    )


@pytest.mark.parametrize("bits", [8, 4])
def test_lowbit_adamw_converges(bits):
    import optax

    from dlrover_tpu.ops.quant import QuantizedArray, lowbit_adamw

    opt = lowbit_adamw(1e-1, bits=bits)
    params = {"w": jnp.ones((128, 64)), "b": jnp.zeros((4,))}
    state = opt.init(params)
    assert isinstance(state["m"]["w"], QuantizedArray)
    assert state["m"]["w"].bits == bits
    assert isinstance(state["m"]["b"], jax.Array)  # small leaf stays dense

    def loss(p):
        return jnp.sum(p["w"] ** 2) + jnp.sum(p["b"] ** 2)

    step = jax.jit(opt.update)
    for _ in range(10):
        g = jax.grad(loss)(params)
        updates, state = step(g, state, params)
        params = optax.apply_updates(params, updates)
    assert float(loss(params)) < 0.2 * 128 * 64


def test_make_optimizer_int8_uses_fused_path():
    from dlrover_tpu.train.optimizer import make_optimizer

    opt = make_optimizer(state_dtype="int8", learning_rate=1e-2)
    params = {"w": jnp.ones((128, 64))}
    state = opt.init(params)
    # chain state: (clip, lowbit) — lowbit state is the step/m/v dict
    flat = jax.tree.leaves(
        state, is_leaf=lambda x: hasattr(x, "bits")
    )
    assert any(getattr(x, "bits", None) == 8 for x in flat)
    g = {"w": jnp.full((128, 64), 0.5)}
    updates, state = jax.jit(opt.update)(g, state, params)
    assert jnp.all(jnp.isfinite(updates["w"]))


def test_wsam_converges_and_matches_sam_at_half_gamma():
    import optax

    from dlrover_tpu.train.optimizer import wsam

    def loss(p):
        return jnp.sum((p["w"] - 2.0) ** 2)

    # gamma=0.5 → coef=1 → pure SAM gradient at the perturbed point
    opt = wsam(optax.sgd(0.05), rho=0.01, gamma=0.5)
    params = {"w": jnp.zeros((8,))}
    state = opt.init(params)
    step = jax.jit(opt.update)
    for _ in range(200):  # 100 effective steps (2 phases each)
        g = jax.grad(loss)(params)
        updates, state = step(g, state, params)
        params = optax.apply_updates(params, updates)
    assert float(loss(params)) < 1e-3


def test_wsam_gamma_zero_is_vanilla():
    import optax

    from dlrover_tpu.train.optimizer import wsam

    def loss(p):
        return jnp.sum(p["w"] ** 2)

    opt = wsam(optax.sgd(0.1), rho=0.05, gamma=0.0)
    ref = optax.sgd(0.1)
    params = {"w": jnp.full((4,), 3.0)}
    rparams = {"w": jnp.full((4,), 3.0)}
    state, rstate = opt.init(params), ref.init(rparams)
    for _ in range(40):  # 20 effective steps
        g = jax.grad(loss)(params)
        updates, state = opt.update(g, state, params)
        params = optax.apply_updates(params, updates)
    # one vanilla step per two wsam phases: at gamma=0 the descent applies
    # the cached params-point gradient and undoes the ascent exactly, so
    # the net trajectory IS vanilla sgd
    for _ in range(20):
        rg = jax.grad(loss)(rparams)
        rupd, rstate = ref.update(rg, rstate, rparams)
        rparams = optax.apply_updates(rparams, rupd)
    np.testing.assert_allclose(
        np.asarray(params["w"]), np.asarray(rparams["w"]), rtol=1e-5
    )


def test_wsam_gamma_bounds():
    import optax

    from dlrover_tpu.train.optimizer import make_optimizer, wsam

    with pytest.raises(ValueError):
        wsam(optax.sgd(0.1), gamma=1.0)
    with pytest.raises(ValueError):
        make_optimizer(name="wsam", state_dtype="int8")


def test_make_optimizer_wsam_and_int4():
    from dlrover_tpu.train.optimizer import make_optimizer

    opt = make_optimizer(name="wsam", learning_rate=1e-2)
    params = {"w": jnp.ones((16,))}
    state = opt.init(params)
    g = jax.tree.map(jnp.ones_like, params)
    updates, state = opt.update(g, state, params)
    assert jax.tree.structure(updates) == jax.tree.structure(params)

    opt4 = make_optimizer(state_dtype="int4")
    state4 = opt4.init({"w": jnp.ones((128, 64))})
    from dlrover_tpu.ops.quant import QuantizedArray

    leaves = jax.tree.leaves(
        state4, is_leaf=lambda x: isinstance(x, QuantizedArray)
    )
    assert any(
        isinstance(leaf, QuantizedArray) and leaf.bits == 4
        for leaf in leaves
    )


@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_matches_reference(causal):
    """The chunked flash backward (lse-based) must match autodiff through
    the reference attention — without materializing [S, S]."""
    from dlrover_tpu.ops.pallas_attention import (
        _chunked_backward,
        _flash_fwd,
        _stat_rows,
    )

    q, k, v = _qkv(jax.random.key(2), b=2, s=256, h=4, d=64)
    scale = q.shape[-1] ** -0.5
    out, lse = _flash_fwd(
        q, k, v, causal, scale, block_q=128, block_k=128, interpret=True
    )
    lse = _stat_rows(lse, 2, 4, 1)  # the fallback keeps its [B, H, S] input
    g = jax.random.normal(jax.random.key(3), out.shape, out.dtype)

    dq, dk, dv = _chunked_backward(
        q, k, v, out, lse, g, causal, scale, chunk=64
    )

    def ref(q, k, v):
        return mha_reference(q, k, v, causal=causal, softmax_scale=scale)

    _, vjp = jax.vjp(ref, q, k, v)
    rdq, rdk, rdv = vjp(g)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv), rtol=2e-3, atol=2e-3)


def test_flash_backward_gqa():
    from dlrover_tpu.ops.pallas_attention import (
        _chunked_backward,
        _flash_fwd,
        _stat_rows,
    )

    q, k, v = _qkv(jax.random.key(4), b=2, s=128, h=8, hkv=2, d=32)
    scale = q.shape[-1] ** -0.5
    out, lse = _flash_fwd(
        q, k, v, True, scale, block_q=128, block_k=128, interpret=True
    )
    lse = _stat_rows(lse, 2, 8, 1)
    g = jax.random.normal(jax.random.key(5), out.shape, out.dtype)
    dq, dk, dv = _chunked_backward(q, k, v, out, lse, g, True, scale, chunk=64)

    def ref(q, k, v):
        return mha_reference(q, k, v, causal=True, softmax_scale=scale)

    _, vjp = jax.vjp(ref, q, k, v)
    rdq, rdk, rdv = vjp(g)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv), rtol=2e-3, atol=2e-3)


def test_chunked_backward_with_lse_cotangent():
    """Ring attention differentiates through the flash lse output; the
    chunked backward's g_lse term must match autodiff of (out, lse)."""
    from dlrover_tpu.ops.pallas_attention import (
        _chunked_backward,
        _flash_fwd,
        _stat_rows,
    )

    q, k, v = _qkv(jax.random.key(7), b=2, s=128, h=4, d=32)
    scale = q.shape[-1] ** -0.5
    out, lse = _flash_fwd(
        q, k, v, True, scale, block_q=128, block_k=128, interpret=True
    )
    lse = _stat_rows(lse, 2, 4, 1)
    g_out = jax.random.normal(jax.random.key(8), out.shape, out.dtype)
    g_lse = jax.random.normal(jax.random.key(9), lse.shape, lse.dtype)

    def ref(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.exp(s - lse[..., None])
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return o, lse

    _, vjp = jax.vjp(ref, q, k, v)
    rdq, rdk, rdv = vjp((g_out, g_lse))
    dq, dk, dv = _chunked_backward(
        q, k, v, out, lse, g_out, True, scale, chunk=64, g_lse=g_lse
    )
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv), rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# fp8 delayed-scaling GEMM (ops/fp8.py)
# ---------------------------------------------------------------------------


def test_fp8_dot_close_to_exact():
    from dlrover_tpu.ops import fp8

    x = jax.random.normal(jax.random.key(0), (64, 128)) * 2.0
    w = jax.random.normal(jax.random.key(1), (128, 32)) * 0.5
    state = fp8.init_fp8_state()
    # warm the amax histories so the delayed scales match the data
    for _ in range(2):
        g = jax.grad(
            lambda x, w, s: jnp.sum(fp8.fp8_dot(x, w, s) ** 2),
            argnums=(0, 1, 2),
        )(x, w, state)
        state = g[2]
    out = fp8.fp8_dot(x, w, state)
    exact = x @ w
    # e4m3 has ~2 decimal digits; relative error stays in the few-% band
    rel = float(
        jnp.linalg.norm(out.astype(jnp.float32) - exact)
        / jnp.linalg.norm(exact)
    )
    assert rel < 0.05, rel


def test_fp8_state_rides_the_cotangent():
    from dlrover_tpu.ops import fp8

    x = jax.random.normal(jax.random.key(0), (16, 64)) * 3.0
    w = jax.random.normal(jax.random.key(1), (64, 16))
    state = fp8.init_fp8_state()
    dx, dw, new_state = jax.grad(
        lambda x, w, s: jnp.sum(fp8.fp8_dot(x, w, s)), argnums=(0, 1, 2)
    )(x, w, state)
    # the "state gradient" is the UPDATED state: histories rolled with
    # the observed amaxes, not derivatives
    assert float(new_state["amax_x"][-1]) == pytest.approx(
        float(jnp.max(jnp.abs(x))), rel=1e-6
    )
    assert float(new_state["amax_w"][-1]) == pytest.approx(
        float(jnp.max(jnp.abs(w))), rel=1e-6
    )
    assert float(new_state["amax_g"][-1]) == pytest.approx(1.0)  # dL/dy = 1
    # gradients exist and have the right shapes/dtypes
    assert dx.shape == x.shape and dw.shape == w.shape
    assert jnp.isfinite(dx).all() and jnp.isfinite(dw).all()


def test_fp8_gradients_approximate_exact():
    from dlrover_tpu.ops import fp8

    x = jax.random.normal(jax.random.key(2), (32, 64))
    w = jax.random.normal(jax.random.key(3), (64, 48))
    state = fp8.init_fp8_state()
    for _ in range(2):
        g = jax.grad(
            lambda x, w, s: jnp.sum(fp8.fp8_dot(x, w, s) ** 2),
            argnums=(0, 1, 2),
        )(x, w, state)
        state = g[2]
    dx8, dw8, _ = jax.grad(
        lambda x, w, s: jnp.sum(fp8.fp8_dot(x, w, s) ** 2),
        argnums=(0, 1, 2),
    )(x, w, state)
    dx, dw = jax.grad(
        lambda x, w: jnp.sum((x @ w) ** 2), argnums=(0, 1)
    )(x, w)
    for a, b in ((dx8, dx), (dw8, dw)):
        rel = float(
            jnp.linalg.norm(a.astype(jnp.float32) - b)
            / jnp.linalg.norm(b)
        )
        # e5m2 gradient quantization: coarser than e4m3
        assert rel < 0.15, rel


def test_fp8_strategy_gated_on_hardware():
    from dlrover_tpu.accelerate.device_context import (
        detect_device_context,
        fp8_supported,
    )
    from dlrover_tpu.accelerate.strategy import apply_strategy

    ctx = detect_device_context()
    assert ctx.n_devices >= 1
    assert not fp8_supported()  # CPU test platform has no native fp8
    with pytest.raises(ValueError, match="fp8"):
        apply_strategy([("fp8", {})])
    plan = apply_strategy([("fp8", {"force": True})])
    assert plan.fp8


def test_mixed_adamw_tracks_dense_adamw():
    """bf16 m + int8 nu must track dense AdamW step-for-step within
    quantization tolerance on a toy quadratic."""
    import optax

    from dlrover_tpu.ops.quant import mixed_adamw

    params = {"w": jnp.linspace(-1.0, 1.0, 4096).reshape(16, 256)}
    dense = optax.adamw(1e-2, b1=0.9, b2=0.99, weight_decay=0.01)
    mixed = mixed_adamw(1e-2, b1=0.9, b2=0.99, weight_decay=0.01)
    sd, sm = dense.init(params), mixed.init(params)
    pd = pm = params
    for i in range(5):
        g = jax.tree.map(
            lambda p: p + 0.1 * jnp.sin(i + jnp.arange(p.size, dtype=jnp.float32)).reshape(p.shape),
            pd,
        )
        ud, sd = dense.update(g, sd, pd)
        um, sm = mixed.update(g, sm, pm)
        pd = optax.apply_updates(pd, ud)
        pm = optax.apply_updates(pm, um)
    # blockwise-int8 nu leaves a small tail of outliers where a block's
    # absmax dwarfs an element's variance (known 8-bit-Adam behavior) —
    # require elementwise agreement for >=99.5% and a bounded drift
    close = np.isclose(pm["w"], pd["w"], rtol=0.05, atol=2e-3)
    assert close.mean() > 0.995, close.mean()
    assert float(jnp.abs(pm["w"] - pd["w"]).mean()) < 5e-3


def test_factored_adamw_matrix_and_vector_paths():
    """Factored nu (Adafactor estimator) approximates dense AdamW on
    matrices; vectors/scalars use EXACT nu and must match tightly."""
    import optax

    from dlrover_tpu.train.optimizer import factored_adamw

    params = {
        "w": jnp.ones((256, 512)) * 0.5,   # factored
        "b": jnp.ones((300,)) * 0.5,        # exact nu (vector)
    }
    dense = optax.adamw(1e-2, b1=0.9, b2=0.99, weight_decay=0.0)
    fact = factored_adamw(1e-2, b1=0.9, b2=0.99)
    sd, sf = dense.init(params), fact.init(params)
    pd = pf = params
    rng = np.random.RandomState(0)
    for _ in range(5):
        g = {
            # rank-1-ish gradient so the factored estimator is near-exact
            "w": jnp.asarray(
                np.outer(rng.rand(256) + 0.5, rng.rand(512) + 0.5),
                jnp.float32,
            ),
            "b": jnp.asarray(rng.rand(300) + 0.5, jnp.float32),
        }
        ud, sd = dense.update(g, sd, pd)
        uf, sf = fact.update(g, sf, pf)
        pd = optax.apply_updates(pd, ud)
        pf = optax.apply_updates(pf, uf)
    # vector path: bf16-m noise only
    np.testing.assert_allclose(pf["b"], pd["b"], rtol=2e-2, atol=1e-3)
    # matrix path: factored estimator tolerance
    np.testing.assert_allclose(pf["w"], pd["w"], rtol=0.1, atol=5e-3)
    # state size: factored nu is O(rows+cols), not O(rows*cols)
    v_w = sf[0]["v"]["w"] if isinstance(sf, tuple) else sf["v"]["w"]
    assert v_w["r"].size + v_w["c"].size == 256 + 512


def test_factored_adamw_trains_tiny_model():
    """End-to-end: make_optimizer(state_dtype='factored') drives the
    decoder loss down (the bench recipe's optimizer actually learns)."""
    from dlrover_tpu.models import decoder, get_config
    from dlrover_tpu.train import make_optimizer
    import optax

    cfg = get_config("tiny", n_layer=2, d_model=64, d_ff=128, n_head=4,
                     vocab_size=128, max_seq=32)
    opt = make_optimizer(
        learning_rate=3e-3, warmup_steps=2, decay_steps=200,
        state_dtype="factored",
    )
    params = decoder.init(jax.random.key(0), cfg)
    opt_state = opt.init(params)
    base = np.random.RandomState(0).randint(0, 8, size=(8, 33))
    batch = {
        "tokens": jnp.asarray(base[:, :-1], jnp.int32),
        "targets": jnp.asarray(base[:, 1:], jnp.int32),
    }

    @jax.jit
    def step(params, opt_state):
        (loss, _), g = jax.value_and_grad(
            lambda p: decoder.loss_fn(p, batch, cfg), has_aux=True
        )(params)
        upd, opt_state = opt.update(g, opt_state, params)
        return optax.apply_updates(params, upd), opt_state, loss

    first = None
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state)
        if first is None:
            first = float(loss)
    assert float(loss) < first * 0.7, (first, float(loss))


# -- narrow-head packing (pallas_attention head_pack) -----------------------
# The packed kernels read 128-lane slabs of the projections' [B, S, H·D]
# arrays: 128 // D heads side by side. Interpret mode pads what a block
# reads beyond an array's bounds with NaN, so a case whose last slab is
# not full (25 x 64, 5 x 32) also shows that nothing out there reaches a
# result. Both kernel families keep the row statistics in their own
# tiles and make delta in the dq kernel, so the unpacked ones (head size
# 128 with GQA, 256) are cases of the same tests.

def _watch_inner_grid(monkeypatch):
    """[(arguments, result)] of every ``_inner_grid`` call from here on:
    the grid each flash kernel traced after this is built with."""
    from dlrover_tpu.ops import pallas_attention as pa

    calls, inner_grid = [], pa._inner_grid

    def watched(*args):
        result = inner_grid(*args)
        calls.append((args, result))
        return result

    monkeypatch.setattr(pa, "_inner_grid", watched)
    return calls


SLAB_CASES = {
    # GPT-2 XL's head count: 12½ slabs, the half slab in the kernel
    "25x64-causal": dict(h=25, d=64),
    "26x64-causal": dict(h=26, d=64),
    "4x32-causal": dict(h=4, d=32),  # four heads a slab
    "5x32-causal": dict(h=5, d=32),  # ... and one head in the last
    "4x64-dense": dict(h=4, d=64, causal=False),
    "4x32-dense": dict(h=4, d=32, causal=False),
    "25x64-prefix": dict(h=25, d=64, prefix=(17, 100)),
    "5x64-window": dict(h=5, d=64, window=48),
    # the banded grid (a window's kernels walk the key blocks it admits):
    # windows under a tile, of a tile, off the tiles' grid and of several
    # tiles, over four tiles of keys at the two tiles given outright
    # (the public entry's rule would pick 128 at each of these windows)
    **{
        f"{name}-window{window}-tile{tile}": dict(
            window=window, tile=tile, s=4 * tile, b=1, **heads
        )
        for name, heads in (
            ("2x128", dict(h=2, d=128)),
            ("4x128-gqa", dict(h=4, hkv=1, d=128)),
            ("3x64", dict(h=3, d=64)),  # packed, the last slab half full
        )
        for window in (48, 128, 200, 384)
        for tile in (128, 256)
    },
    "1x64-causal": dict(h=1, d=64),  # the array narrower than a slab
    "5x64-two-blocks": dict(h=5, d=64, s=256),  # the carried statistics
    "16x128-gqa": dict(h=16, hkv=4, d=128),  # unpacked: Mistral, OLMoE
    "2x256-two-blocks": dict(h=2, d=256, s=256),  # ... and GLM's width
}


@pytest.mark.parametrize("case", sorted(SLAB_CASES))
def test_flash_slab_kernels_match_reference(monkeypatch, case):
    """Forward and all three gradients against ``mha_reference``, through
    the public entry (head_pack=0: auto), with delta from the dq kernel;
    a case that names its tile goes to the kernels' own entries."""
    from dlrover_tpu.observability import tracing
    from dlrover_tpu.ops import pallas_attention as pa

    spec = dict(SLAB_CASES[case])
    h, d, s_len = spec.pop("h"), spec.pop("d"), spec.pop("s", 128)
    hkv = spec.pop("hkv", None)
    causal = spec.pop("causal", True)
    prefix = spec.pop("prefix", None)
    tile, batch = spec.pop("tile", None), spec.pop("b", 2)
    kw = dict(spec)
    if prefix is not None:
        kw["prefix_len"] = jnp.array(prefix, jnp.int32)
    monkeypatch.setattr(pa, "INTERPRET", True)
    q, k, v = _qkv(jax.random.key(20), b=batch, s=s_len, h=h, hkv=hkv, d=d)
    scale = d ** -0.5
    pack = 1 if hkv else max(128 // d, 1)
    g = jax.random.normal(jax.random.key(25), q.shape)
    fr = lambda q, k, v: jnp.vdot(  # noqa: E731
        mha_reference(q, k, v, causal=causal, softmax_scale=scale, **kw),
        g,
    )
    if tile is None:
        f = lambda q, k, v: jnp.vdot(  # noqa: E731
            pa.flash_attention(q, k, v, causal=causal, block_q=128,
                               block_k=128, **kw), g
        )
        (lo, go) = jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
        assert tracing.counters()["attn.heads_per_slab"] == pack
        assert tracing.counters()["attn.delta_in_kernel"] == 1
    else:
        # the kernels at the tile given outright, past the rules that
        # choose one: four tiles of keys, of which a block's window
        # reaches ceil(W / tile) + 1, and no grid's inner axis is longer
        grids = _watch_inner_grid(monkeypatch)
        tiled = dict(window=kw["window"], head_pack=pack)
        out, lse = pa._flash_fwd(q, k, v, True, scale, tile, tile, **tiled)
        lo = jnp.vdot(out, g)
        go = pa._pallas_backward(
            q, k, v, out, lse, g, True, scale, tile, tile, **tiled
        )[:3]
        steps = min(-(-kw["window"] // tile) + 1, 4)
        assert [(band, ks, qs) for _, (band, (ks, _), (qs, _)) in grids] == [
            (True, steps, steps)
        ] * 2
    (lr, gr) = jax.value_and_grad(fr, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(lo), float(lr), rtol=2e-3)
    for a, r in zip(go, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), rtol=2e-3, atol=2e-3
        )


def test_flash_half_slab_equals_a_zero_head(monkeypatch):
    """25 heads are the first 25 of a 26-head call whose last head is
    zero, bit for bit, forward and gradients: the half slab's unreal
    head is made zero inside the kernel, whatever lies beyond column
    H·D (NaN here: the interpreter's padding)."""
    from dlrover_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "INTERPRET", True)
    q, k, v = _qkv(jax.random.key(26), b=1, s=128, h=25, d=64)
    g = jax.random.normal(jax.random.key(27), q.shape)
    zpad = [(0, 0), (0, 0), (0, 1), (0, 0)]

    def run(q, k, v, g):
        f = lambda q, k, v: jnp.vdot(  # noqa: E731
            pa.flash_attention(q, k, v, causal=True, block_q=128,
                               block_k=128).astype(jnp.float32), g
        )
        out = pa.flash_attention(q, k, v, causal=True, block_q=128,
                                 block_k=128)
        return (out, *jax.grad(f, argnums=(0, 1, 2))(q, k, v))

    odd = run(q, k, v, g)
    even = run(*(jnp.pad(x, zpad) for x in (q, k, v, g)))
    for a, e in zip(odd, even):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(e[:, :, :25])
        )
        assert not np.asarray(e[:, :, 25:]).any()


@pytest.mark.parametrize(
    "h,hkv,d,dtype",
    [
        (25, None, 64, jnp.bfloat16),  # the half slab, stored precision
        (5, None, 32, jnp.float32),  # four heads a slab, one in the last
        (4, 2, 128, jnp.float32),  # unpacked, GQA
        (2, None, 256, jnp.bfloat16),
    ],
)
def test_flash_delta_from_the_dq_kernel(h, hkv, d, dtype):
    """The dq kernel's second output is delta = Σ_d dO·out (f32 products
    of the stored values, f32 row sum per head) in lse's tile format,
    ``[B·slabs, S, 8]`` with head p of a slab in lane p; the head past H
    of a half slab reads zero though the block holds NaN beyond column
    H·D (the interpreter's padding): those lanes are in no head's sum."""
    from dlrover_tpu.ops import pallas_attention as pa

    b, s_len = 2, 256
    q, k, v = _qkv(jax.random.key(32), b=b, s=s_len, h=h, hkv=hkv, d=d,
                   dtype=dtype)
    pack = 128 // d if d < 128 else 1
    out, lse = pa._flash_fwd(
        q, k, v, True, d ** -0.5, 128, 128, interpret=True, head_pack=pack
    )
    g = jax.random.normal(jax.random.key(33), out.shape, dtype)
    *_, delta = pa._pallas_backward(
        q, k, v, out, lse, g, True, d ** -0.5, 128, 128, interpret=True,
        head_pack=pack,
    )
    n_slabs = -(-h // pack)
    assert delta.shape == lse.shape == (b * n_slabs, s_len, 8)
    assert delta.dtype == jnp.float32
    want = np.asarray(
        jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    ).transpose(0, 2, 1)
    got = np.asarray(pa._stat_rows(delta, b, h, pack))
    assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()
    if h % pack:
        heads = np.asarray(pa._stat_rows(delta, b, n_slabs * pack, pack))
        assert heads.shape[1] > h and not heads[:, h:].any()
    # and the way back in: the ring's lse cotangent, an lse kept as rows
    back = pa._stat_tiles(jnp.asarray(got), pack)
    assert back.shape == delta.shape
    np.testing.assert_array_equal(
        np.asarray(pa._stat_rows(back, b, h, pack)), got
    )


@pytest.mark.parametrize("h", [5, 4])
def test_flash_packed_lse_contract(monkeypatch, h):
    """``flash_attention_with_lse`` keeps its [B, H, S] lse (ring and
    Ulysses attention merge on it) on both kernel families, the packed
    equal to the unpacked to rounding, and a nonzero lse cotangent
    reaches q, k, v through the dq kernel's delta as autodiff of a plain
    (out, lse) has it."""
    from dlrover_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "INTERPRET", True)
    q, k, v = _qkv(jax.random.key(28), s=256, h=h, d=64)
    scale = 64 ** -0.5

    def run(pack):
        return lambda q, k, v: pa.flash_attention_with_lse(
            q, k, v, None, None, True, scale, 128, 128, 0, pack
        )

    def plain(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]), v), lse

    (out_p, lse_p), (out_u, lse_u) = run(2)(q, k, v), run(1)(q, k, v)
    assert lse_p.shape == (2, h, 256) and lse_p.dtype == jnp.float32
    assert lse_u.shape == (2, h, 256)
    np.testing.assert_allclose(
        np.asarray(lse_p), np.asarray(lse_u), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_u), rtol=1e-5, atol=1e-5
    )
    g = jax.random.normal(jax.random.key(29), out_p.shape)
    g_lse = jax.random.normal(jax.random.key(30), lse_p.shape)

    def grads(fn):
        def loss(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.vdot(out, g) + jnp.vdot(lse, g_lse)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    packed, unpacked, want = grads(run(2)), grads(run(1)), grads(plain)
    for a, u, w in zip(packed, unpacked, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(u), rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(
            np.asarray(u), np.asarray(w), rtol=2e-3, atol=2e-3
        )


def test_flash_head_pack_one_runs_unpacked(monkeypatch):
    """``head_pack=1`` keeps narrow heads on the unpacked kernels; the
    counter says which path a trace took."""
    from dlrover_tpu.observability import tracing
    from dlrover_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "INTERPRET", True)
    q, k, v = _qkv(jax.random.key(31), s=128, h=3, d=64)
    outs = {}
    for head_pack in (1, 0):
        outs[head_pack] = pa.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128,
            head_pack=head_pack,
        )
        want = 1 if head_pack == 1 else 2
        assert tracing.counters()["attn.heads_per_slab"] == want
    np.testing.assert_allclose(
        np.asarray(outs[0]), np.asarray(outs[1]), rtol=1e-5, atol=1e-5
    )


def test_flash_attention_gqa_demotes_head_pack(monkeypatch):
    """GQA layouts run unpacked even when head_pack is forced: numerics
    must still match the reference (the demotion, not a crash)."""
    from dlrover_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "INTERPRET", True)
    q, k, v = _qkv(jax.random.key(26), s=128, h=4, hkv=2, d=64)
    scale = 64 ** -0.5
    out = pa.flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, head_pack=2
    )
    ref = mha_reference(q, k, v, causal=True, softmax_scale=scale)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


# -- the banded grid (pallas_attention._inner_grid) -------------------------

BAND_GRIDS = {
    # (seq, window, block_q, block_k): (k steps, q steps) where a cell
    # runs it, else None
    "trinity-512": ((16384, 2048, 512, 512), (5, 5)),
    "trinity-1024": ((16384, 2048, 1024, 1024), (3, 3)),
    "mistral-1024": ((8192, 4096, 1024, 1024), (5, 5)),
    "window-of-tiles": ((1024, 256, 128, 128), (3, 3)),
    "window-off-the-tiles": ((1024, 200, 128, 128), (3, 3)),
    "window-under-a-tile": ((1024, 48, 128, 128), (2, 2)),
    "window-of-one-key": ((512, 1, 128, 128), (1, 1)),
    # not live: the square, whose every step is its own block
    "window-of-the-sequence": ((1024, 1024, 256, 256), (4, 4)),
    "window-past-the-sequence": ((1024, 4096, 256, 256), (4, 4)),
    "one-tile": ((256, 100, 256, 256), (1, 1)),
    "wide-q-blocks": ((1024, 384, 256, 128), None),
    "wide-k-blocks": ((1024, 384, 128, 256), None),
    "q-blocks-of-four": ((2048, 520, 512, 128), None),
    "k-blocks-of-four": ((2048, 640, 128, 512), None),
}


@pytest.mark.parametrize("case", sorted(BAND_GRIDS))
def test_band_walks_exactly_the_blocks_the_gate_admits(case):
    """The banded grid's index arithmetic, in plain integers: for every
    outer block, the steps the run gate lets through stand for exactly
    the blocks ``_block_runs`` admits on the square, each once and in
    order, and fetch the block they stand for; every other step fetches
    a block of the sequence (a neighbour, not refetched) and runs
    nothing. Forward / dq map and dk/dv map."""
    from dlrover_tpu.ops import pallas_attention as pa

    (seq, window, bq, bk), extents = BAND_GRIDS[case]
    nq, nk = seq // bq, seq // bk
    band, (k_steps, k_block), (q_steps, q_block) = pa._inner_grid(
        True, bq, bk, nq, nk, window
    )
    assert band == (window < seq)
    assert 1 <= k_steps <= nk and 1 <= q_steps <= nq
    if extents:
        assert (k_steps, q_steps) == extents

    def runs(i, j, in_band=None):
        return bool(pa._block_runs(
            True, False, None, i * bq, j * bk, bq, bk, window, in_band
        ))

    for i in range(nq):
        walked = []
        for step in range(k_steps):
            j, in_band = pa._band_k_block(
                i, step, bq, bk, window, nk * band
            )
            fetched = int(k_block(i, step))
            assert 0 <= fetched < nk
            if runs(i, j, in_band):
                assert fetched == j
                walked.append(j)
        assert walked == [j for j in range(nk) if runs(i, j)], i
    for j in range(nk):
        walked = []
        for step in range(q_steps):
            i, in_band = pa._band_q_block(j, step, bq, bk, nq * band)
            fetched = int(q_block(j, step))
            assert 0 <= fetched < nq
            if runs(i, j, in_band):
                assert fetched == i
                walked.append(i)
        assert walked == [i for i in range(nq) if runs(i, j)], j


@pytest.mark.parametrize(
    "seq,window,block,band_blocks,tile",
    [
        (16384, 2048, 1024, 3, 512),  # Trinity's window layers
        (8192, 4096, 1024, 5, 1024),  # Mistral: the tile it had
        (8192, 4096, 512, 9, 512),  # the caller's block is a cap
        (1024, 48, 512, 2, 128),  # no tile under 128
        (2048, 2048, 1024, 2, 0),  # a window of the sequence: not live
        (2048, 0, 1024, 2, 0),  # no window: the square
    ],
)
def test_window_tile_and_band_counters(monkeypatch, seq, window, block,
                                       band_blocks, tile):
    """``attn.band_blocks`` is the inner extent of the forward grid —
    under a live window the key blocks it admits for a query block at
    the caller's tile, else every key block — and ``attn.window_tile``
    the backward kernels' k tile under a live window — the largest
    128-multiple dividing the sequence, at most the caller's block, the
    head width's cap and a quarter of the window — else 0. Set while
    tracing; the backward's grids follow the tile."""
    from dlrover_tpu.observability import tracing
    from dlrover_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "INTERPRET", True)
    q = jax.ShapeDtypeStruct((1, seq, 4, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, seq, 1, 128), jnp.bfloat16)
    grids = _watch_inner_grid(monkeypatch)
    tracing._counters.clear()
    jax.eval_shape(
        jax.grad(
            lambda q, k, v: pa.flash_attention(
                q, k, v, causal=True, block_q=block, block_k=block,
                window=window,
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        ),
        q, kv, kv,
    )
    got = tracing.counters()
    assert got["attn.band_blocks"] == band_blocks
    assert got["attn.window_tile"] == tile
    # the last grid traced is the backward's: at its tile, and banded
    # exactly where the window is live
    (_, block_q, block_k, *_), (band, _, _) = grids[-1]
    assert block_q == block_k == (tile or block)
    assert band == bool(tile)
