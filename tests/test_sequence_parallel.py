"""Ulysses SP and ring attention numerics vs the reference attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.common import device
from dlrover_tpu.ops.attention import mha_reference
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.parallel.sequence import ring_attention, ulysses_attention

# ring-attention compiles are heavy on the CPU mesh; excluded from the tier-1 budget
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshConfig(dp=2, sp=4))


def _qkv(key, b=2, s=128, h=4, d=32):
    ks = jax.random.split(key, 3)
    return (
        jax.random.normal(ks[0], (b, s, h, d)),
        jax.random.normal(ks[1], (b, s, h, d)),
        jax.random.normal(ks[2], (b, s, h, d)),
    )


def _shard_seq(mesh, x):
    return jax.device_put(x, NamedSharding(mesh, P(None, "sp", None, None)))


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(mesh, causal):
    q, k, v = _qkv(jax.random.key(0))
    ref = mha_reference(q, k, v, causal=causal)
    out = ring_attention(
        _shard_seq(mesh, q),
        _shard_seq(mesh, k),
        _shard_seq(mesh, v),
        mesh,
        causal=causal,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_reference(mesh, causal):
    q, k, v = _qkv(jax.random.key(1))
    ref = mha_reference(q, k, v, causal=causal)
    out = ulysses_attention(
        _shard_seq(mesh, q),
        _shard_seq(mesh, k),
        _shard_seq(mesh, v),
        mesh,
        causal=causal,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_ulysses_gqa_on_sp_tp_mesh():
    """Regression: sp=4×tp=2 GQA (Hq=8, Hkv=4). The kv-expansion decision
    must use the tp-LOCAL kv head count (4%4==0 globally, but each tp
    shard holds 2 kv heads, which sp=4 cannot split without expansion)."""
    mesh = build_mesh(MeshConfig(sp=4, tp=2))
    ks = jax.random.split(jax.random.key(3), 3)
    b, s, hq, hkv, d = 2, 64, 8, 4, 16
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    ref = mha_reference(q, k, v, causal=True)

    def put(x):
        return jax.device_put(
            x, NamedSharding(mesh, P(None, "sp", "tp", None))
        )

    out = ulysses_attention(put(q), put(k), put(v), mesh, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_ring_train_step_matches_dp(mesh):
    """Full train step with ring attention == plain attention numerics."""
    from dlrover_tpu.accelerate import auto_accelerate
    from dlrover_tpu.models import get_config

    cfg = get_config("tiny")
    tokens = jax.random.randint(jax.random.key(5), (8, 64), 0, 1000)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}

    def run(strategy):
        res = auto_accelerate(
            cfg, global_batch=8, seq=64, strategy=strategy
        )
        state = res.init_state(jax.random.key(0))
        b = jax.device_put(batch, res.batch_sharding)
        state, metrics = res.train_step(state, b)
        return float(metrics["loss"])

    loss_dp = run([("mixed_parallel", {"dp": -1})])
    loss_ring = run(
        [
            ("mixed_parallel", {"dp": 2, "sp": 4}),
            ("ring_attention", {"size": 4}),
        ]
    )
    assert loss_dp == pytest.approx(loss_ring, rel=1e-4)


def test_ring_attention_grads(mesh):
    q, k, v = _qkv(jax.random.key(2), s=64)

    def loss_ring(q, k, v):
        return jnp.sum(
            ring_attention(q, k, v, mesh, causal=True) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring)(
        _shard_seq(mesh, q), _shard_seq(mesh, k), _shard_seq(mesh, v)
    )
    g_ref = jax.grad(loss_ref)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(g_ring), np.asarray(g_ref), rtol=5e-4, atol=5e-4
    )


def test_ulysses_prefix_matches_reference(mesh):
    """Prefix-LM masking through the all-to-all path (GLM + ulysses)."""
    q, k, v = _qkv(jax.random.key(5))
    prefix = jnp.array([17, 90], jnp.int32)
    ref = mha_reference(q, k, v, causal=True, prefix_len=prefix)
    out = ulysses_attention(
        _shard_seq(mesh, q),
        _shard_seq(mesh, k),
        _shard_seq(mesh, v),
        mesh,
        causal=True,
        prefix_len=prefix,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_ulysses_window_matches_reference(mesh):
    """Sliding window through the all-to-all path: the inner attention
    sees global positions, so the mask carries over unchanged."""
    q, k, v = _qkv(jax.random.key(11))
    ref = mha_reference(q, k, v, causal=True, window=40)
    out = ulysses_attention(
        _shard_seq(mesh, q),
        _shard_seq(mesh, k),
        _shard_seq(mesh, v),
        mesh,
        causal=True,
        window=40,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("window", [20, 48, 130])
def test_ring_window_matches_reference(mesh, window):
    """Sliding window over the ring (jnp block path): windows smaller
    than, spanning, and exceeding the 32-wide ring blocks."""
    q, k, v = _qkv(jax.random.key(12))  # s=128 over sp=4 → 32-blocks
    ref = mha_reference(q, k, v, causal=True, window=window)
    out = ring_attention(
        _shard_seq(mesh, q),
        _shard_seq(mesh, k),
        _shard_seq(mesh, v),
        mesh,
        causal=True,
        window=window,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_ring_window_flash_path(monkeypatch):
    """Windowed ring over the flash-kernel path: dense, diagonal
    causal+window, boundary-partial, and empty block cases all hit."""
    from dlrover_tpu.ops import pallas_attention as pa

    if pa.pltpu is None:
        pytest.skip("pallas TPU module unavailable")
    monkeypatch.setattr(pa, "INTERPRET", True)
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    mesh = build_mesh(MeshConfig(sp=4, dp=2))
    b, s, h, d = 2, 1024, 2, 32  # 256-wide ring blocks
    ks = jax.random.split(jax.random.key(13), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    window = 400  # crosses one block boundary, darkens distant blocks
    out = ring_attention(q, k, v, mesh, causal=True, window=window)
    ref = mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=3e-3, atol=3e-3
    )

    def loss(q, k, v):
        return jnp.sum(
            ring_attention(q, k, v, mesh, causal=True, window=window) ** 2
        )

    def ref_loss(q, k, v):
        return jnp.sum(
            mha_reference(q, k, v, causal=True, window=window) ** 2
        )

    g = jax.grad(loss)(q, k, v)
    rg = jax.grad(ref_loss)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(rg), rtol=5e-3, atol=5e-3
    )


def test_ring_window_flash_path_gqa(monkeypatch):
    """GQA through the windowed flash ring: k/v stay at hkv heads on the
    ring (groups× fewer ppermute bytes) and the offset kernel handles
    the boundary blocks without a head expansion."""
    from dlrover_tpu.ops import pallas_attention as pa

    if pa.pltpu is None:
        pytest.skip("pallas TPU module unavailable")
    monkeypatch.setattr(pa, "INTERPRET", True)
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    mesh = build_mesh(MeshConfig(sp=4, dp=2))
    b, s, hq, hkv, d = 2, 1024, 4, 2, 32
    ks = jax.random.split(jax.random.key(21), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    window = 400
    out = ring_attention(q, k, v, mesh, causal=True, window=window)
    ref = mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=3e-3, atol=3e-3
    )


def test_ring_prefix_matches_reference(mesh):
    """Prefix-LM masking through the ring (jnp block path): prefixes
    crossing ring-block boundaries, incl. one inside an after-block."""
    q, k, v = _qkv(jax.random.key(6))  # s=128 over sp=4 → 32-blocks
    prefix = jnp.array([50, 100], jnp.int32)
    ref = mha_reference(q, k, v, causal=True, prefix_len=prefix)
    out = ring_attention(
        _shard_seq(mesh, q),
        _shard_seq(mesh, k),
        _shard_seq(mesh, v),
        mesh,
        causal=True,
        prefix_len=prefix,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )

    def loss(q, k, v):
        return jnp.sum(
            ring_attention(
                q, k, v, mesh, causal=True, prefix_len=prefix
            ) ** 2
        )

    def ref_loss(q, k, v):
        return jnp.sum(
            mha_reference(q, k, v, causal=True, prefix_len=prefix) ** 2
        )

    g = jax.grad(loss)(q, k, v)
    rg = jax.grad(ref_loss)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(rg), rtol=5e-4, atol=5e-4
    )


def test_ring_prefix_flash_path(monkeypatch):
    """Prefix ring over the flash-kernel path (interpret): diagonal
    causal+prefix blocks and prefix-reaching after-blocks."""
    from dlrover_tpu.ops import pallas_attention as pa

    if pa.pltpu is None:
        pytest.skip("pallas TPU module unavailable")
    monkeypatch.setattr(pa, "INTERPRET", True)
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    mesh = build_mesh(MeshConfig(sp=2, dp=4))
    b, s, h, d = 4, 512, 4, 32
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    # one prefix inside the first ring block, one reaching the second
    prefix = jnp.array([100, 300, 0, 511], jnp.int32)
    out = ring_attention(q, k, v, mesh, causal=True, prefix_len=prefix)
    ref = mha_reference(q, k, v, causal=True, prefix_len=prefix)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=3e-3, atol=3e-3
    )

    # gradients: prefix must flow through flash_attention_with_lse's
    # custom_vjp (float0 dprefix) and the g_lse chunked backward
    def loss(q, k, v):
        return jnp.sum(
            ring_attention(
                q, k, v, mesh, causal=True, prefix_len=prefix
            ) ** 2
        )

    def ref_loss(q, k, v):
        return jnp.sum(
            mha_reference(q, k, v, causal=True, prefix_len=prefix) ** 2
        )

    g = jax.grad(loss)(q, k, v)
    rg = jax.grad(ref_loss)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(rg), rtol=5e-3, atol=5e-3
    )


def test_ring_attention_flash_path_matches_reference(monkeypatch):
    """Exercise the flash-kernel ring path (lax.switch over kernel
    variants + lse merge) on the CPU mesh via interpret mode."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.ops import pallas_attention as pa
    from dlrover_tpu.ops.attention import mha_reference
    from dlrover_tpu.parallel import MeshConfig, build_mesh
    from dlrover_tpu.parallel.sequence import ring_attention

    if pa.pltpu is None:
        pytest.skip("pallas TPU module unavailable: flash path untestable")
    monkeypatch.setattr(pa, "INTERPRET", True)
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    # _fit_block needs 128-multiples: S=512 over sp=2 → 256-local blocks
    mesh = build_mesh(MeshConfig(sp=2, dp=4))
    b, s, h, d = 4, 512, 4, 32
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    out = ring_attention(q, k, v, mesh, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=3e-3, atol=3e-3
    )

    # gradients flow through the kernel + lse merge
    def loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g = jax.grad(loss)(q, k, v)
    rg = jax.grad(ref_loss)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(rg), rtol=5e-3, atol=5e-3
    )
