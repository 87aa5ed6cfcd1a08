"""The main path's kernels, compiled for the chip without the chip.

The TPU's compiler is installed in the sandbox and compiles for a device
that is described, not attached (``jax.experimental.topologies``). These
are the first tests in the repo that the chip's compiler, not the Pallas
interpreter, decides: interpret mode accepted every kernel here while
Mosaic refused the norm backward's partials block, every prefill chunk of
256 rows or more, and the int8 paged dequant at 25 heads x 64.

Widths are GPT-2 XL's (25 heads x 64, d 1600 — the unaligned ones) and a
lane-aligned control (16 x 128, d 2048). Nothing runs, so nothing here
says a kernel is right or fast — only that the chip would take it.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from dlrover_tpu.common import device
from dlrover_tpu.models.config import get_config
from dlrover_tpu.ops import pallas_attention, pallas_norm, pallas_paged
from dlrover_tpu.serving import kv_cache as kvc

BF16 = jnp.bfloat16
F32 = jnp.float32


@pytest.fixture(scope="module")
def chip():
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _as_on_the_chip(monkeypatch):
    """The code under test asks the one probe where it runs and would
    take its CPU branch; the test, not a new option of the program,
    tells it otherwise. A compile for a described device is written to
    the persistent cache but cannot be read back without a chip, so the
    cache is off around these tests."""
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    monkeypatch.setattr(device, "on_cpu", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _flash(heads, head_dim, grad):
    def build(S):
        q = S((8, 1024, heads, head_dim), BF16)

        def fwd(q, k, v):
            return pallas_attention.flash_attention(q, k, v, causal=True)

        if not grad:
            return fwd, (q, q, q)
        loss = lambda q, k, v: fwd(q, k, v).astype(F32).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2)), (q, q, q)

    return build


def _norm(d, grad, residual):
    def build(S):
        x, scale = S((8, 1024, d), BF16), S((d,), F32)

        def fwd(x, scale, bias, res):
            out = pallas_norm.norm(
                x, scale, bias, kind="layernorm",
                residual=res if residual else None,
            )
            return out if residual else (out,)

        if not grad:
            return fwd, (x, scale, scale, x)
        loss = lambda *a: sum(o.astype(F32).sum() for o in fwd(*a))  # noqa: E731
        argnums = (0, 1, 2, 3) if residual else (0, 1, 2)
        return jax.grad(loss, argnums=argnums), (x, scale, scale, x)

    return build


def _paged(variant, c, mode):
    def build(S):
        cfg = get_config("gpt2-1.5b")
        geom = kvc.make_geometry(
            cfg, n_slots=4, max_len=1024, page_size=16, mode=mode
        )
        pools = jax.eval_shape(lambda: kvc.init_pools(geom))
        layer = {k: S(v.shape[1:], v.dtype) for k, v in pools.items()}
        b, h, d = 4, cfg.n_head, cfg.head_dim
        q = S((b, c, h, d), BF16)
        tables = S((b, geom.max_pages_per_slot), jnp.int32)
        pos = S((b,) if variant == "decode" else (b, c), jnp.int32)
        extra = (q, q) if variant == "verify" else ()

        def fn(q, layer, tables, pos, *extra):
            kw = dict(zip(("extra_k", "extra_v"), extra))
            return pallas_paged.paged_attention(
                q, layer, tables, pos, scale=d**-0.5, kv_heads=h,
                variant=variant, **kw,
            )

        return fn, (q, layer, tables, pos, *extra)

    return build


CASES = {
    "flash-fwd-25x64-packed": (_flash(25, 64, grad=False), 1),
    "flash-bwd-25x64-packed": (_flash(25, 64, grad=True), 3),
    "flash-fwd-16x128": (_flash(16, 128, grad=False), 1),
    "flash-bwd-16x128": (_flash(16, 128, grad=True), 3),
    "norm-fwd-d1600": (_norm(1600, grad=False, residual=False), 1),
    "norm-bwd-d1600": (_norm(1600, grad=True, residual=False), 1),
    "norm-residual-bwd-d1600": (_norm(1600, grad=True, residual=True), 2),
    "norm-fwd-d2048": (_norm(2048, grad=False, residual=False), 1),
    "norm-bwd-d2048": (_norm(2048, grad=True, residual=False), 1),
    "norm-residual-bwd-d2048": (_norm(2048, grad=True, residual=True), 2),
    **{
        f"paged-{variant}{c}-{mode}": (_paged(variant, c, mode), 1)
        for mode in ("bf16", "int8")
        for variant, c in (("decode", 1), ("chunk", 256), ("verify", 4))
    },
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    build, n_kernels = CASES[case]

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    fn, args = build(struct)
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == n_kernels
