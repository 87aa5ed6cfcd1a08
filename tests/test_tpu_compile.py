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
from dlrover_tpu.ops import (
    gated_delta, pallas_align, pallas_attention, pallas_conv, pallas_norm, pallas_paged,
    pallas_rows, pallas_selective_scan, pallas_ssd, selective_scan, ssd,
)
from dlrover_tpu.parallel import moe
from dlrover_tpu.serving import kv_cache as kvc

BF16 = jnp.bfloat16
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {exc}")


@pytest.fixture(scope="module")
def chip(topo):
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


def _flash_window(seq, kv_heads, window):
    """A window layer's three kernels on the banded grid at a cell's own
    shape: GQA 32 / ``kv_heads`` heads of 128 over one sequence, at the
    model's blocks of 1024 (the tile comes from the window)."""
    def build(S):
        q = S((1, seq, 32, 128), BF16)
        k = S((1, seq, kv_heads, 128), BF16)

        def loss(q, k, v):
            return pallas_attention.flash_attention(
                q, k, v, causal=True, block_q=1024, block_k=1024,
                window=window,
            ).astype(F32).sum()

        return jax.grad(loss, argnums=(0, 1, 2)), (q, k, k)

    return build


def _flash_selected(grad):
    """Keye-VL-2.0's attention: GQA 32 / 4 heads of 128 over one
    sequence of 8192 with the int8 selection operand, at the model's
    blocks of 1024 (the backward's tile is 1024 x 1024 too)."""
    def build(S):
        q = S((1, 8192, 32, 128), BF16)
        k = S((1, 8192, 4, 128), BF16)
        sel = S((1, 8192, 8192), jnp.int8)

        def fwd(q, k, v, sel):
            return pallas_attention.flash_attention(
                q, k, v, causal=True, block_q=1024, block_k=1024,
                selected=sel,
            )

        if not grad:
            return fwd, (q, k, k, sel)
        loss = lambda *a: fwd(*a)[0].astype(F32).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2)), (q, k, k, sel)

    return build


def _align():
    """Keye-VL-2.0's alignment term for one layer of the cell: 16 index
    heads of 64 over one sequence of 8192 in chunks of 512, against the
    attention's 32 / 4 heads of 128, at the tiles the program picks."""
    def build(S):
        s = 8192
        args = (
            S((1, s, 16, 64), BF16), S((1, s, 64), BF16), S((1, s, 16), F32),
            S((1, s, s), jnp.int8), S((1, s, 32, 128), BF16),
            S((1, s, 4, 128), BF16), S((1, 32, s), F32),
        )
        tiles = pallas_align.tiles(s, 512, 16, 64)
        assert tiles == (256, 512)

        def fn(*a):
            return pallas_align.alignment_kl_and_grads(
                *a, 128 ** -0.5, 512, *tiles
            )

        return fn, args

    return build


def _ssd(grad):
    """A Mamba-2 layer's scan at Nemotron-3-Super's widths: one sequence
    of 8,192, 128 heads of 64 in 8 groups over a state of 128, at the
    chunk the program picks (``ssd.kernel_chunk``)."""
    def build(S):
        s, heads, channels, groups, state = 8192, 128, 64, 8, 128
        args = (
            S((1, s, heads, channels), BF16), S((1, s, heads), F32),
            S((heads,), F32), S((1, s, groups, state), BF16),
            S((1, s, groups, state), BF16),
        )
        assert ssd.kernel_chunk(s, heads, channels, groups, state, 128) == 256

        def fwd(*a):
            return ssd.ssd_scan(*a, 128, 16)

        if not grad:
            return fwd, args
        loss = lambda *a: fwd(*a).astype(F32).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4)), args

    return build


def _ssd_lightning(grad):
    """A lightning layer's recurrence at MiniCPM-SALA's widths: one
    sequence of 16,384, 32 groups of ONE head of 128 channels over a
    state of 128 (one slab a group, one turn), Δ ≡ 1, gradients for q, k
    and v alone."""
    def build(S):
        s, heads, channels, state = 16384, 32, 128, 128
        args = (
            S((1, s, heads, channels), BF16), S((1, s, heads), F32),
            S((heads,), F32), S((1, s, heads, state), BF16),
            S((1, s, heads, state), BF16),
        )
        assert ssd.kernel_chunk(s, heads, channels, heads, state, 128) == 256

        def fwd(*a):
            return ssd.ssd_scan(*a, 128, 0)

        if not grad:
            return fwd, args
        loss = lambda *a: fwd(*a).astype(F32).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 3, 4)), args

    return build


def _flash_selected_by_kv_head(grad):
    """MiniCPM-SALA's sparse attention: GQA 32 / 2 heads of 128 over one
    sequence of 16,384 with an int8 selection A KV HEAD, at the model's
    blocks of 1024."""
    def build(S):
        q = S((1, 16384, 32, 128), BF16)
        k = S((1, 16384, 2, 128), BF16)
        sel = S((1, 2, 16384, 16384), jnp.int8)

        def fwd(q, k, v, sel):
            return pallas_attention.flash_attention(
                q, k, v, causal=True, block_q=1024, block_k=1024,
                selected=sel,
            )

        if not grad:
            return fwd, (q, k, k, sel)
        loss = lambda *a: fwd(*a)[0].astype(F32).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2)), (q, k, k, sel)

    return build


def _sscan(grad):
    """A Mamba-1 layer's selective scan at Jamba2-3B's widths: one
    sequence of 8,192, 5,120 channels of 16 states, float32 as the mixer
    hands them over, at the module's chunk."""
    def build(S):
        s, channels, states = 8192, 5120, 16
        args = (
            S((1, s, channels), F32), S((1, s, channels), F32),
            S((channels, states), F32), S((1, s, states), F32),
            S((1, s, states), F32),
        )
        assert pallas_selective_scan.tile(
            s, channels, states, selective_scan.SCAN_CHUNK
        )

        if not grad:
            return selective_scan.selective_scan, args
        loss = lambda *a: selective_scan.selective_scan(*a).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4)), args

    return build


def _gdn(grad):
    """A gated-delta-rule layer at Qwen3-Next's widths (16 key heads
    shared by 32 value heads of 128, one sequence of 16,384, float32 as
    the mixer hands them over), under the caller's scope: the kernels
    take their names behind it."""
    def build(S):
        s = 16384
        args = (
            S((1, s, 16, 128), F32), S((1, s, 16, 128), F32),
            S((1, s, 32, 128), F32), S((1, s, 32), F32), S((1, s, 32), F32),
        )
        assert gated_delta.in_kernels(128, 128)

        def rule(*a):
            with jax.named_scope("gdn.rule"):
                return gated_delta.gated_delta_rule(*a)

        if not grad:
            return rule, args
        loss = lambda *a: rule(*a).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4)), args

    return build


def _conv(channels, dtype, grad):
    """A mixer's causal conv of 4 taps over one sequence of 8,192 as the
    two cells run it: Nemotron-3-Super's 10,240 channels in bf16 with
    bf16 taps, Jamba2-3B's 5,120 in float32 with bf16 taps; the ``silu``
    behind it keeps the forward kernel in the gradient's program, as a
    mixer does."""
    def build(S):
        args = (
            S((1, 8192, channels), dtype), S((4, channels), BF16),
            S((channels,), BF16),
        )
        assert pallas_conv.tile(8192, channels, 4) == 1024

        if not grad:
            return ssd.causal_conv, args
        loss = lambda *a: jax.nn.silu(  # noqa: E731
            ssd.causal_conv(*a).astype(F32)
        ).sum()
        return jax.grad(loss, argnums=(0, 1, 2)), args

    return build


def _conv_of_projection(grad):
    """The gated-delta-rule mixer's conv (Qwen3-Next): 4 taps over the
    first 8,192 columns [q | k | v] of a 12,288-wide in-projection, read
    where they lie (``ssd.Columns``), one sequence of 16,384, bf16."""
    def build(S):
        args = (
            S((1, 16384, 12288), BF16), S((4, 8192), BF16), S((8192,), F32),
        )
        assert pallas_conv.tile(16384, 8192, 4, 0) is not None

        def fwd(proj, w, b):
            return ssd.causal_conv(ssd.Columns(proj, 0), w, b)

        if not grad:
            return fwd, args
        loss = lambda *a: jax.nn.silu(fwd(*a).astype(F32)).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1)), args

    return build


def _gated_conv(grad):
    """LFM2's gated short conv: 3 taps over B ⊙ x gated by C, the three
    read where they lie in the 6,144-wide in-projection [B | C | x],
    eight sequences of 4,096, bf16."""
    def build(S):
        args = (S((8, 4096, 6144), BF16), S((3, 2048), BF16))
        assert ssd.gated_conv_in_kernel(4096, 3, 2048, BF16) == 1024

        if not grad:
            return ssd.gated_conv, args
        loss = lambda *a: jax.nn.silu(  # noqa: E731
            ssd.gated_conv(*a).astype(F32)
        ).sum()
        return jax.grad(loss, argnums=(0, 1)), args

    return build


def _flash_gqa_256(grad):
    """Qwen3-Next's full layers: 16 query heads on 2 key-value heads of
    256 channels, one sequence of 16,384."""
    def build(S):
        q = S((1, 16384, 16, 256), BF16)
        k = S((1, 16384, 2, 256), BF16)

        def fwd(q, k, v):
            return pallas_attention.flash_attention(q, k, v, causal=True)

        if not grad:
            return fwd, (q, k, k)
        loss = lambda q, k, v: fwd(q, k, v).astype(F32).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2)), (q, k, k)

    return build


def _held_rows(t, k, d, tiles, bound=None, back=False):
    """A routed block's sum over the rows its held experts received
    (``ops/pallas_rows.py``) as the cells run it: the combine (weighted)
    of Keye-VL-2.0's and GLM-4.7-Flash's 65,536 rows and Trinity-Mini's
    131,072 at 2,048 columns, and the dispatch's derivative
    (unweighted, ``back``) of Nemotron-3-Super's 180,224 pairs cut to
    65,536 rows at its latent's 1,024."""
    def build(S):
        n = bound or t * k
        assert pallas_rows.tile(t, n, d, BF16) == tiles
        mask, rows = S((t * k,), jnp.bool_), S((), jnp.int32)
        order, inv = S((n,), jnp.int32), S((t * k,), jnp.int32)
        if back:
            def fn(xt, cot, order, inv, mask, rows):
                held = moe.Held(mask, rows, True)
                return jax.grad(
                    lambda x: (
                        moe._dispatch(k, x, order // k, inv, held)
                        .astype(F32) * cot
                    ).sum()
                )(xt)

            return fn, (S((t, d), BF16), S((n, d), F32), order, inv, mask,
                        rows)

        def fn(out_rows, weights, order, inv, mask, rows):
            return moe._combine_weighted(
                out_rows, weights, order, inv, BF16,
                moe.Held(mask, rows, True),
            )

        return fn, (S((n, d), BF16), S((t, k), F32), order, inv, mask, rows)

    return build


def _interior(n, d, gated, tiles):
    """The experts' activation between the grouped matmuls and its
    derivative over the held prefix (``moe._interior_held``:
    ``pallas_rows.experts_act`` / ``experts_act_bwd``) at a held cell's
    rows and expert width; ``gated`` False: Nemotron-3-Super's
    relu(.)² experts."""
    def build(S):
        assert pallas_rows.act_tile(n, d, BF16, gated) == tiles

        def fn(up, gate, cot, rows):
            h, pull = jax.vjp(
                lambda u, g: moe._interior_held(u, g, rows, tiles), up, gate
            )
            return h, pull(cot)

        a = S((n, d), BF16)
        return fn, (a, a if gated else None, a, S((), jnp.int32))

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


def _l2(heads):
    """Both kernels of ``pallas_norm.l2_heads`` at a delta-rule mixer's
    q (or k) in its cell: one sequence of 16,384, ``heads`` heads of 128
    a run of columns each, float32."""
    def build(S):
        x = S((1, 16384, heads * 128), F32)

        def fn(x, dy):
            y, pull = jax.vjp(
                lambda x: pallas_norm.l2_heads(x, 128, 128 ** -0.5), x
            )
            return y, pull(dy)[0]

        return fn, (x, x)

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
    # a live window, the banded grid: Trinity-Mini's window layers (five
    # tiles of 512) and Mistral's (five of 1024)
    "flash-bwd-32x4x128-window2048-of-16384": (
        _flash_window(16384, 4, 2048), 3),
    "flash-bwd-32x8x128-window4096-of-8192": (
        _flash_window(8192, 8, 4096), 3),
    # latent attention expanded (GLM-4.7-Flash): 20 heads of 256
    "flash-fwd-20x256": (_flash(20, 256, grad=False), 1),
    "flash-bwd-20x256": (_flash(20, 256, grad=True), 3),
    # latent attention at 192 score channels, values padded to them
    # (Kimi-Linear): a head and a half of lanes, the tiles of 256
    "flash-fwd-32x192": (_flash(32, 192, grad=False), 1),
    "flash-bwd-32x192": (_flash(32, 192, grad=True), 3),
    # a selection of keys (Keye-VL-2.0): the ``_sel`` kernels
    # GQA at head size 256 (Qwen3-Next)
    "flash-fwd-16x2x256-of-16384": (_flash_gqa_256(grad=False), 1),
    "flash-bwd-16x2x256-of-16384": (_flash_gqa_256(grad=True), 3),
    "flash-fwd-sel-32x4x128": (_flash_selected(grad=False), 1),
    "flash-bwd-sel-32x4x128": (_flash_selected(grad=True), 3),
    # a selection a KV head (MiniCPM-SALA): the same kernels, the tile's
    # row picked by the head's group
    "flash-fwd-sel-32x2x128-by-kv-head": (
        _flash_selected_by_kv_head(grad=False), 1),
    "flash-bwd-sel-32x2x128-by-kv-head": (
        _flash_selected_by_kv_head(grad=True), 3),
    # and its alignment term (``ops/pallas_align.py``)
    "align-kl-16x64-32x4x128": (_align(), 1),
    # a Mamba-2 layer's scan (Nemotron-3-Super): ``ops/pallas_ssd.py``
    "ssd-fwd-128x64-8x128": (_ssd(grad=False), 1),
    "ssd-bwd-128x64-8x128": (_ssd(grad=True), 2),
    # a lightning layer's recurrence (MiniCPM-SALA): one head of 128 a
    # group
    "ssd-fwd-32x128-32x128": (_ssd_lightning(grad=False), 1),
    "ssd-bwd-32x128-32x128": (_ssd_lightning(grad=True), 2),
    # a Mamba-1 layer's selective scan (Jamba2-3B): the gradient alone
    # still needs the forward kernel, for the chunks' starting states
    "sscan-fwd-5120x16": (_sscan(grad=False), 1),
    "sscan-bwd-5120x16": (_sscan(grad=True), 2),
    # a gated-delta-rule layer's walk over the chunks (Qwen3-Next)
    # behind the triangular inverse of whole chunks: the gradient alone
    # needs the inverse, the state pass and the walk back
    "gdn-fwd-16x2x128": (_gdn(grad=False), 2),
    "gdn-bwd-16x2x128": (_gdn(grad=True), 3),
    # both mixers' causal conv (``ops/pallas_conv.py``)
    "conv-fwd-10240-bf16": (_conv(10240, BF16, grad=False), 1),
    "conv-bwd-10240-bf16": (_conv(10240, BF16, grad=True), 2),
    "conv-fwd-8192-of-12288-bf16": (_conv_of_projection(grad=False), 1),
    "conv-bwd-8192-of-12288-bf16": (_conv_of_projection(grad=True), 2),
    "conv-fwd-5120-f32": (_conv(5120, F32, grad=False), 1),
    "conv-bwd-5120-f32": (_conv(5120, F32, grad=True), 2),
    # the gated short conv (LFM2's ``C`` part)
    "gated-conv-fwd-3x2048-bf16": (_gated_conv(grad=False), 1),
    "gated-conv-bwd-3x2048-bf16": (_gated_conv(grad=True), 2),
    # the routed blocks' sums over the held rows (``ops/pallas_rows.py``)
    "rows-sum-8192x8-2048": (_held_rows(8192, 8, 2048, (2048, 1024)), 1),
    "rows-sum-16384x8-2048": (_held_rows(16384, 8, 2048, (2048, 512)), 1),
    "rows-sum-back-8192x22-1024": (
        _held_rows(8192, 22, 1024, (2048, 1024), bound=65536, back=True), 1),
    # the experts' interior over the held prefix, forward and back, at
    # the seven held cells' rows x expert width: Trinity-Mini and
    # Kimi-Linear, Mellum2, Keye-VL-2.0, GLM-4.7-Flash, Nemotron-3-Super
    # (its pairs cut to 65,536 rows, no gate), Qwen3-Next
    "experts-act-131072x1024": (
        _interior(131072, 1024, True, (2048, 1024, 16)), 2),
    "experts-act-262144x896": (
        _interior(262144, 896, True, (2048, 896, 16)), 2),
    "experts-act-65536x768": (_interior(65536, 768, True, (2048, 768, 16)), 2),
    "experts-act-65536x1536": (
        _interior(65536, 1536, True, (2048, 1536, 16)), 2),
    "experts-act-relu2-65536x2688": (
        _interior(65536, 2688, False, (2048, 2688, 16)), 2),
    "experts-act-163840x512": (
        _interior(163840, 512, True, (2048, 512, 32)), 2),
    # its two rank norms
    "norm-bwd-d768": (_norm(768, grad=True, residual=False), 1),
    "norm-bwd-d512": (_norm(512, grad=True, residual=False), 1),
    "norm-fwd-d1600": (_norm(1600, grad=False, residual=False), 1),
    "norm-bwd-d1600": (_norm(1600, grad=True, residual=False), 1),
    "norm-residual-bwd-d1600": (_norm(1600, grad=True, residual=True), 2),
    "norm-fwd-d2048": (_norm(2048, grad=False, residual=False), 1),
    "norm-bwd-d2048": (_norm(2048, grad=True, residual=False), 1),
    "norm-residual-bwd-d2048": (_norm(2048, grad=True, residual=True), 2),
    # q's and k's L2 norm a head on the flat layout, forward and back
    # (Kimi-Linear's 32 heads, Qwen3-Next's 16 key heads)
    "l2-heads-32x128": (_l2(32), 2),
    "l2-heads-16x128": (_l2(16), 2),
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
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == n_kernels
    if case.startswith("norm-") and case.endswith("-d1600"):
        # the width as it is (PR 74): nothing rounded up to 13 whole
        # lane tiles around the kernels, no pad made and none taken off
        assert "1664" not in text
        assert " pad(" not in text and " slice(" not in text
    if "-sel-" in case:
        names = ("flash_fwd_sel", "flash_bwd_dq_sel", "flash_bwd_dkv_sel")
        assert sum(name in text for name in names) == n_kernels
    if case.startswith("align-"):
        assert "%align_kl" in text
    if case.startswith("ssd-"):
        # the gradient alone needs no y: the forward kernel is dead code
        # there, and the backward rule's two kernels are what is left
        names = ("ssd_states", "ssd_bwd") if "bwd" in case else ("ssd_fwd",)
        assert all(f"%{name}" in text for name in names)
    if case.startswith("sscan-"):
        names = ("sscan_fwd", "sscan_bwd") if "bwd" in case else ("sscan_fwd",)
        assert all(f"%{name}" in text for name in names)
    if case.startswith("gdn-"):
        import math
        import re

        names = ("gdn_states", "gdn_bwd") if "bwd" in case else ("gdn_fwd",)
        assert all(f"%{name}" in text for name in names)
        assert _kernel_calls(text, "tri_inverse") == 1
        # the 256 chunks' dependence is the kernels' grid: no loop of
        # the compiler's around a chunk step, and what XLA makes of
        # whole chunks beside them (K K^T and T; going back the states,
        # 537 MB, T's cotangent and A's) fits beside the cell's 9.4 GB
        # of state: 0.81 and 2.03 GB by the compiler's count (0.95 and
        # 2.3 before PR 71)
        assert "while(" not in text and " while " not in text
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < (2.23e9 if "bwd" in case else 0.89e9)
        # T and the cotangents of T and A cross HBM a key head's two
        # value heads side by side, f32[.., 64, 128], and A going
        # forward not at all: no array of the 8,192 chunk-heads ends in
        # [64, 64] or [32, 32], which a tile pads to its 128 lanes
        # (K K^T and its cotangent, a matrix a KEY head, are what is
        # left)
        padded = re.findall(r"f32\[([\d,]*),(?:64,64|32,32)\]", text)
        assert padded
        assert all(
            math.prod(map(int, dims.split(","))) <= 256 * 16
            for dims in padded
        ), sorted(set(padded))
    if case.startswith("l2-heads-"):
        import re

        for name in ("l2_heads_fwd", "l2_heads_bwd"):
            assert re.search(rf"%\w*{name}[_.\d]* = ", text), name
        # x as it lies: a head a run of columns, nothing made [S, H, D]
        assert not re.search(r"f32\[[\d,]*16384,\d+,128\]", text)
    if case.startswith("rows-sum-"):
        assert "%rows_sum" in text
    if case.startswith("experts-act-"):
        for name in ("experts_act", "experts_act_bwd"):
            assert _kernel_calls(text, name) == 1
    if case.startswith("gated-conv-"):
        names = ("gated_conv_fwd", "gated_conv_bwd")
        assert all(f"%{n}" in text for n in names[:2 if "bwd" in case else 1])
        # the in-projection as it lies: no window of it copied out, no
        # float32 copy, and going back ONE array of the three cotangents
        entry = text.split("ENTRY")[1]
        assert "f32[8,4096,2048]" not in entry
        assert "f32[8,4096,6144]" not in entry
        assert " slice(" not in entry and " concatenate(" not in entry
        assert " pad(" not in entry
    if case.startswith("conv-"):
        names = ("conv_fwd", "conv_bwd") if "bwd" in case else ("conv_fwd",)
        assert all(f"%{name}" in text for name in names)
        # x as it lies: no padded copy and no float32 copy of it
        assert "8195" not in text and "16387" not in text
        if "of-12288" in case:
            assert "f32[1,16384,8192]" not in text.split("ENTRY")[1]
        elif "bf16" in case:
            assert "f32[1,8192,10240]" not in text.split("ENTRY")[1]


def test_gated_delta_rule_compiles_a_stretch_at_a_time(topo, chip):
    """The gated delta rule's XLA body at Qwen3-Next's widths (16 key
    heads shared by 32 value heads of 128, one sequence of 16,384,
    chunks of 64, float32 operands as the mixer hands them over),
    forward and backward, for a described v5e: matmuls and no kernel
    (the op's fallback since PR 64), and with each stretch of 2,048 tokens under its own checkpoint
    the compiler counts 1.26 GB of temporaries (on bf16 operands 0.94
    GB where the sequence whole took 3.6, and two periods' step then
    needed 15.98 GiB of 15.75). No array has blocks of 16 rows as its
    trailing dimensions, which a tile pads to 128 lanes: the inverse
    works with the batch on the lanes."""
    import re

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    s = 16384
    args = (
        struct((1, s, 16, 128), F32), struct((1, s, 16, 128), F32),
        struct((1, s, 32, 128), F32), struct((1, s, 32), F32),
        struct((1, s, 32), F32),
    )
    # the fallback's own entry: a mesh of several devices rules the
    # kernels out
    several = jax.sharding.Mesh(topo.devices[:2], ("dp",))
    assert not gated_delta.in_kernels(128, 128, mesh=several)
    loss = lambda *a: gated_delta.gated_delta_rule(  # noqa: E731
        *a, chunk=64, mesh=several
    ).astype(F32).sum()
    compiled = jax.jit(jax.grad(loss, argnums=range(5))).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6e9
    assert not re.search(r"f32\[[\d,]*,16,\d+,16\]", text)


def _vector_rule_args(chip):
    """Kimi-Linear's rule: 32 heads of 128 key and 128 value channels,
    one sequence of 16,384, float32 operands as the mixer hands them."""
    def struct(shape):
        return jax.ShapeDtypeStruct(shape, F32, sharding=chip)

    wide = struct((1, 16384, 32, 128))
    return wide, wide, wide, wide, struct((1, 16384, 32))


def test_vector_delta_rule_compiles_a_stretch_at_a_time(topo, chip):
    """The delta rule with a decay a key channel at Kimi-Linear's widths
    (32 heads of 128 key and 128 value channels, one sequence of 16,384,
    chunks of 64 in sub-blocks of 16, float32 operands as the mixer
    hands them over), its XLA body (the op's fallback since PR 66),
    forward and backward, for a described v5e: matmuls and no kernel,
    and with each stretch of 1,024 tokens under its own checkpoint the
    compiler counts 0.53 GB of temporaries (1.03 at stretches of 2,048).
    No array holds a whole chunk's [64, 64, 128] differences: the
    largest with two token axes and the channels is a sub-block's
    [16, 16, 128]."""
    import re

    # the fallback's own entry: a mesh of several devices rules the
    # kernels out
    several = jax.sharding.Mesh(topo.devices[:2], ("dp",))
    assert not gated_delta.in_kernels(
        128, 128, mesh=several, per_channel=True
    )
    loss = lambda *a: gated_delta.gated_delta_rule(  # noqa: E731
        *a, chunk=64, mesh=several
    ).astype(F32).sum()
    compiled = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        *_vector_rule_args(chip)
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7e9
    assert not re.search(r"f32\[[\d,]*64,64,128\]", text)
    assert re.search(r"f32\[[\d,]*16,16,128\]", text)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_vector_delta_rule_kernels_compile_for_v5e(chip, grad):
    """The same rule on its kernels (``ops/pallas_kda.py``: what a v5e
    runs at these widths on one device): the pairs, the triangular
    inverse and the walk going forward; going back the pairs and the
    inverse again, the state pass, the walk back and the pairs'
    pull-back — no loop of the compiler's around a chunk step and no
    stretch. What XLA holds around them, by the compiler's
    count: 1.62 GB of temporaries forward (A, M and T, float32
    [256, 32, 64, 64] each, the 64 padded to 128 lanes: 268 MB, and what
    the substitution holds between A and T) and 2.97 GB going back
    (those, the states every chunk starts from, 537 MB, the walk's parts
    of dq, dk and dγ, 268 MB each, and dT, dM and dA), which the cell's
    step fits beside its train state
    (``test_kimi_cell_fits_the_chip``): 1.61 and 2.96 GB since T is the
    kernel ``tri_inverse``'s (PR 71). Neither a sub-block's
    [16, 16, 128] nor a chunk's [64, 64, 128] differences exist as an
    array."""
    import re

    assert gated_delta.in_kernels(128, 128, per_channel=True)
    fwd = lambda *a: gated_delta.gated_delta_rule(*a)  # noqa: E731
    fn = jax.grad(
        lambda *a: fwd(*a).astype(F32).sum(), argnums=range(5)
    ) if grad else fwd
    compiled = jax.jit(fn).lower(*_vector_rule_args(chip)).compile()
    text = compiled.as_text()
    names = (
        ("kda_pairs", "kda_states", "kda_bwd", "kda_pairs_bwd") if grad
        else ("kda_pairs", "kda_fwd")
    ) + ("tri_inverse",)
    assert text.count("tpu_custom_call") == len(names)
    for name in names:
        assert re.search(rf"%\w*{name}[_.\d]* = ", text), name
    assert "while(" not in text and " while " not in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (3.26e9 if grad else 1.77e9)
    assert not re.search(r"f32\[[\d,]*(16,16|64,64),128\]", text)
    # the inverse is a kernel: nothing of the substitution's — blocks of
    # 16 or 32 rows, the batch on the lanes — is XLA's
    assert not re.search(r"f32\[[\d,]*(16,16|32,32),8192\]", text)


@pytest.mark.parametrize(
    "case,kernels",
    [
        ("flash-bwd-25x64-packed",
         {"flash_fwd_packed", "flash_bwd_dq_packed", "flash_bwd_dkv_packed"}),
        ("flash-bwd-16x128", {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    ],
)
def test_flash_layout_around_the_kernels(chip, case, kernels):
    """Head size 64 runs the three packed kernels on slabs of the
    projections' own ``[8, 1024, 1600]`` arrays: no array with the heads
    padded to 26 and moved in front of the sequence (``[8,26,1024,64]``,
    ``[104,2,1024,64]``) exists around them — before PR 32 seven relayout
    passes a tensor did, 80-95 ms of GPT-2 XL's step. Head size 128 keeps
    the unpacked kernels."""
    import re

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    fn, args = CASES[case][0](struct)
    text = jax.jit(fn).lower(*args).compile().as_text()
    named = set()
    for line in text.splitlines():
        if "tpu_custom_call" in line:
            m = re.match(
                r"\s*(?:ROOT )?%\w*?(flash_(?:fwd|bwd_dq|bwd_dkv)"
                r"(?:_packed)?)[_.\d]* = ", line
            )
            assert m, line[:160]
            named.add(m.group(1))
    assert named == kernels
    for shape in ("[8,26,1024,64]", "[104,2,1024,64]"):
        assert shape not in text


def _pallas_grids(jaxpr, found):
    """{kernel name: grid} of every ``pallas_call`` in a jaxpr."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = tuple(eqn.params["grid_mapping"].grid)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr's
                if hasattr(sub, "eqns"):
                    _pallas_grids(sub, found)
    return found


@pytest.mark.parametrize(
    "case,grids",
    [
        # no window: the square, the grid before PR 48
        ("flash-bwd-16x128", {"flash_fwd": (128, 2, 2),
                              "flash_bwd_dq": (128, 2, 2),
                              "flash_bwd_dkv": (128, 2, 2)}),
        ("flash-bwd-25x64-packed", {"flash_fwd_packed": (8, 13, 2, 2),
                                    "flash_bwd_dq_packed": (8, 13, 2, 2),
                                    "flash_bwd_dkv_packed": (8, 13, 2, 2)}),
        ("flash-bwd-sel-32x4x128", {"flash_fwd_sel": (32, 8, 8),
                                    "flash_bwd_dq_sel": (32, 8, 8),
                                    "flash_bwd_dkv_sel": (32, 8, 8)}),
        # a live window: the forward a band of 3 blocks of 1024 and the
        # backward one of 5 of 512 (the square had 16 x 16 of 1024);
        # Mistral's a band of 5 of 1024 (8 x 8)
        ("flash-bwd-32x4x128-window2048-of-16384",
         {"flash_fwd": (32, 16, 3), "flash_bwd_dq": (32, 32, 5),
          "flash_bwd_dkv": (32, 32, 5)}),
        ("flash-bwd-32x8x128-window4096-of-8192",
         {"flash_fwd": (32, 8, 5), "flash_bwd_dq": (32, 8, 5),
          "flash_bwd_dkv": (32, 8, 5)}),
    ],
)
def test_flash_grids(case, grids):
    """The grid each flash kernel is lowered with: without a live window
    the square ``(…, q blocks, k blocks)`` it always had, under one the
    band of key blocks the window admits, the backward's at the tile
    taken from the window."""
    fn, args = CASES[case][0](jax.ShapeDtypeStruct)
    assert _pallas_grids(jax.make_jaxpr(fn)(*args).jaxpr, {}) == grids


# ---- the whole train step: kernel names and phase scopes ------------------
# What a device trace shows for an operation is its HLO instruction's
# name, and what the program's reducer (observability/runtime_timer.py)
# knows of its place in the step is its ``op_name`` metadata. Both are
# decided by the chip's compiler, so both are pinned here, at the
# benchmark's three recipes cut to two layers.

STEP_CASES = {
    # GPT-2 XL widths: head size 64, so the head-packed kernels
    "gpt2-like": dict(
        model="gpt2-1.5b",
        overrides=dict(n_layer=2, max_seq=1024, remat="full",
                       param_dtype="bfloat16"),
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(8, 1024),
        kernels={"flash_fwd_packed", "flash_bwd_dq_packed",
                 "flash_bwd_dkv_packed", "norm_fwd", "norm_bwd"},
        scopes={"embed", "attn", "mlp", "head_loss", "optimizer"},
        stat_tiles="f32[104,1024,8]",  # [B·slabs, S, 8]
        stat_rows="8,25,1024",  # [B, H, S]: span 512.5, never made
    ),
    # Mistral widths: head size 128, GQA 32/8, the window live
    "mistral-like": dict(
        model="mistral-7b",
        overrides=dict(n_layer=2, max_seq=2048, attn_window=1024,
                       remat="full", param_dtype="bfloat16"),
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(1, 2048),
        kernels={"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "norm_fwd", "norm_bwd"},
        scopes={"embed", "attn", "mlp", "head_loss", "optimizer"},
        stat_tiles="f32[32,2048,8]",  # [B·H, S, 8]
        stat_rows="1,32,2048",  # span 768.25 under the window
        # the forward's band is both blocks of 1024; the backward's tile
        # is a quarter of the window
        band=(2, 256),
    ),
    # OLMoE's published widths, one layer of 16: 64 experts of width
    # 1024 top-8 through ``lax.ragged_dot`` (the compiler's own grouped
    # matmul and its tile-table kernel), QK-norm as two more norm calls
    "olmoe-like": dict(
        model="olmoe-1b-7b",
        overrides=dict(n_layer=1, remat="full", param_dtype="bfloat16"),
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(2, 4096),
        kernels={"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "norm_fwd", "norm_bwd", "ragged-dot-none",
                 "ragged-dot-metadata"},
        scopes={"embed", "attn", "mlp", "head_loss", "optimizer",
                "moe.route", "moe.sort", "moe.experts", "moe.combine"},
        stat_tiles="f32[32,4096,8]",
        kept=True,  # span 2,048.5
    ),
    # GLM-4.7-Flash's published widths, 1 dense + 1 routed layer + the
    # prediction module, 8 of 64 experts held: latent attention through
    # the unpacked flash kernels at head size 256 (whose backward tile
    # is cut to fit VMEM), the rank norms as norm calls, the shared
    # expert and the module under scopes of their own
    "glm-like": dict(
        model="glm-4.7-flash",
        overrides=dict(n_layer=2, n_experts_held=8, vocab_size=19360,
                       max_seq=8192, remat="full", param_dtype="bfloat16"),
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(2, 8192),
        kernels={"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "norm_fwd", "norm_bwd", "ragged-dot-none",
                 "ragged-dot-metadata", "rows_sum", "experts_act",
                 "experts_act_bwd"},
        scopes={"embed", "attn", "attn.latent", "mlp", "head_loss", "mtp",
                "optimizer", "moe.route", "moe.sort", "moe.experts",
                "moe.combine", "moe.shared"},
        stat_tiles="f32[40,8192,8]",
        kept=True,
    ),
    # Keye-VL-2.0's language tower as the benchmark's cell runs it (12
    # layers in one scan, 16 of 128 experts held): the indexer, the
    # selection and the alignment term under scopes of their own, the
    # unpacked flash kernels at head size 128 with the selection
    # operand, under names of their own, and the alignment kernel
    "keye-cell": dict(
        model="keye-vl-2.0",
        overrides=dict(n_layer=12, n_experts_held=16, expert_offset=0,
                       vocab_size=18992, max_seq=8192, remat="full",
                       param_dtype="bfloat16"),
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(1, 8192),
        kernels={"flash_fwd_sel", "flash_bwd_dq_sel", "flash_bwd_dkv_sel",
                 "align_kl", "norm_fwd", "norm_bwd", "ragged-dot-none",
                 "ragged-dot-metadata", "rows_sum", "experts_act",
                 "experts_act_bwd"},
        scopes={"embed", "attn", "attn.index", "attn.select",
                "attn.index_loss", "mlp", "head_loss", "optimizer",
                "moe.route", "moe.sort", "moe.experts", "moe.combine"},
        stat_tiles="f32[32,8192,8]",
        kept=True,
    ),
    # the dp=4 ZeRO-1 recipe: f32 parameters, tied head
    "zero1-dp4": dict(
        model="gpt2-1.5b",
        overrides=dict(n_layer=2, max_seq=1024, remat="full",
                       param_dtype="float32"),
        optimizer={}, comm=dict(update_sharding="zero1"), chips=4,
        batch=(32, 1024),
        kernels={"flash_fwd_packed", "flash_bwd_dq_packed",
                 "flash_bwd_dkv_packed", "norm_fwd", "norm_bwd"},
        scopes={"embed", "attn", "mlp", "head_loss", "zero.pack",
                "zero.exchange", "zero.update", "zero.gather"},
        stat_tiles="f32[104,1024,8]",  # 8 of the 32 sequences a chip
        stat_rows="8,25,1024",
    ),
    # the same under ZeRO-2, two microbatches: an exchange (and the tied
    # head's buckets) inside the accumulation scan. Layout guard only.
    "zero2-dp4": dict(
        model="gpt2-1.5b",
        overrides=dict(n_layer=2, max_seq=1024, remat="full",
                       param_dtype="float32"),
        optimizer={}, comm=dict(update_sharding="zero2"), chips=4,
        grad_accum=2, batch=(32, 1024),
    ),
}


_STEP_TEXT = {}
_STEP_MEMORY = {}  # case -> the compiled step's memory_analysis()
_STEP_LOWERED = {}  # case -> the step's text before XLA, where kept


def _compiled_step(topo, case):
    """(builder, compiled text, counters set while tracing) of one of
    STEP_CASES, compiled for the described chips once a session."""
    if case in _STEP_TEXT:
        return _STEP_TEXT[case]
    from dlrover_tpu.observability import tracing
    from dlrover_tpu.parallel import MeshConfig, build_mesh
    from dlrover_tpu.parallel import sharding as shd
    from dlrover_tpu.train import (
        TrainStepBuilder, batch_sharding, make_optimizer,
    )
    from dlrover_tpu.train.train_step import abstract_train_state

    spec = STEP_CASES[case]
    cfg = get_config(spec["model"], **spec["overrides"])
    mesh = build_mesh(
        MeshConfig(dp=-1), devices=list(topo.devices[: spec["chips"]])
    )
    opt = make_optimizer(
        learning_rate=1e-4, warmup_steps=10, decay_steps=1000,
        **spec["optimizer"],
    )
    comm = shd.CommConfig(**spec["comm"]) if spec["comm"] else None
    builder = TrainStepBuilder(
        cfg, mesh, opt, comm=comm, grad_accum=spec.get("grad_accum", 1)
    )
    assert bool(builder.update_sharding) == bool(comm), (
        builder.update_sharding_reason
    )
    state = abstract_train_state(
        cfg, mesh, opt, comm=builder.comm_resolved
    )
    batch = {
        k: jax.ShapeDtypeStruct(
            spec["batch"], jnp.int32, sharding=batch_sharding(mesh)
        )
        for k in ("tokens", "targets")
    }
    tracing._counters.clear()
    lowered = builder.build().lower(state, batch)
    if spec.get("keep_lowered"):
        _STEP_LOWERED[case] = lowered.as_text()
    compiled = lowered.compile()
    _STEP_MEMORY[case] = compiled.memory_analysis()
    _STEP_TEXT[case] = builder, compiled.as_text(), dict(tracing.counters())
    return _STEP_TEXT[case]


@pytest.mark.parametrize(
    "case", sorted(c for c in STEP_CASES if "scopes" in STEP_CASES[c])
)
def test_step_names_its_kernels_and_phases(topo, case):
    import re

    from dlrover_tpu.observability import runtime_timer

    spec = STEP_CASES[case]
    builder, text, counters = _compiled_step(topo, case)
    comm = spec["comm"]

    # every Pallas kernel's instruction is named after the kernel
    kernel_lines = [
        line for line in text.splitlines() if "tpu_custom_call" in line
    ]
    named = set()
    for line in kernel_lines:
        m = re.match(r"\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = ", line)
        assert m and m.group(1) in spec["kernels"], line[:160]
        named.add(m.group(1))
    assert named == spec["kernels"]

    # and every part of the step shows in the op_names the reducer reads
    op_names = runtime_timer.op_names_from_hlo(text)
    scopes = {runtime_timer.scope_of(n) for n in op_names.values()}
    assert spec["scopes"] <= scopes, spec["scopes"] - scopes
    phases = {
        runtime_timer.phase_of("%" + i + " = x", n)
        for i, n in op_names.items()
    }
    wanted = {"forward", "recompute", "backward", "optimizer"}
    if comm:
        wanted.add("exchange")
        plan = builder._plan
        assert counters["zero.exchange_bytes"] == (
            (plan.n_buckets + plan.n_tie_buckets) * plan.bucket_elems * 4
        )
    assert wanted <= phases, wanted - phases
    # which attention kernels a trace took, and that the packed ones
    # keep the projections' layout in the whole step too
    packed = "flash_fwd_packed" in spec["kernels"]
    assert counters["attn.heads_per_slab"] == (2 if packed else 1)
    # the norm kernels' call sites at a width off the 128 lanes (PR 74:
    # GPT-2 XL's 1,600 columns as they are, no [.., 1664] array made)
    unaligned = builder.cfg.d_model % 128 != 0
    assert (counters["norm.unaligned_calls"] > 0) == unaligned
    if unaligned:
        assert not re.search(r"[\[,]1664[\],]", text)
    # the forward grid's inner axis: the band of key blocks under a live
    # window, else every key block; the backward's tile under a window
    if "flash_fwd_sel" not in spec["kernels"]:  # (no window with one)
        seq = spec["batch"][1]
        band_blocks, window_tile = spec.get("band", (None, 0))
        assert counters["attn.window_tile"] == window_tile
        assert counters["attn.band_blocks"] == (
            band_blocks or seq // pallas_attention._fit_block(
                seq, builder.cfg.attn_block_k
            )
        )
    if packed:
        assert "[8,26,1024,64]" not in text and "[104,2,1024,64]" not in text
    # the row statistics (lse, delta) stay in the kernels' own tiles from
    # the kernel that makes them to those that read them: no slice,
    # broadcast or copy of that shape, and delta comes from the dq
    # kernel, not from a reduce over 64-lane heads (before PR 35 four
    # operations a layer, 33 ms of GPT-2 XL's step)
    assert counters["attn.delta_in_kernel"] == 1
    made = [
        line for line in text.splitlines()
        if re.match(
            r"\s*(?:ROOT )?%[\w.\-]+ = " + re.escape(spec["stat_tiles"]),
            line,
        )
    ]
    # what ``remat: full`` keeps of the attention (``decoder.
    # keeps_attention_output``: a forward kernel that executes 2,048
    # keys a query or more): where it keeps nothing, no [B, H, S] statistics exist and
    # every layer body runs its forward kernel twice
    # (the counter counts the layers that keep it)
    kept = spec.get("kept", False)
    assert counters["attn.output_kept"] == (
        builder.cfg.n_attention_layers if kept else 0
    )
    calls = {
        k: sum(bool(re.match(rf"\s*(?:ROOT )?%{k}[.\d]* = ", ln))
               for ln in kernel_lines)
        for k in spec["kernels"] if k.startswith("flash_")
    }
    fwd = next(k for k in calls if k.startswith("flash_fwd"))
    bwd = calls[fwd.replace("fwd", "bwd_dq")]
    assert calls[fwd] == (bwd if kept else 2 * bwd), calls
    if kept:
        # the statistics are kept as numbers: the forward slices the
        # [B, H, S] array out of the kernel's tiles (a fusion with the
        # tiles as its parameter) and the backward pads it into tiles
        # again, once a kernel pair; nothing else touches either
        made = [ln for ln in made if " parameter(" not in ln]
        pads = [ln for ln in made if " pad(" in ln]
        assert len(pads) == bwd and all(
            "transpose(jvp" in ln for ln in pads
        ), [ln[:160] for ln in pads]
        # (a copy-done of the shape is the compiler prefetching the
        # padded tiles to another memory space, not a pass of the step's)
        made = [
            ln for ln in made if ln not in pads and " copy-done(" not in ln
        ]
    else:
        # [B, H, S] float32, or stacked by a scan of layers
        assert not re.search(rf"f32\[(?:\d+,)?{spec['stat_rows']}\]", text)
    by_kernel = [
        re.search(r" get-tuple-element\(%(flash_\w+?)[.\d]*\), index=1", ln)
        for ln in made
    ]
    assert all(by_kernel), [ln[:160] for ln in made]
    assert {m.group(1) for m in by_kernel} == {
        k for k in spec["kernels"] if k.startswith(("flash_fwd", "flash_bwd_dq"))
    }
    assert "f32[104,2,1024,8]" not in text  # the tiles before PR 35
    assert not re.search(r"= f32\[8,1024,25\]\S* reduce\(", text)
    # the held rows' paths (PR 59) are a held model's alone: with every
    # expert here — or no routed block — a step holds no ``rows_sum``
    # call and no loop under the routed blocks' two row scopes
    row_loops = [
        name for name, op_name in op_names.items()
        if name.startswith("while")
        and runtime_timer.scope_of(op_name) in ("moe.sort", "moe.combine")
    ]
    held_rows = [ln for ln in kernel_lines if "%rows_sum" in ln]
    if "rows_sum" in spec["kernels"]:
        assert row_loops and held_rows
        assert all(
            "/moe.sort/" in ln or "/moe.combine/" in ln for ln in held_rows
        )
    else:
        assert not row_loops and not held_rows
        assert not any(
            "/moe.sort/" in ln or "/moe.combine/" in ln for ln in kernel_lines
        )
    if builder.cfg.n_experts:
        # which interior the routed blocks traced: by the held prefix
        # where a part of the experts is here, the XLA body otherwise
        assert counters["moe.experts_by_prefix"] == int(
            "experts_act" in spec["kernels"]
        )
    if spec["model"] == "olmoe-1b-7b":
        # the routed layer's grouped matmuls under their scope (by the
        # kernel's name: it has no name stack)
        grouped = [
            name for name, op_name in op_names.items()
            if name.startswith("ragged-dot-none")
            and runtime_timer.scope_of(op_name) == "moe.experts"
        ]
        assert len(grouped) == 12  # a layer: 3 forward, 3 recomputed, 6 back
    if spec["model"] == "keye-vl-2.0":
        # the alignment term through its kernel: one custom call in the
        # step, in the forward's scanned body and under the term's
        # scope; the recomputed body holds none (its derivative is a
        # kept residual)
        assert counters["attn.align_in_kernel"] == 1
        (align,) = [ln for ln in kernel_lines if "%align_kl" in ln]
        name = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", align).group(1)
        assert runtime_timer.scope_of(op_names[name]) == "attn.index_loss"
        assert runtime_timer.phase_of(align, op_names[name]) == "forward"
        # GQA 32 / 4 heads of 128 over d 2048, one scanned layer body:
        # the forward, dq and dk/dv once each (before PR 42 the forward
        # twice: ``full`` remade its output)
        flash = [ln for ln in kernel_lines if "%flash_" in ln]
        assert len(flash) == 3 and all(
            "bf16[32,8192,128]" in ln and "s8[1,8192,8192]" in ln
            for ln in flash
        )
        _no_whole_score_array(text)
    if spec["model"] == "glm-4.7-flash":
        # the flash kernels run at head size 256, all three layers
        flash = [ln for ln in kernel_lines if "%flash_" in ln]
        assert len(flash) == 3 * 3 and all(
            "bf16[40,8192,256]" in ln for ln in flash
        )
    for line in kernel_lines:
        name = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1)
        kernel, phase = name.split(".")[0], runtime_timer.phase_of(
            line, op_names[name]
        )
        if kernel.startswith("ragged-dot"):
            continue  # the compiler's kernels run in every phase
        if kernel == "rows_sum":
            # the combine's sum going forward, the dispatch's coming back
            want = "moe.sort" if phase == "backward" else "moe.combine"
            assert runtime_timer.scope_of(op_names[name]) == want
            continue
        if kernel in ("experts_act", "experts_act_bwd"):
            # the experts' interior over the held prefix (PR 72), under
            # the scope the XLA body's passes had
            assert runtime_timer.scope_of(op_names[name]) == "moe.experts"
            assert (phase == "backward") == (kernel == "experts_act_bwd")
            continue
        if kernel.startswith("flash_bwd") or kernel == "norm_bwd":
            assert phase == "backward", (name, op_names[name])
        else:
            assert phase in ("forward", "recompute"), (name, op_names[name])


def _no_whole_score_array(text):
    """A selecting model's step at 8192 tokens: no float [.., S, S] is
    ever whole — not the attention's [B, H, S, S], not an index head's
    [B, S, S], not the head-summed index scores — the selection is int8
    [B, S, S] a layer (a residual of the forward: the stacked [L, B, S,
    S] is the scan's), and no mask is among the step's results."""
    import re

    assert not re.search(r"(?:f32|bf16|f16)\[[\d,]*8192,8192\]", text)
    assert not re.search(r"pred\[[\d,]*8192,8192\]", text)
    assert "s8[1,8192,8192]" in text
    entry = next(
        ln for ln in text.splitlines() if ln.startswith("ENTRY ")
    )
    assert "8192,8192" not in entry.split("->")[-1], entry[-400:]


@pytest.mark.parametrize("case", ["zero1-dp4", "zero2-dp4"])
def test_zero_step_has_no_stream_relayout_loop(topo, case):
    """A 2-D array is tiled (8, 128) on the chip and a 1-D one by 1024,
    so a reshape between the 1-D parameter stream and
    ``[n_buckets, bucket_elems]`` compiles to a ``while`` that copies
    one 4 MiB row per trip at a twentieth of the memory's rate (17% of
    the dp=4 step before PR 26). Its signature is a ``while`` that
    carries the whole stream as a 1-D f32 array."""
    import re

    builder, text, _ = _compiled_step(topo, case)
    plan = builder._plan
    assert plan.tie_size and plan.n_buckets > 100
    streams = {plan.padded, plan.n_tie_buckets * plan.bucket_elems}
    whiles = [ln for ln in text.splitlines() if " while(" in ln]
    assert whiles  # the layer scans: the text is the step's
    for ln in whiles:
        carried = ln.split(" while(")[0]
        hit = [
            n for n in re.findall(r"f32\[(\d+)\]", carried)
            if int(n) in streams
        ]
        assert not hit, ln[:200]
    assert "tpu_custom_call" in text


def test_routed_layer_scatters_no_rows(topo):
    """Between token order and expert order rows move by gather in both
    directions of the derivative (``parallel/moe.py``'s ``inv``). A
    gather left to jax's transpose comes back as a scatter-add of
    floating-point rows under a ``moe.*`` scope, which the chip
    serialises where indices repeat: 14 ms each a step at OLMoE's 65,536
    rows before PR 30. Integer scatters (a count, a permutation) are
    allowed there; the embedding's gradient is a row scatter outside."""
    import re

    from dlrover_tpu.observability import runtime_timer

    _, text, _ = _compiled_step(topo, "olmoe-like")
    op_names = runtime_timer.op_names_from_hlo(text)
    scatters = []  # (dtype, shape, scope, line) of every scatter
    for line in text.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* scatter\(", line
        )
        if m:
            scope = runtime_timer.scope_of(op_names.get(m.group(1), ""))
            scatters.append((m.group(2), m.group(3).split(","), scope, line))
    # the text is the step's, and the pattern finds its scatters
    assert any(scope == "embed" for _, _, scope, _ in scatters)
    for dtype, shape, scope, line in scatters:
        if scope.startswith("moe.") and len(shape) > 1:
            assert dtype[0] in "su", line[:200]


def test_backward_tile_of_1024_is_refused_at_head_size_256(chip, monkeypatch):
    """Why ``BWD_BLOCK_256`` is not ``BWD_BLOCK_WIDE``: at 256 channels a
    1024 x 1024 backward tile needs 17.3 MB of the 16 MB of VMEM a kernel
    may use, and Mosaic refuses it (interpret mode takes it). The model's
    own forward blocks (``attn_block_q/k`` 1024) compile with the cut."""
    q = jax.ShapeDtypeStruct((2, 8192, 20, 256), BF16, sharding=chip)

    def loss(q, k, v):
        return pallas_attention.flash_attention(
            q, k, v, causal=True, block_q=1024, block_k=1024
        ).astype(F32).sum()

    def compiled():
        # a fresh function each time: the tile is read while tracing
        fn = jax.grad(lambda q, k, v: loss(q, k, v), argnums=(0, 1, 2))
        return jax.jit(fn).lower(q, q, q).compile()

    assert compiled().as_text().count("tpu_custom_call") == 3
    monkeypatch.setattr(pallas_attention, "BWD_BLOCK_256", (1024, 1024))
    with pytest.raises(Exception, match="vmem"):
        compiled()


def _kernel_calls(text, kernel):
    """Custom calls of ``kernel`` in a compiled step's text (one traced
    under a derivative's rule is ``jvp_<kernel>_``)."""
    import re

    return sum(
        bool(re.match(
            rf"\s*(?:ROOT )?%(?:jvp_)?{kernel}[_.\d]* = .*tpu_custom_call", ln
        ))
        for ln in text.splitlines()
    )


def test_glm_cell_fits_the_chip_at_its_depth(topo):
    """The benchmark's GLM-4.7-Flash configuration as it is run (1 dense
    + 8 routed layers + the module, 2 x 8192 tokens) compiles for a
    described v5e and fills the chip. The count of memory made here
    does not track the chip (D18): 15.42 GB before PR 42, where the
    chip itself read 13.26, and **18.36 GB** since, over the 16.9 GB
    the runtime gives, where the chip reads **14.77** (my chip run, PR
    42) — ``full`` keeps the ten attention layers' kernel output, 1.68
    GB, and the count rises by 2.94. So the limit is this count's own,
    not the chip's: it guards a change that adds a gigabyte unseen.

    What is kept shows in the text: each of the three layer bodies (the
    dense layer, the scanned routed layers, the module's block) holds
    ONE forward kernel where it held two, the scan's residuals hold the
    stacked output ``bf16[8,2,8192,20,256]`` and the statistics as
    numbers ``f32[8,2,20,8192]``, and no stacked tile array."""
    import json
    import pathlib

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads((path / "glm-4.7-flash-ep8-1chip.json").read_text())
    STEP_CASES["glm-cell"] = dict(
        model=config["program"]["model"],
        overrides=config["program"]["overrides"],
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(2, 8192),
    )
    try:
        _, text, counters = _compiled_step(topo, "glm-cell")
    finally:
        del STEP_CASES["glm-cell"]
    stats = _STEP_MEMORY["glm-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    assert 15e9 < need < 18.7e9, need
    assert counters["attn.output_kept"] == 10  # 9 layers and the module
    assert _kernel_calls(text, "flash_fwd") == 3
    assert _kernel_calls(text, "flash_bwd_dq") == 3
    assert "bf16[8,2,8192,20,256]" in text and "f32[8,2,20,8192]" in text
    assert "f32[8,40,8192,8]" not in text
    assert stats.argument_size_in_bytes == pytest.approx(
        6 * 1_133_834_752, rel=1e-3  # bf16 parameters and two moments
    )


def test_kimi_cell_fits_the_chip(topo):
    """The benchmark's Kimi-Linear configuration as it is run (published
    layers 1-5 — a KDA mixer and the dense MLP, then KDA, KDA, latent
    attention, KDA with sixteen held experts each — one sequence of
    16,384 tokens) compiles for a described v5e under the chip's 15.75
    GiB (16.91 GB; 13.98 GiB by this count since PR 69, 14.76 since PR
    66, 14.08 before):
    the vector rule as its kernels in every KDA layer (``ops/
    pallas_kda.py``: under ``remat: full`` the pairs and the forward
    walk twice a layer — the layer's and the remade one, whose pairs the
    backward rule shares —, the state pass, the walk back and the pairs'
    pull-back once; no stretch and none of the scalar rule's kernels),
    the latent layer's three flash kernels at 192 channels with the
    backward's tile of 1024 x 512 (1024 x 1024 asks 17.5 MB of VMEM's
    16 at a head and a half of lanes), its output kept, the convs as
    kernels. No array holds a whole chunk's [64, 64, 128]
    differences."""
    import json
    import pathlib
    import re

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads(
        (path / "kimi-linear-48b-a3b-ep16-1chip.json").read_text()
    )
    STEP_CASES["kimi-cell"] = dict(
        model=config["program"]["model"],
        overrides=config["program"]["overrides"],
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(1, 16384),
    )
    try:
        _, text, counters = _compiled_step(topo, "kimi-cell")
    finally:
        del STEP_CASES["kimi-cell"]
    stats = _STEP_MEMORY["kimi-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    assert 12e9 < need < 15.75 * 2 ** 30, need
    assert stats.argument_size_in_bytes == pytest.approx(
        6 * 828_925_824, rel=1e-3  # bf16 parameters and two moments
    )
    assert counters["kda.layers"] == 4
    assert counters["kda.kernel_layers"] == 4
    assert counters["kda.norm_kernel_layers"] == 4
    # q's and k's L2 norms (PR 69): a kernel each in the layer's forward
    # and again in the one ``full`` remakes (2 x 4 x 2), their
    # derivatives once (2 x 4)
    for kernel, calls in (
        ("kda_pairs", 8), ("tri_inverse", 8), ("kda_fwd", 8),
        ("kda_states", 4), ("kda_bwd", 4), ("kda_pairs_bwd", 4),
        ("l2_heads_fwd", 16), ("l2_heads_bwd", 8),
    ):
        assert _kernel_calls(text, kernel) == calls, kernel
    # between the conv and the rule's kernels [B, S, H, D] is a view:
    # nothing under the scope makes, copies or relays an array of a
    # whole sequence's heads in that form or out of it (the parent held
    # 24 broadcasts of the norms to f32[16384,32,128] and 24 relayouts
    # of them to f32[1,16384,4096], 268 MB each)
    under_rule = [ln for ln in text.splitlines() if "kda.rule" in ln]
    assert under_rule
    assert not [
        ln for ln in under_rule if re.search(
            r"= f32\[(?:1,)?16384,(?:4096|32,128)\]\S* "
            r"(?:copy|reshape|transpose)\(", ln
        ) or re.search(r"= f32\[(?:1,)?16384,32,128\]\S* broadcast\(", ln)
    ]
    assert counters["attn.output_kept"] == 1
    assert counters["ssm.conv_in_kernel"] == 1
    assert _kernel_calls(text, "flash_fwd") == 1
    assert _kernel_calls(text, "flash_bwd_dq") == 1
    assert _kernel_calls(text, "flash_bwd_dkv") == 1
    assert "bf16[32,16384,192]" in text
    assert "gdn_fwd" not in text and "gdn_bwd" not in text
    assert "gdn_states" not in text
    assert not re.search(r"f32\[[\d,]*64,64,128\]", text)


def _count_traced_bodies(monkeypatch, module, kernels):
    """{name: times traced from here on} for ``module``'s kernel bodies
    ``kernels`` = {name: the body's attribute}."""
    traced = dict.fromkeys(kernels, 0)

    def counting(name, kernel):
        def body(*refs, **statics):
            traced[name] += 1
            return kernel(*refs, **statics)

        return body

    for name, attr in kernels.items():
        monkeypatch.setattr(
            module, attr, counting(name, getattr(module, attr))
        )
    return traced


# the causal conv's bodies (``ops/pallas_conv.py``), by kernel name
CONV_BODIES = {"conv_fwd": "_fwd_kernel", "conv_bwd": "_bwd_kernel"}


def _conv_calls_sit_under(text, op_names, scope, forward, backward):
    """A compiled step's ``conv_fwd`` / ``conv_bwd`` calls, counted, each
    under ``scope`` (what the benchmark's mixer-share readers sum)."""
    import re

    assert _kernel_calls(text, "conv_fwd") == forward
    assert _kernel_calls(text, "conv_bwd") == backward
    calls = [
        op_name for name, op_name in op_names.items()
        if name.startswith("conv_")
    ]
    assert len(calls) == forward + backward and all(
        scope in re.split(r"[/()]", op_name) for op_name in calls
    )


def test_nemotron_cell_fits_the_chip_with_its_rows_cut(topo, monkeypatch):
    """The benchmark's Nemotron-3-Super configuration as it is run (one
    period MEMEMEMEM*E + the module, 8 of 512 experts held, 1 x 8192
    tokens): the step the chip's compiler lays out needs under the 16.9
    GB the runtime gives and over 12 GB (14.54 GB by this count, PRs 41
    and 42 alike, where the chip itself reads 14.83 — the one cell whose
    count reads low, D18; PR 42 keeps the two attention layers' kernel
    output, 2 x 68 MB, and neither number moves; bf16 parameters and
    two moments are 8.27 GB of arguments). Every
    part shows under its scope, the attention goes through the unpacked
    flash kernels (GQA 32 / 2 at head size 128), and the held experts'
    rows are cut to 8,192 x 8: no array of 180,224 rows is as wide as
    an expert.

    Since PR 50 the five Mamba-2 layers' scan runs its kernels
    (``ops/pallas_ssd.py``). What they cost BEFORE the step runs is
    held here without a clock (PR 49's form ran as fast and was refused
    for 4.5 s of set-up): while the step is traced the forward kernel's
    body is traced twice (the forward — the primal's and the forward
    rule's are one trace — and the state pass) and the backward's once,
    not once a layer and not once a rule; and in the step LOWERED,
    before XLA, the twenty ``ssd_*`` calls hold three bodies."""
    import json
    import pathlib
    import re

    from dlrover_tpu.observability import runtime_timer

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads(
        (path / "nemotron-3-super-ep64-1chip.json").read_text()
    )
    STEP_CASES["nemotron-cell"] = dict(
        model=config["program"]["model"],
        overrides=config["program"]["overrides"],
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(1, 8192), keep_lowered=True,
    )
    # the kernels' traces are kept by shape for the process: forget the
    # ``ssd-*`` cases' above, and count the bodies traced from here on
    jax.clear_caches()
    traced = _count_traced_bodies(
        monkeypatch, pallas_ssd,
        {"ssd_fwd": "_fwd_kernel", "ssd_bwd": "_bwd_kernel"},
    )
    traced_conv = _count_traced_bodies(monkeypatch, pallas_conv, CONV_BODIES)
    try:
        _, text, counters = _compiled_step(topo, "nemotron-cell")
    finally:
        del STEP_CASES["nemotron-cell"]
    assert traced == {"ssd_fwd": 2, "ssd_bwd": 1}, traced
    assert traced_conv == {"conv_fwd": 1, "conv_bwd": 1}, traced_conv
    bodies = {}
    for line in _STEP_LOWERED.pop("nemotron-cell").splitlines():
        name = re.search(r'kernel_name = "((?:ssd|conv)_\w+)"', line)
        if name:
            bodies.setdefault(name.group(1), []).append(
                re.search(r'body\W+(\w+)', line).group(1)
            )
    assert {k: (len(v), len(set(v))) for k, v in bodies.items()} == {
        "ssd_fwd": (10, 1), "ssd_states": (5, 1), "ssd_bwd": (5, 1),
        "conv_fwd": (10, 1), "conv_bwd": (5, 1),
    }
    stats = _STEP_MEMORY["nemotron-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    assert 12e9 < need < 16.9e9, need
    assert stats.argument_size_in_bytes == pytest.approx(
        6 * 1_378_721_664, rel=1e-3  # bf16 parameters and two moments
    )
    op_names = runtime_timer.op_names_from_hlo(text)
    parts = {
        part for name in op_names.values()
        for part in re.split(r"[/()]", name)
    }
    scopes = {"ssm", "ssm.conv", "ssm.scan", "attn", "mlp", "moe.route",
              "moe.sort", "moe.latent", "moe.experts", "moe.combine",
              "moe.shared", "mtp", "head_loss", "optimizer"}
    assert scopes <= parts, scopes - parts
    kernels = {
        line.split("=")[0].strip().removeprefix("ROOT ").lstrip("%")
        .split(".")[0]
        for line in text.splitlines() if "tpu_custom_call" in line
    }
    assert kernels == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "norm_fwd",
        "norm_bwd", "ragged-dot-none", "ragged-dot-metadata",
        "ssd_fwd", "ssd_states", "ssd_bwd", "conv_fwd", "conv_bwd",
        "rows_sum", "experts_act", "experts_act_bwd",
    }
    # the routed blocks' relu(.)² by the held prefix (PR 72): a block's
    # interior going forward, remade, and its derivative
    assert counters["moe.experts_by_prefix"] == 1
    routed = _kernel_calls(text, "experts_act_bwd")
    assert routed and _kernel_calls(text, "experts_act") == 2 * routed
    # the five layers' conv through its kernels (PR 55), every call
    # under ``ssm.conv``: forward, remade, backward; x read as it lies,
    # no padded float32 copy of it
    assert counters["ssm.conv_in_kernel"] == 1
    _conv_calls_sit_under(text, op_names, "ssm.conv", forward=10, backward=5)
    assert "f32[1,8195,10240]" not in text
    # the five Mamba-2 layers' scan through its kernels, every call
    # under the scope the benchmark's ``ssm.*`` readers sum: the forward
    # once in the forward and once remade, and in the backward the pass
    # that makes the chunks' starting states and the backward kernel —
    # where the XLA body ran three forwards a backward
    assert counters["ssm.scan_in_kernel"] == 1
    assert _kernel_calls(text, "ssd_fwd") == 10
    assert _kernel_calls(text, "ssd_states") == 5
    assert _kernel_calls(text, "ssd_bwd") == 5
    scan_calls = [
        (name, op_name) for name, op_name in op_names.items()
        if name.startswith("ssd_")
    ]
    assert len(scan_calls) == 20 and all(
        "ssm.scan" in re.split(r"[/()]", op_name)
        for _, op_name in scan_calls
    )
    assert sorted(
        runtime_timer.phase_of(f"%{name} = x", op_name)
        for name, op_name in scan_calls
    ) == ["backward"] * 10 + ["forward"] * 5 + ["recompute"] * 5
    # the starting states live only around the backward kernel, not at
    # the step's peak: the count of memory is the parent's (14.54 GB)
    assert need < 14.65e9, need
    # the trunk's attention layer and the module's keep their kernel's
    # output (PR 42): one forward call each where there were two
    assert counters["attn.output_kept"] == 2
    assert _kernel_calls(text, "flash_fwd") == 2
    assert _kernel_calls(text, "flash_bwd_dq") == 2
    assert "[65536,2688]" in text and "[65536,1024]" in text
    assert "[180224,2688]" not in text
    # no score or decay block in memory, of all 128 heads at once or of
    # a block of 16 (the XLA body's), at either chunk
    assert not re.search(r"\[1,(?:64|32),\d+,16,(?:128,128|256,256)\]", text)


def test_jamba_cell_compiles_with_its_runs_scanned(topo, monkeypatch):
    """The benchmark's Jamba2-3B configuration as it is run (one period
    of 14 mixer + MLP layers, 1 x 8192 tokens) compiles for a described
    v5e: the two runs of ``m-`` as scans over their own stacks with the
    unit the remat unit, the attention layer's two parts unrolled. The
    count of memory made here reads high (D18: the chip reads 15.84 GB,
    and the compiler's own check, which passes, is what says the step
    fits), so it is held to its own reading, under the XLA body's 19.04.
    The kernels are the unpacked flash kernels at 20 / 1 heads of 128,
    the fused norms and, since PR 54, the selective scan's two
    (``ops/pallas_selective_scan.py``), nothing else; no array holds a
    state a token (``[B, S, 5120, 16]`` in either order) before XLA or
    after, nor a chunk of states (the backward remakes one in VMEM);
    u, Δ, y and their cotangents reach the kernels as bitcasts of the
    ``[1, 8192, 5120]`` arrays, not as copies; and while the step is
    traced each kernel's body is traced once."""
    import json
    import pathlib
    import re

    from dlrover_tpu.observability import runtime_timer

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads((path / "jamba2-3b-l14.json").read_text())
    STEP_CASES["jamba-cell"] = dict(
        model=config["program"]["model"],
        overrides=config["program"]["overrides"],
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(1, 8192), keep_lowered=True,
    )
    # the kernels' traces are kept by shape for the process: forget the
    # ``sscan-*`` cases' above, and count the bodies traced from here on
    jax.clear_caches()
    traced = _count_traced_bodies(
        monkeypatch, pallas_selective_scan,
        {"sscan_fwd": "_fwd_kernel", "sscan_bwd": "_bwd_kernel"},
    )
    traced_conv = _count_traced_bodies(monkeypatch, pallas_conv, CONV_BODIES)
    try:
        _, text, counters = _compiled_step(topo, "jamba-cell")
    finally:
        del STEP_CASES["jamba-cell"]
    assert traced == {"sscan_fwd": 1, "sscan_bwd": 1}, traced
    assert traced_conv == {"conv_fwd": 1, "conv_bwd": 1}, traced_conv
    lowered = _STEP_LOWERED.pop("jamba-cell")
    assert counters["ssm1.layers"] == 13
    assert counters["ssm1.scan_chunk"] == 128
    assert counters["ssm1.scan_in_kernel"] == 1
    assert counters["pattern.scanned_parts"] == 26
    assert counters["attn.output_kept"] == 1  # a span of 4,096.5 keys
    stats = _STEP_MEMORY["jamba-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    # 19.27 GB (18.85 before PR 55, whose conv kernels leave LESS alive
    # at the peak, 14.38 GB for 14.57, in a temporary heap the compiler
    # packs 0.2 GB looser: PERF.md section 7); 19.04 at PR 53, with the
    # scan's XLA body and its chunk of states
    assert 18.0e9 < need < 19.4e9, need
    assert stats.argument_size_in_bytes == pytest.approx(
        6 * 1_598_556_096, rel=1e-3  # bf16 parameters and two moments
    )
    kernels = {
        line.split("=")[0].strip().removeprefix("ROOT ").lstrip("%")
        .split(".")[0]
        for line in text.splitlines() if "tpu_custom_call" in line
    }
    assert kernels == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "norm_fwd", "norm_bwd",
        "sscan_fwd", "sscan_bwd", "conv_fwd", "conv_bwd",
    }
    flash = [
        ln for ln in text.splitlines()
        if "tpu_custom_call" in ln and "%flash_" in ln
    ]
    # multi-query: 20 heads of 128 on one; the output kept, so one
    # forward call
    assert len(flash) == 3 and all(
        "bf16[20,8192,128]" in ln and "bf16[1,8192,128]" in ln for ln in flash
    )
    # the scan's kernels once a scanned run's body: the forward in the
    # forward and remade in the backward, the backward kernel beside it
    assert _kernel_calls(text, "sscan_fwd") == 4
    assert _kernel_calls(text, "sscan_bwd") == 2
    op_names = runtime_timer.op_names_from_hlo(text)
    scan_calls = [
        (name, op_name) for name, op_name in op_names.items()
        if name.startswith("sscan_")
    ]
    assert len(scan_calls) == 6 and all(
        "ssm1.scan" in re.split(r"[/()]", op_name)
        for _, op_name in scan_calls
    )
    # the conv's kernels (PR 55) once a scanned run's body as the scan's
    # are, under the mixer's ``ssm1.conv`` (and the function's own
    # ``ssm.conv``); no padded copy of the float32 u
    assert counters["ssm.conv_in_kernel"] == 1
    _conv_calls_sit_under(text, op_names, "ssm1.conv", forward=4, backward=2)
    _conv_calls_sit_under(text, op_names, "ssm.conv", forward=4, backward=2)
    assert "f32[1,8195,5120]" not in text
    parts = {
        part for name in op_names.values()
        for part in re.split(r"[/()]", name)
    }
    scopes = {"ssm1", "ssm1.conv", "ssm1.dbc", "ssm1.scan", "attn", "mlp",
              "head_loss", "optimizer"}
    assert scopes <= parts, scopes - parts
    # a state a token, in either order, with any leading axes
    whole = r"8192[x,](?:1[x,])?(?:5120[x,]16|16[x,]5120)\b"
    assert not re.search(whole, lowered) and not re.search(whole, text)
    assert not re.search(r"\b64[x,]128[x,]1[x,]16[x,]5120\b", lowered)
    # no chunk of states in memory any more; one state a chunk kept, as
    # the kernels tile it
    assert "128x1x16x5120xf32" not in lowered
    assert "64x1x16x5x8x128xf32" in lowered
    # the kernels' operands of [1, 8192, 5120] are handed over in the
    # order their tiles lie in already: no copy to or from the view
    view = r"f32\[1,1024,320,128\]"
    assert re.search(view, text)
    assert not re.search(
        rf"= {view}\S* (?:copy|transpose)\(|(?:copy|transpose)\(\S*{view}", text
    )


def test_sala_cell_compiles_inside_the_memory_its_count_allows(
    topo, monkeypatch
):
    """The benchmark's MiniCPM-SALA configuration as it is run (published
    layers 0-3, ``S-L-L-L-``, an eighth of the vocabulary, 1 x 16,384
    tokens) compiles for a described v5e — the compiler's own check,
    which passes, is what says the step fits; the count of memory made
    here reads high (D18), 17.99 GB where the chip reads 15.95 (my chip
    runs, PR 57), and is held to its own reading. Arguments are the bf16
    parameters and two moments, 6 bytes each of 1,184,654,336. The
    kernels are the ``_sel`` flash kernels (32 / 2 heads of 128, a
    selection a KV head), the scan's three at one head of 128 a group
    (each body traced once) and the fused norms, nothing else; the
    lightning layers run as one scan of three; the selection is made
    once, in the forward, under ``attn.block_select`` (none of it under
    the remade part), what is kept of it the units, int8 [1, 2, 16384,
    256] (8 MB), and not the key mask they are expanded to; the scorer
    is never whole (no float [.., 16384, 1023])."""
    import json
    import pathlib
    import re

    from dlrover_tpu.observability import runtime_timer

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads((path / "minicpm-sala-l4.json").read_text())
    STEP_CASES["sala-cell"] = dict(
        model=config["program"]["model"],
        overrides=config["program"]["overrides"],
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(1, 16384), keep_lowered=True,
    )
    jax.clear_caches()
    traced = _count_traced_bodies(
        monkeypatch, pallas_ssd,
        {"ssd_fwd": "_fwd_kernel", "ssd_bwd": "_bwd_kernel"},
    )
    try:
        _, text, counters = _compiled_step(topo, "sala-cell")
    finally:
        del STEP_CASES["sala-cell"]
    lowered = _STEP_LOWERED.pop("sala-cell")
    # the forward kernel's body serves ``ssd_fwd`` and ``ssd_states``
    assert traced == {"ssd_fwd": 2, "ssd_bwd": 1}, traced
    assert counters["attn.sparse_layers"] == 1
    assert counters["attn.select_block"] == 64
    assert counters["attn.select_groups"] == 2
    assert counters["lin.layers"] == 3
    assert counters["ssm.scan_in_kernel"] == 1
    assert counters["pattern.scanned_parts"] == 6
    assert counters["attn.output_kept"] == 1  # a span of 8,192.5 keys
    stats = _STEP_MEMORY["sala-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    assert 17.0e9 < need < 18.6e9, need
    assert stats.argument_size_in_bytes == pytest.approx(
        6 * 1_184_654_336, rel=1e-3  # bf16 parameters and two moments
    )
    kernels = {
        line.split("=")[0].strip().removeprefix("ROOT ").lstrip("%")
        .split(".")[0]
        for line in text.splitlines() if "tpu_custom_call" in line
    }
    assert kernels == {
        "flash_fwd_sel", "flash_bwd_dq_sel", "flash_bwd_dkv_sel",
        "ssd_fwd", "ssd_states", "ssd_bwd", "norm_fwd", "norm_bwd",
    }
    flash = [
        ln for ln in text.splitlines()
        if "tpu_custom_call" in ln and "%flash_" in ln
    ]
    # the output kept, so one forward call; the selection's rows are the
    # KV heads', batch-major
    assert len(flash) == 3 and all(
        "bf16[32,16384,128]" in ln and "bf16[2,16384,128]" in ln
        and "s8[2,16384,16384]" in ln for ln in flash
    )
    # the scan's kernels once in the scanned run's body: the forward in
    # the forward and remade in the backward, the other two beside it
    assert _kernel_calls(text, "ssd_fwd") == 2
    assert _kernel_calls(text, "ssd_states") == 1
    assert _kernel_calls(text, "ssd_bwd") == 1
    op_names = runtime_timer.op_names_from_hlo(text)
    scan_calls = [
        op_name for name, op_name in op_names.items()
        if name.startswith("ssd_")
    ]
    assert len(scan_calls) == 4 and all(
        {"lin", "ssm.scan"} <= set(re.split(r"[/()]", op_name))
        for op_name in scan_calls
    )
    parts = {
        part for name in op_names.values()
        for part in re.split(r"[/()]", name)
    }
    scopes = {"embed", "attn", "attn.block_select", "attn.gate", "lin",
              "ssm.scan", "mlp", "head_loss", "optimizer"}
    assert scopes <= parts, scopes - parts
    select = [
        name for name in op_names.values() if "attn.block_select" in name
    ]
    assert select and not [
        name for name in select
        if "rematted_computation" in name or "transpose" in name
    ]
    # what is kept between forward and backward: the units, not the mask
    assert "1x2x16384x256xi8" in lowered
    # the scorer a chunk of queries at a time, never whole
    assert not re.search(r"f32\[[\d,]*16384,1023\]", text)
    assert re.search(r"f32\[1,2,16,512,1023\]", text)


def _equations(jaxpr):
    """Equations of a jaxpr with those of the jaxprs its equations hold."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _equations(sub)
    return n


# Equations of a kernel's body traced at Nemotron-3's widths (TURN 4;
# the state pass a group whole), with a tenth of room. PR 50's form
# reads 332 / 288 / 717, each traced once a process, and costs the
# chip's host +0.91 s of the step's trace and lowering over the parent's
# 3.90 s (my chip runs, PR 50: that host traces a thousand kernel
# equations in about a third of a second); PR 49's bodies were 651 / 393
# / 1,696, the forward's traced twice, behind a nested ``jit`` that cost
# 1.6 s by itself: +4.3 s in the driver's runs, and the PR refused
SCAN_BODY_BUDGET = {
    "ssd-bwd-128x64-8x128": {"ssd_fwd": 365, "ssd_states": 317, "ssd_bwd": 789},
    # the same three at MiniCPM-SALA's lightning widths (PR 57: one head
    # of 128 a group is one slab and one turn, 61 / 37 / 141; traced
    # once a process beside no other shape of theirs in that cell)
    "ssd-bwd-32x128-32x128": {"ssd_fwd": 67, "ssd_states": 41, "ssd_bwd": 155},
    # the selective scan's two at Jamba2-3B's widths (PR 54: 242 / 797,
    # each traced once a process; the per-state text, 16 states, is the
    # body — the token loops are rolled)
    "sscan-bwd-5120x16": {"sscan_fwd": 266, "sscan_bwd": 877},
    # the causal conv's two at both cells' widths (PR 55: 48 / 79 in
    # bf16, 46 / 76 in float32, each traced once a process: a tap is a
    # rotate, a select and a concatenate)
    "conv-bwd-10240-bf16": {"conv_fwd": 53, "conv_bwd": 87},
    "conv-bwd-5120-f32": {"conv_fwd": 51, "conv_bwd": 84},
    # the held rows' sum (PR 59: 115 weighted, the combine's, and 99
    # unweighted, the dispatch's derivative's; 8 rows of the loop
    # unrolled; each traced once a process)
    "rows-sum-8192x8-2048": {"rows_sum": 127},
    "rows-sum-back-8192x22-1024": {"rows_sum": 109},
    # the experts' interior over the held prefix and its derivative (PR
    # 72: 36 / 45 with a gate, 33 / 36 without; a turn of rows is one
    # rolled loop whatever the tile; each traced once a process)
    "experts-act-131072x1024": {"experts_act": 40, "experts_act_bwd": 50},
    "experts-act-relu2-65536x2688": {
        "experts_act": 37, "experts_act_bwd": 40,
    },
    # the L2 norm a head, forward and back (PR 69: 9 and 16 equations a
    # head of the block, 76 / 132 at the 8 heads a block holds whatever
    # the width — 292 / 516 with all of Kimi-Linear's 32 in it, which
    # cost its cell 2-5 s of warm ``setup_s`` for no speed; each traced
    # twice a process, once for q's scale and once for k's)
    "l2-heads-32x128": {"l2_heads_fwd": 83, "l2_heads_bwd": 145},
    "l2-heads-16x128": {"l2_heads_fwd": 83, "l2_heads_bwd": 145},
    # the gated delta rule's walk (212 / 158 / 492) and, since PR 71, the
    # triangular inverse before it (926 where it makes A of two value
    # heads itself, 893 of a given A: the fifteen steps of a diagonal
    # block and the 16 or 32 terms of a product's eight rows are
    # unrolled — rolled they were 412 equations and 2.3 times the
    # kernel's time —, the blocks, the pairs merged, the rows of eight
    # and the transposes are rolled loops; traced once a process)
    "gdn-bwd-16x2x128": {
        "tri_inverse": 1018, "gdn_fwd": 233, "gdn_states": 174,
        "gdn_bwd": 541,
    },
}


@pytest.mark.parametrize("case", sorted(SCAN_BODY_BUDGET))
def test_scan_kernels_stay_inside_their_build_budget(case):
    """A kernel's body is traced and lowered to Mosaic in every process
    before anything runs, warm or cold, and the seconds go by the
    equations (``ops/pallas_ssd.py``'s docstring): a body that grows
    past its budget fails here, not in the benchmark's ``setup_s``."""
    build, _ = CASES[case]
    fn, args = build(jax.ShapeDtypeStruct)
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = _equations(eqn.params["jaxpr"])
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    budget = SCAN_BODY_BUDGET[case]
    assert set(found) == set(budget)
    for name, most in budget.items():
        assert 0.5 * most < found[name] <= most, (name, found[name])


def test_keye_cell_compiles_at_its_depth(topo):
    """The benchmark's Keye-VL-2.0 configuration as it is run (12
    layers, 16 of 128 experts held, 1 x 8192 tokens; STEP_CASES'
    ``keye-cell`` IS the file's program): the step compiles for a
    described v5e; bf16 parameters and two moments are 7.44 GB of
    arguments and the compiler counted 17.63 GB in all before PR 38,
    where the chip itself read 14.10 GB (my chip run, PR 37: on GLM's
    cell the same count read 2.2 GB high, here 3.5). No float
    [.., 8192, 8192] array in the step, the twelve selections int8,
    none among its results.

    The alignment term is made once a step (PR 38): its derivative is
    a kept residual, bf16[12,1,8192,16,64] and two smaller. Since PR 40
    the term is the kernel ``align_kl``: nothing of the jnp rule is
    left under the term's scope — not the float32 [1, 8, 512, keys]
    score products of ``_chunk_target`` (a convolution f32[keys,512,8]
    and its exponential, one a chunk and kv group: 64 in the parent's
    text), not an index head's float32 products of a chunk's keys
    (f32[keys,512,16]: written once and read three times there), no
    convolution at all. The count of memory reads 17.40 GB against the
    parent's 16.22 (the chip itself 13.80 against 13.68: my chip runs,
    PR 40; the described-chip count has read 2.5-3.6 GB high on this
    cell since PR 37), under PR 37's 17.63.

    Since PR 42 ``full`` keeps the kernel's output at this span: the
    scanned body holds one ``flash_fwd_sel`` where it held two, the
    scan's residuals hold ``bf16[12,1,8192,32,128]`` and the statistics
    as numbers ``f32[12,1,32,8192]`` (12 x 68 MB) and no stacked tile
    array; the count reads **17.93 GB** and the chip **14.11** (13.80
    before: my chip runs, PR 42), so the limit is 18.2."""
    import json
    import pathlib
    import re

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads((path / "keye-vl-2.0-ep8-1chip.json").read_text())
    spec = STEP_CASES["keye-cell"]
    assert (spec["model"], spec["overrides"]) == (
        config["program"]["model"], config["program"]["overrides"]
    )
    _, text, counters = _compiled_step(topo, "keye-cell")
    stats = _STEP_MEMORY["keye-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    assert 15e9 < need < 18.2e9, need
    assert stats.argument_size_in_bytes == pytest.approx(
        6 * 1_240_585_984, rel=1e-3  # bf16 parameters and two moments
    )
    _no_whole_score_array(text)
    assert _kernel_calls(text, "flash_fwd_sel") == 1
    # a routed block's held rows (PR 59): the combine's sum in the
    # scanned forward body, the dispatch's derivative in the backward
    # one; the remade forward needs no combine
    assert _kernel_calls(text, "rows_sum") == 2
    # the experts' interior by the held prefix (PR 72): in the scanned
    # forward body, remade in the backward one, and its derivative there
    assert counters["moe.experts_by_prefix"] == 1
    assert _kernel_calls(text, "experts_act") == 2
    assert _kernel_calls(text, "experts_act_bwd") == 1
    assert "bf16[12,1,8192,32,128]" in text and "f32[12,1,32,8192]" in text
    assert "f32[12,32,8192,8]" not in text
    assert "s8[12,1,8192,8192]" in text  # the saved selections, stacked
    assert "bf16[12,1,8192,16,64]" in text  # and the derivative for qi
    under_term = [ln for ln in text.splitlines() if "attn.index_loss" in ln]
    assert sum("%align_kl" in ln and "custom-call(" in ln
               for ln in under_term) == 1
    assert not [ln for ln in under_term if " convolution(" in ln]
    assert not [ln for ln in under_term if " exponential(" in ln]
    assert not re.search(r"f32\[\d+,512,(?:8|16)\]", "\n".join(under_term))
    # an index head's products of a chunk's keys live only inside the
    # selection's own fusion (product, ReLU, then weighted and summed
    # over the heads before anything is written): no fusion's result,
    # no fusion's parameter
    products = [
        ln for ln in text.splitlines()
        if re.search(r"f32\[\d{3,},512,16\]", ln)
    ]
    assert len(products) == 3 * 12  # the chunks past index_topk
    assert all(
        "/attn.index/" in ln and not ln.lstrip().startswith("ROOT")
        and re.search(r" (?:convolution|broadcast|maximum)\(", ln)
        for ln in products
    ), [ln[:200] for ln in products[:3]]


def test_trinity_cell_keeps_both_kinds_output(topo):
    """The benchmark's Trinity-Mini configuration as it is run (1 dense
    + 4 routed layers, ``layer_types`` SSSSF, 16 of 128 experts held,
    1 x 16,384 tokens): the step compiles for a described v5e and fits
    (at 1 + 8 it does not: 16.45 GiB of 15.75, PR 47); one step holds BOTH flash variants — the window layers' and
    the full layers' calls are different programs of the same three
    kernels — and ``remat: full`` decides kind by kind, by the keys the
    forward kernel EXECUTES (PR 61): a window layer attends to 1,920
    keys a query but its forward walks a band of three tiles of 1,024,
    2,880 keys, over ``KEEP_ATTN_SPAN`` as a full layer's 8,704 are, so
    both kinds' output and row statistics are kept and no forward
    kernel runs again in the recomputed forward (before PR 61 the
    window layers' did: the rule read the 1,920). The routed stack is
    one period of four: three window layers and one full one."""
    import json
    import pathlib

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads((path / "trinity-mini-ep8-1chip.json").read_text())
    STEP_CASES["trinity-cell"] = dict(
        model=config["program"]["model"],
        overrides=config["program"]["overrides"],
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(1, 16384),
    )
    try:
        builder, text, counters = _compiled_step(topo, "trinity-cell")
    finally:
        del STEP_CASES["trinity-cell"]
    cfg = builder.cfg
    assert cfg.executed_span(16384, "S") == 1920.0625
    assert cfg.executed_span(16384, "F") == 8192.5
    stats = _STEP_MEMORY["trinity-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    # 10.45 GB, PR 47; + 0.54 for the four window layers' kept output
    assert 9e9 < need < 12e9, need
    assert counters["attn.window_layers"] == 4
    assert counters["attn.full_layers"] == 1
    assert counters["attn.output_kept"] == 5
    # the window layers' kernels walk the band (PR 48): the forward
    # three key blocks of 1024 a query block, on a grid of three where
    # it was sixteen; the backward five of 512
    assert counters["attn.band_blocks"] == 3
    assert counters["attn.window_tile"] == 512
    # by scope: every layer's forward kernel once — its output is kept,
    # the recomputed forward holds none; the dense prefix's window layer
    # outside the scan, the period's three inside it
    lines = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]

    def calls(kernel, scope):
        return sum(
            bool(re.match(rf"\s*(?:ROOT )?%{kernel}[.\d]* = ", ln))
            and f"/{scope}/" in ln
            for ln in lines
        )

    import re

    assert calls("flash_fwd", "attn.window") == 1 + 3
    assert calls("flash_bwd_dq", "attn.window") == 1 + 3
    assert calls("flash_bwd_dkv", "attn.window") == 1 + 3
    assert calls("flash_fwd", "attn.full") == 1
    assert calls("flash_bwd_dq", "attn.full") == 1
    assert calls("flash_bwd_dkv", "attn.full") == 1
    assert "/attn.gate/" in text


def test_mellum_cell_builds_a_table_a_rope_kind(topo):
    """The benchmark's Mellum2 configuration as it is run (one period
    SSSY, 16 of 64 experts held, 1 x 32,768 tokens): the step compiles
    for a described v5e and fits the chip's 15.75 GiB; one step holds
    BOTH flash variants — the window layers' banded calls at a window of
    1,024 and the full layer's over the whole causal span —; the forward
    builds two rope tables, the plain one and YaRN's, outside the layer
    scan, and every turning of q and k sits under the scope
    ``attn.rope`` inside its kind's. ``remat: full`` keeps the full
    layer's flash output (16,896 keys a query executed) and remakes the
    window layers' (a band of two tiles of 1,024, 2,016 keys, under
    ``KEEP_ATTN_SPAN``)."""
    import json
    import pathlib
    import re

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads(
        (path / "mellum2-12b-a2.5b-ep4-1chip.json").read_text()
    )
    STEP_CASES["mellum-cell"] = dict(
        model=config["program"]["model"],
        overrides=config["program"]["overrides"],
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(1, 32768),
    )
    try:
        builder, text, counters = _compiled_step(topo, "mellum-cell")
    finally:
        del STEP_CASES["mellum-cell"]
    cfg = builder.cfg
    assert cfg.rope_kinds == ("plain", "scaled")
    stats = _STEP_MEMORY["mellum-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    # 13.78 GB = 12.83 GiB (PR 70)
    assert 12e9 < need < 15.75 * 2 ** 30, need
    assert counters["attn.rope_tables"] == 2
    assert counters["attn.scaled_rope_layers"] == 1
    assert counters["attn.window_layers"] == 3
    assert counters["attn.full_layers"] == 1
    assert counters["attn.output_kept"] == 1
    lines = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]

    def calls(kernel, scope):
        return sum(
            bool(re.match(rf"\s*(?:ROOT )?%{kernel}[.\d]* = ", ln))
            and f"/{scope}/" in ln
            for ln in lines
        )

    # the window layers' forward runs again in the recomputed forward
    assert calls("flash_fwd", "attn.window") == 2 * 3
    assert calls("flash_bwd_dq", "attn.window") == 3
    assert calls("flash_bwd_dkv", "attn.window") == 3
    assert calls("flash_fwd", "attn.full") == 1
    assert calls("flash_bwd_dq", "attn.full") == 1
    assert calls("flash_bwd_dkv", "attn.full") == 1
    assert "/attn.window/attn.rope/" in text
    assert "/attn.full/attn.rope/" in text


def test_lfm2_cell_runs_the_gated_conv_in_kernels(topo):
    """The benchmark's LFM2 configuration as it is run (the first six
    published layers ``C-C-*eCeCeCe``, 8 of 32 experts held, 8 x 4,096
    tokens): the step compiles for a described v5e and fits the chip's
    15.75 GiB; the five conv mixers run the gated conv's kernels — a
    forward and a recomputed forward a layer (the scanned ``C-`` pair
    holds one body), one backward — under ``conv.gate`` inside ``conv``,
    beside ``conv.in_proj`` and ``conv.out_proj``, with no window of the
    in-projection copied out and no float32 copy of it; the one
    attention layer runs the flash kernels at 32 / 8 heads of 64."""
    import json
    import pathlib
    import re

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads((path / "lfm2-8b-a1b-ep4-1chip.json").read_text())
    STEP_CASES["lfm2-cell"] = dict(
        model=config["program"]["model"],
        overrides=config["program"]["overrides"],
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(8, 4096),
    )
    try:
        builder, text, counters = _compiled_step(topo, "lfm2-cell")
    finally:
        del STEP_CASES["lfm2-cell"]
    assert builder.cfg.num_params() == 568_647_808
    stats = _STEP_MEMORY["lfm2-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    # 9.84 GB = 9.16 GiB (PR 73)
    assert 8e9 < need < 15.75 * 2 ** 30, need
    assert counters["conv.layers"] == 5
    assert counters["conv.kernel_layers"] == 5
    assert counters["pattern.scanned_parts"] == 4
    assert counters["moe.experts_by_prefix"] == 1
    lines = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]

    def calls(kernel, scope):
        return sum(
            bool(re.match(rf"\s*(?:ROOT )?%{kernel}[.\d]* = ", ln))
            and f"/{scope}" in ln
            for ln in lines
        )

    # the scanned pair's body once, the three unrolled layers each
    assert calls("gated_conv_fwd", "conv.gate") == 2 * (1 + 3)
    assert calls("gated_conv_bwd", "conv.gate") == 1 + 3
    for scope in ("conv.in_proj", "conv.gate", "conv.out_proj"):
        assert f"/conv/{scope}" in text, scope
    assert "f32[8,4096,6144]" not in text
    flash = {
        m.group(1) for ln in lines
        if (m := re.match(r"\s*(?:ROOT )?%(flash_\w+?)[.\d]* = ", ln))
    }
    assert len(flash) == 3, flash
