"""The selective scan's Pallas kernels (``ops/pallas_selective_scan.py``)
in interpret mode on the CPU: against the XLA body of the same
``selective_scan`` — the output and the gradient of every operand —,
against the token-by-token recurrence, that ``starts`` is the only
residual beside the operands, and that ``selective_scan`` takes the
kernels only where it says it does.

What the chip's compiler makes of them is ``tests/test_tpu_compile.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_jamba_reference import _sequential
from test_ssd_kernel import _mesh

from dlrover_tpu.models import decoder, get_config
from dlrover_tpu.observability import tracing
from dlrover_tpu.ops import pallas_attention
from dlrover_tpu.ops import pallas_selective_scan as kernels
from dlrover_tpu.ops import selective_scan as sscan

F32 = jnp.float32
# seq, channels, states, batch, chunk, dtype of u, B and C. A block is
# 1,024 channels; short lengths keep the interpreter's loops short
SHAPES = {
    "one-chunk": (64, 1024, 8, 1, 64, "float32"),
    "three-chunks": (192, 1024, 8, 1, 64, "float32"),
    "chunks-of-128": (256, 1024, 8, 1, 128, "float32"),
    "two-blocks": (128, 2048, 8, 1, 64, "float32"),
    "two-rows": (128, 1024, 8, 2, 64, "float32"),
    "sixteen-states": (128, 1024, 16, 1, 64, "float32"),
    "padded": (150, 1024, 8, 2, 64, "float32"),
    "padded-two-blocks-of-128": (200, 2048, 8, 1, 128, "float32"),
    "chunks-of-16": (80, 1024, 8, 1, 16, "float32"),
    "bfloat16": (128, 1024, 8, 1, 64, "bfloat16"),
}
# max |kernel - XLA body| over max |XLA body|, an output or a gradient
# (float32 reads 1e-6 and under: the same operations in another order; a
# wrong or missing term reads 1e-2 and up)
TOLERANCE = {"float32": 2e-5, "bfloat16": 2e-2}
NAMES = ("y", "du", "dΔ", "dA", "dB", "dC")


def _operands(seq, channels, states, batch, dtype="float32", key=3):
    k = jax.random.split(jax.random.key(key), 6)
    return (
        jax.random.normal(k[0], (batch, seq, channels), dtype),
        jax.nn.softplus(jax.random.normal(k[1], (batch, seq, channels)) - 2.0),
        -jnp.exp(jax.random.normal(k[2], (channels, states))),
        jax.random.normal(k[3], (batch, seq, states), dtype),
        jax.random.normal(k[4], (batch, seq, states), dtype),
    ), jax.random.normal(k[5], (batch, seq, channels))


def _value_and_grads(scan, args, weight, chunk):
    def loss(*a):
        y = scan(*a, chunk=chunk)
        return (y.astype(F32) * weight).sum(), y

    (_, y), grads = jax.value_and_grad(loss, range(5), has_aux=True)(*args)
    return (y, *grads)


def _close(got, want, tolerance):
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        err = np.abs(a - b).max() / np.abs(b).max()
        assert err <= tolerance, (name, err)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernels_are_the_xla_body(monkeypatch, shape):
    """y and the gradients of u, Δ, A, B and C: one chunk and several
    (the carried state forward, its cotangent and dA backward), one
    channel block and two (dB and dC summed over the blocks outside), a
    length the chunk does not divide (padded with Δ = 0), chunks of 16,
    64 and 128, one row and two, bf16 operands with float32 inside."""
    seq, channels, states, batch, chunk, dtype = SHAPES[shape]
    args, weight = _operands(seq, channels, states, batch, dtype)
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    tracing._counters.clear()
    got = _value_and_grads(sscan.selective_scan, args, weight, chunk)
    assert tracing.counters()["ssm1.scan_in_kernel"] == 1
    assert tracing.counters()["ssm1.scan_chunk"] == chunk
    monkeypatch.setattr(pallas_attention, "INTERPRET", False)
    want = _value_and_grads(sscan.selective_scan, args, weight, chunk)
    assert tracing.counters()["ssm1.scan_in_kernel"] == 0
    _close(got, want, TOLERANCE[dtype])


@pytest.mark.parametrize("seq,chunk", [(96, 32), (70, 32)])
def test_kernels_are_the_token_by_token_recurrence(monkeypatch, seq, chunk):
    """Another algorithm, differentiated by autodiff: three whole chunks,
    and three with the last padded."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    args, weight = _operands(seq, 1024, 8, 2, key=5)
    got = _value_and_grads(sscan.selective_scan, args, weight, chunk)
    assert tracing.counters()["ssm1.scan_in_kernel"] == 1
    want = _value_and_grads(
        lambda *a, chunk: _sequential(*a), args, weight, chunk
    )
    _close(got, want, 2e-5)


def test_kernels_keep_chunk_starts_only(monkeypatch):
    """The residuals of the kernels' rule are the XLA body's: the
    operands and one state a chunk ``[S / chunk, B, N, C]``, the same
    numbers; no state a token, and no chunk of states, in the program
    around the kernels."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    args, _ = _operands(128, 1024, 8, 2)
    _, residuals = kernels._sscan_fwd(*args, 32)
    *kept, starts = residuals
    assert all(k is a for k, a in zip(kept, args))
    # [chunks, B, N, C] with C as the kernels tile it: blocks of 8 x 128
    assert starts.shape == (4, 2, 8, 1, 8, 128) and starts.dtype == F32
    want = sscan._scan_fwd(*args, 32)[1][-1]
    np.testing.assert_allclose(
        np.asarray(starts).reshape(want.shape), np.asarray(want),
        rtol=1e-5, atol=1e-6,
    )
    text = jax.jit(jax.grad(
        lambda *x: sscan.selective_scan(*x, chunk=32).sum(), range(5)
    )).lower(*args).as_text()
    assert "128x2x8x1024" not in text and "2x128x1024x8" not in text
    assert "32x2x8x1024" not in text  # the XLA body's chunk of states


# channels, states, chunk, devices, interpreted
XLA_BODY = {
    "tier-1-widths": (128, 4, 16, 1, True),
    "channels-off-the-blocks": (1536, 8, 64, 1, True),
    "four-states": (1024, 4, 64, 1, True),
    "a-chunk-of-36": (1024, 8, 36, 1, True),
    "a-mesh-of-eight": (1024, 8, 64, 8, True),
    "off-the-chip": (1024, 8, 64, 1, False),
}


@pytest.mark.parametrize("case", sorted(XLA_BODY))
def test_shapes_the_kernels_do_not_tile_take_the_xla_body(monkeypatch, case):
    """Untileable widths, a multi-device mesh, and the CPU without
    interpret mode: ``selective_scan`` lowers no ``pallas_call`` and says
    0; the same call at tileable widths on one device lowers two (the
    forward, which the rule shares with the primal, and the backward)
    and says 1."""
    channels, states, chunk, devices, interpreted = XLA_BODY[case]
    monkeypatch.setattr(pallas_attention, "INTERPRET", interpreted)
    mesh = _mesh(devices)
    assert not kernels.tile(72, channels, states, chunk, mesh)

    def calls(mesh, chunk, *a):
        tracing._counters.clear()
        text = str(jax.make_jaxpr(jax.grad(
            lambda *a: sscan.selective_scan(*a, chunk=chunk, mesh=mesh).sum(),
            range(5),
        ))(*a))
        return text.count("pallas_call"), tracing.counters()[
            "ssm1.scan_in_kernel"
        ]

    args, _ = _operands(72, channels, states, 1)
    assert calls(mesh, chunk, *args) == (0, 0)
    if interpreted:
        fit, _ = _operands(72, 1024, 8, 1)
        assert calls(_mesh(1), 24, *fit) == (2, 1)


def test_off_the_chip_the_scan_runs_through_the_modules_own_jnp(monkeypatch):
    """The benchmark plants its defects from outside by patching the
    module's ``jnp`` (its ``exp``): off the TPU and not interpreted the
    XLA body runs through it whatever the shape, block-sized channels
    too."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", False)
    seen = []

    class Watching:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def exp(x):
            seen.append(x.shape)
            return jnp.exp(x)

    monkeypatch.setattr(sscan, "jnp", Watching())
    args, _ = _operands(64, 1024, 8, 1)
    sscan.selective_scan(*args, chunk=64)
    assert seen and all(shape == (1, 8, 1024) for shape in seen)


def test_a_kernel_is_traced_once_a_process(monkeypatch):
    """What a kernel costs before it runs is its body's trace and its
    lowering (``pallas_ssd``'s docstring): two checkpointed layers
    differentiated trace the forward kernel once (the primal, the
    forward rule and the remade forward share it) and the backward
    kernel once — not once a layer, not once a rule —, and a second
    program of the same shapes traces nothing."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    traced = {"fwd": 0, "bwd": 0}

    def counting(name, kernel):
        def body(*refs):
            traced[name] += 1
            return kernel(*refs)

        return body

    monkeypatch.setattr(
        kernels, "_fwd_kernel", counting("fwd", kernels._fwd_kernel)
    )
    monkeypatch.setattr(
        kernels, "_bwd_kernel", counting("bwd", kernels._bwd_kernel)
    )
    # shapes no other test takes: the trace is kept by shape
    args, _ = _operands(88, 1024, 8, 1)

    @jax.checkpoint
    def layer(u, *rest):
        return sscan.selective_scan(u, *rest, chunk=8)

    def loss(u, *rest):
        return layer(layer(u, *rest), *rest).sum()

    jax.jit(jax.grad(loss, range(5))).trace(*args)
    assert traced == {"fwd": 1, "bwd": 1}
    jax.jit(lambda *a: layer(*a).sum()).trace(*args)
    assert traced == {"fwd": 1, "bwd": 1}


MIXER = dict(
    n_layer=2, layer_pattern="m-m-", d_model=512, n_head=4, n_kv_head=1,
    d_head=16, d_ff=128, vocab_size=256, max_seq=64, mamba_dt_rank=8,
    ssm_state_size=8, remat="full", dtype="float32",
)


@pytest.mark.parametrize(
    "interpreted,engaged", [(True, 1), (False, 0)],
    ids=["tileable", "off-the-chip"],
)
def test_model_says_which_body_the_scan_took(
    monkeypatch, interpreted, engaged
):
    """``ssm1.scan_in_kernel``, set by ``selective_scan`` while the model
    is traced (1,024 channels: ``mamba_expand`` 2 of a width of 512)."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", interpreted)
    cfg = get_config("jamba2-3b", **MIXER)
    assert cfg.d_inner1 == 1024
    params = jax.eval_shape(lambda k: decoder.init(k, cfg), jax.random.key(0))
    tracing._counters.clear()
    jax.eval_shape(
        lambda p, t: decoder.forward(p, t, cfg), params,
        jax.ShapeDtypeStruct((1, 64), jnp.int32),
    )
    counters = tracing.counters()
    assert counters["ssm1.scan_in_kernel"] == engaged
    assert counters["ssm1.scan_chunk"] == 64


def test_a_model_without_a_mamba1_layer_sets_no_counter():
    cfg = get_config("jamba2-3b", **{**MIXER, "layer_pattern": "*-*-"})
    params = jax.eval_shape(lambda k: decoder.init(k, cfg), jax.random.key(0))
    tracing._counters.clear()
    jax.eval_shape(
        lambda p, t: decoder.forward(p, t, cfg), params,
        jax.ShapeDtypeStruct((1, 64), jnp.int32),
    )
    assert "ssm1.scan_in_kernel" not in tracing.counters()
