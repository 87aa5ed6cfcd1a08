"""The causal conv's Pallas kernels (``ops/pallas_conv.py``) in interpret
mode on the CPU: against the XLA body of the same ``ssd.causal_conv`` —
the output and the gradient of x, the taps and the bias —, against the
definition token by token, that nothing but the operands is a residual,
and that ``causal_conv`` takes the kernels only where it says it does.

What the chip's compiler makes of them is ``tests/test_tpu_compile.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_ssd_kernel import _mesh

from dlrover_tpu.models import decoder, get_config
from dlrover_tpu.observability import tracing
from dlrover_tpu.ops import pallas_attention, ssd
from dlrover_tpu.ops import pallas_conv as kernels

F32 = jnp.float32
T = kernels.TOKENS
# token blocks, channels, batch, taps, dtype of x, dtype of the taps and
# the bias. A channel block is the widest of 1,024 / 512 / 256 / 128 that
# divides the channels
SHAPES = {
    "one-block": (1, 128, 1, 4, "float32", "float32"),
    "three-blocks": (3, 256, 1, 4, "float32", "float32"),
    # x float32 between the mixer's matmuls, its parameters bf16
    "as-jamba": (2, 256, 1, 4, "float32", "bfloat16"),
    # bf16 in, bf16 out
    "as-nemotron": (3, 256, 2, 4, "bfloat16", "bfloat16"),
    "two-rows": (2, 128, 2, 4, "float32", "float32"),
    "three-channel-blocks": (2, 1536, 1, 4, "float32", "float32"),
    "nine-channel-blocks-of-128": (2, 1152, 1, 4, "float32", "float32"),
    "two-taps": (2, 128, 1, 2, "float32", "float32"),
    "one-tap": (2, 128, 1, 1, "float32", "float32"),
    "nine-taps": (2, 128, 2, 9, "float32", "float32"),
}
# max |kernel - XLA body| over max |XLA body|, an output or a gradient
# (float32 reads 3e-7 and under: the same sums; bf16 0 or one rounding of
# the output; a missing tap or a halo off by a token reads 1e-1 and up)
TOLERANCE = {"float32": 2e-6, "bfloat16": 1e-2}
NAMES = ("y", "dx", "dw", "db")


def _operands(blocks, channels, batch, taps, dtype, w_dtype, key=5):
    k = jax.random.split(jax.random.key(key), 4)
    seq = blocks * T
    return (
        jax.random.normal(k[0], (batch, seq, channels), dtype),
        jax.random.normal(k[1], (taps, channels), w_dtype),
        jax.random.normal(k[2], (channels,), w_dtype),
    ), jax.random.normal(k[3], (batch, seq, channels))


def _value_and_grads(conv, args, weight):
    def loss(*a):
        y = conv(*a)
        return (y.astype(F32) * weight).sum(), y

    (_, y), grads = jax.value_and_grad(
        loss, range(len(args)), has_aux=True
    )(*args)
    return (y, *grads)


def _close(got, want, tolerance, names=NAMES):
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        err = np.abs(a - b).max() / np.abs(b).max()
        assert err <= tolerance, (name, err)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernels_are_the_xla_body(monkeypatch, shape):
    """y and the gradients of x, the taps and the bias: one token block
    and several (the rows carried across a block's edge, the zeros before
    token 0 and, going back, after the last), one channel block and
    several, one row and two (the taps' sums over the rows outside), 1
    to 9 taps, bf16 and float32 in any mix with float32 inside."""
    *sizes, dtype, w_dtype = SHAPES[shape]
    args, weight = _operands(*sizes, dtype, w_dtype)
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    tracing._counters.clear()
    got = _value_and_grads(ssd.causal_conv, args, weight)
    assert tracing.counters()["ssm.conv_in_kernel"] == 1
    monkeypatch.setattr(pallas_attention, "INTERPRET", False)
    want = _value_and_grads(ssd.causal_conv, args, weight)
    assert tracing.counters()["ssm.conv_in_kernel"] == 0
    _close(want, _value_and_grads(ssd._conv, args, weight), 0.0)
    _close(got, want, max(TOLERANCE[dtype], TOLERANCE[w_dtype]))


@pytest.mark.parametrize("taps", [4, 3])
def test_kernels_are_the_definition_token_by_token(monkeypatch, taps):
    """Another algorithm, in float64 by hand: ``y_t = b + Σ_j w_j ⊙
    x_{t-K+1+j}`` a token, ``dx_t = Σ_j w_j ⊙ dy_{t+K-1-j}``, ``dw_j =
    Σ_t dy_t ⊙ x_{t-K+1+j}``, ``db = Σ_t dy_t``, nothing before the
    first token and nothing after the last."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    args, weight = _operands(3, 128, 2, taps, "float32", "float32", key=7)
    got = _value_and_grads(ssd.causal_conv, args, weight)
    assert tracing.counters()["ssm.conv_in_kernel"] == 1
    x, w, b = (np.asarray(a, np.float64) for a in args)
    dy = np.asarray(weight, np.float64)
    seq = x.shape[1]
    y, dx, dw = np.zeros_like(x), np.zeros_like(x), np.zeros_like(w)
    for t in range(seq):
        y[:, t] = b
        for j in range(taps):
            if 0 <= (src := t - taps + 1 + j):
                y[:, t] += w[j] * x[:, src]
                dx[:, src] += w[j] * dy[:, t]
                dw[j] += (dy[:, t] * x[:, src]).sum(0)
    want = (y, dx, dw, dy.sum((0, 1)))
    for name, a, c in zip(NAMES, got, want):
        np.testing.assert_allclose(
            np.asarray(a, np.float64), c, rtol=0, atol=2e-6 * np.abs(c).max(),
            err_msg=name,
        )


def test_kernels_keep_the_operands_only(monkeypatch):
    """The residuals of the kernels' rule are the caller's own three
    arrays: no padded copy, no float32 copy of x, in the rule or in the
    program around the kernels."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    args, _ = _operands(2, 128, 1, 4, "bfloat16", "bfloat16")
    y, residuals = kernels._conv_fwd(*args, 128, 0)
    assert y.dtype == jnp.bfloat16
    assert all(kept is a for kept, a in zip(residuals, args))
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: ssd.causal_conv(*a).astype(F32).sum(), range(3)
    ))(*args))
    seq = 2 * T
    assert f"f32[1,{seq + 3},128]" not in text
    assert f"f32[1,{seq},128]" not in text.split("pallas_call")[0]


# tokens, channels, taps, devices, interpreted
XLA_BODY = {
    "tier-1-widths": (64, 48, 4, 1, True),
    "channels-off-the-lanes": (2 * T, 192, 4, 1, True),
    "a-length-off-the-block": (T + 8, 128, 4, 1, True),
    "ten-taps": (2 * T, 128, 10, 1, True),
    "a-mesh-of-two": (2 * T, 128, 4, 2, True),
    "off-the-chip": (2 * T, 128, 4, 1, False),
}


@pytest.mark.parametrize("case", sorted(XLA_BODY))
def test_shapes_the_kernels_do_not_tile_take_the_xla_body(monkeypatch, case):
    """Untileable shapes, a mesh of several devices, and the CPU without
    interpret mode: ``causal_conv`` lowers no ``pallas_call``, says 0 and
    is the XLA body to the bit; the same call at tileable shapes on one
    device lowers two (the forward, which the rule shares with the
    primal, and the backward) and says 1."""
    seq, channels, taps, devices, interpreted = XLA_BODY[case]
    monkeypatch.setattr(pallas_attention, "INTERPRET", interpreted)
    mesh = _mesh(devices)
    assert kernels.tile(seq, channels, taps, mesh=mesh) is None

    def calls(mesh, *a):
        tracing._counters.clear()
        text = str(jax.make_jaxpr(jax.grad(
            lambda *a: ssd.causal_conv(*a, mesh=mesh).sum(), range(3)
        ))(*a))
        return text.count("pallas_call"), tracing.counters()[
            "ssm.conv_in_kernel"
        ]

    k = jax.random.split(jax.random.key(1), 3)
    args = (
        jax.random.normal(k[0], (1, seq, channels)),
        jax.random.normal(k[1], (taps, channels)),
        jax.random.normal(k[2], (channels,)),
    )
    assert calls(mesh, *args) == (0, 0)
    np.testing.assert_array_equal(
        ssd.causal_conv(*args, mesh=mesh), ssd._conv(*args)
    )
    if interpreted:
        fit, _ = _operands(2, 128, 1, 4, "float32", "float32")
        assert kernels.tile(2 * T, 128, 4, mesh=_mesh(1)) == 128
        assert calls(_mesh(1), *fit) == (2, 1)


# channels of the array handed over, the conv's first column and width
WINDOWS = {
    "the-first-columns": (256, 0, 128),  # Jamba's u of [u | z]
    "the-middle-columns": (640, 256, 256),  # Nemotron's xBC of [z | xBC | dt]
    "the-last-block-of-128": (384, 256, 128),
    "the-whole-width": (128, 0, 128),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", sorted(WINDOWS))
def test_columns_of_a_wider_array_are_the_slice(monkeypatch, window, dtype):
    """``Columns(of, start)`` through the kernels, which read the wider
    array where it lies, against the XLA body on the slice: y, and the
    gradients of the WIDE array (zero outside the columns), the taps and
    the bias; two token blocks, two rows."""
    wider, start, channels = WINDOWS[window]
    (_, weight, bias), ct = _operands(2, channels, 2, 4, dtype, dtype)
    wide = jax.random.normal(jax.random.key(9), (2, 2 * T, wider), dtype)

    def windowed(wide, weight, bias):
        return ssd.causal_conv(ssd.Columns(wide, start), weight, bias)

    def sliced(wide, weight, bias):
        return ssd._conv(wide[..., start:start + channels], weight, bias)

    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    tracing._counters.clear()
    got = _value_and_grads(windowed, (wide, weight, bias), ct)
    assert tracing.counters()["ssm.conv_in_kernel"] == 1
    want = _value_and_grads(sliced, (wide, weight, bias), ct)
    _close(got, want, TOLERANCE[dtype])
    outside = np.ones(wider, bool)
    outside[start:start + channels] = False
    assert not np.asarray(got[1], np.float32)[..., outside].any()
    monkeypatch.setattr(pallas_attention, "INTERPRET", False)
    _close(_value_and_grads(windowed, (wide, weight, bias), ct), want, 0.0)
    assert tracing.counters()["ssm.conv_in_kernel"] == 0


def test_columns_off_the_lane_grid_take_the_xla_body(monkeypatch):
    """A first column that is no multiple of 128 (tier-1's widths): no
    ``pallas_call``, and the slice's own numbers."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    assert kernels.tile(T, 128, 4, start=64) is None
    assert kernels.tile(T, 1024, 4, start=256) == 256
    (_, weight, bias), _ = _operands(1, 128, 1, 4, "float32", "float32")
    wide = jax.random.normal(jax.random.key(2), (1, T, 256))
    tracing._counters.clear()
    text = str(jax.make_jaxpr(
        lambda a: ssd.causal_conv(ssd.Columns(a, 64), weight, bias)
    )(wide))
    assert "pallas_call" not in text
    assert tracing.counters()["ssm.conv_in_kernel"] == 0
    np.testing.assert_array_equal(
        ssd.causal_conv(ssd.Columns(wide, 64), weight, bias),
        ssd._conv(wide[..., 64:192], weight, bias),
    )


def test_a_kernel_is_traced_once_a_process(monkeypatch):
    """What a kernel costs before it runs is its body's trace and its
    lowering (``pallas_ssd``'s docstring): two checkpointed layers
    differentiated trace the forward kernel once (the primal, the
    forward rule and the remade forward share it) and the backward
    kernel once, and a second program of the same shapes traces
    nothing."""
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
    args, _ = _operands(1, 384, 3, 4, "float32", "float32")

    @jax.checkpoint
    def layer(x, *rest):
        return ssd.causal_conv(x, *rest)

    def loss(x, *rest):
        return layer(layer(x, *rest), *rest).sum()

    jax.jit(jax.grad(loss, range(3))).trace(*args)
    assert traced == {"fwd": 1, "bwd": 1}
    jax.jit(lambda *a: layer(*a).sum()).trace(*args)
    assert traced == {"fwd": 1, "bwd": 1}


MIXERS = {
    # conv_dim 256 + 2 x 2 x 128 = 768
    "nemotron-3-super": dict(
        n_layer=2, layer_pattern="ME", d_model=64, n_head=4, n_kv_head=2,
        d_head=16, vocab_size=256, mamba_num_heads=4, mamba_head_dim=64,
        ssm_state_size=128, n_groups=2, ssm_chunk=128, ssm_head_block=2,
        n_experts=16, expert_top_k=6, d_expert=48, moe_latent_size=32,
        d_shared_expert=96, n_experts_held=4, expert_offset=0, remat="full",
        dtype="float32",
    ),
    # d_inner1 1,024
    "jamba2-3b": dict(
        n_layer=2, layer_pattern="m-m-", d_model=512, n_head=4, n_kv_head=1,
        d_head=16, d_ff=128, vocab_size=256, mamba_dt_rank=8,
        ssm_state_size=8, remat="full", dtype="float32",
    ),
}


def _forward_counters(model, seq, **changes):
    cfg = get_config(model, **{**MIXERS[model], "max_seq": seq, **changes})
    params = jax.eval_shape(lambda k: decoder.init(k, cfg), jax.random.key(0))
    tracing._counters.clear()
    jax.eval_shape(
        lambda p, t: decoder.forward(p, t, cfg), params,
        jax.ShapeDtypeStruct((1, seq), jnp.int32),
    )
    return tracing.counters()


@pytest.mark.parametrize("model", sorted(MIXERS))
@pytest.mark.parametrize(
    "interpreted,blocks,engaged", [(True, 1, 1), (True, 0.5, 0), (False, 1, 0)],
    ids=["tileable", "half-a-block", "off-the-chip"],
)
def test_model_says_which_body_the_conv_took(
    monkeypatch, model, interpreted, blocks, engaged
):
    """``ssm.conv_in_kernel``, set by ``causal_conv`` while either
    mixer's model is traced."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", interpreted)
    counters = _forward_counters(model, int(blocks * T))
    assert counters["ssm.conv_in_kernel"] == engaged


@pytest.mark.parametrize("model", sorted(MIXERS))
def test_mixers_call_the_conv_by_its_three_parameters(monkeypatch, model):
    """The benchmark plants its defects from outside with stand-ins of
    ``causal_conv(x, weight, bias)`` that hand the three on to the
    function they replace: on one device both mixers call it with those
    three and nothing else."""
    seen = []
    conv = ssd.causal_conv

    def stand_in(x, weight, bias):
        seen.append((x.of.shape, x.start, weight.shape, bias.shape))
        return conv(x, weight, bias)

    monkeypatch.setattr(ssd, "causal_conv", stand_in)
    counters = _forward_counters(model, 64)
    # x as ``Columns`` of the in-projection: [z | xBC | dt] and [u | z]
    want = {
        "nemotron-3-super": ((1, 64, 256 + 768 + 4), 256, (4, 768), (768,)),
        "jamba2-3b": ((1, 64, 2048), 0, (4, 1024), (1024,)),
    }[model]
    assert seen and all(args == want for args in seen)
    assert counters["ssm.conv_in_kernel"] == 0


def test_a_model_without_a_mixer_sets_no_counter():
    counters = _forward_counters("jamba2-3b", 64, layer_pattern="*-*-")
    assert "ssm.conv_in_kernel" not in counters


# ---- the GATED conv (``ssd.gated_conv``; LFM2's ``C`` part) ----------------

# token blocks (of the forward's 512; the backward walks blocks of 256),
# channels a gate, batch, taps, dtype of [B | C | x], dtype of the taps. A
# batch of 2: a sequence's first tokens see zeros, not the row before
GATED_SHAPES = {
    "gated-one-block": (1, 128, 1, 3, "float32", "float32"),
    "gated-three-blocks-two-rows": (3, 256, 2, 3, "float32", "float32"),
    # bf16 in, bf16 out, as the LFM2 cell's mixers hand it over
    "gated-as-lfm2": (2, 256, 2, 3, "bfloat16", "bfloat16"),
    "gated-float32-proj-bf16-taps": (2, 128, 2, 3, "float32", "bfloat16"),
    # two chunks of 1,024 channels inside a step, then three of 128
    "gated-two-chunks": (1, 2048, 2, 3, "float32", "float32"),
    "gated-three-chunks-of-128": (1, 384, 1, 3, "float32", "float32"),
    "gated-two-taps": (2, 128, 2, 2, "float32", "float32"),
    "gated-nine-taps": (2, 128, 2, 9, "float32", "float32"),
}
GATED_NAMES = ("y", "dproj", "dw")


def _gated_operands(blocks, channels, batch, taps, dtype, w_dtype, key=11):
    k = jax.random.split(jax.random.key(key), 3)
    seq = blocks * kernels.GATED_TOKENS
    return (
        jax.random.normal(k[0], (batch, seq, 3 * channels), dtype),
        jax.random.normal(k[1], (taps, channels), w_dtype),
    ), jax.random.normal(k[2], (batch, seq, channels))


@pytest.mark.parametrize("shape", sorted(GATED_SHAPES))
def test_gated_kernels_are_the_xla_body(monkeypatch, shape):
    """y and all three cotangents — [dB | dC | dx] as ONE array, and the
    taps' — of ``y = C * conv(B * x)``: one token block and several (the
    rows of B * x carried across a block's edge going forward; going
    back dy * C's carried the other way and B's and x's 16 rows before
    the block read beside it), zeros before a sequence's first token in
    every row of the batch, one chunk of channels and several, 2 to 9
    taps, bf16 and float32 with float32 inside."""
    *sizes, dtype, w_dtype = GATED_SHAPES[shape]
    args, weight = _gated_operands(*sizes, dtype, w_dtype)
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    assert ssd.gated_conv_in_kernel(
        args[0].shape[1], *args[1].shape, dtype
    ) is not None
    text = str(jax.make_jaxpr(lambda *a: ssd.gated_conv(*a))(*args))
    assert text.count("pallas_call") == 1
    got = _value_and_grads(ssd.gated_conv, args, weight)
    monkeypatch.setattr(pallas_attention, "INTERPRET", False)
    text = str(jax.make_jaxpr(lambda *a: ssd.gated_conv(*a))(*args))
    assert "pallas_call" not in text
    want = _value_and_grads(ssd.gated_conv, args, weight)
    _close(
        want, _value_and_grads(ssd._gated_conv, args, weight), 0.0,
        GATED_NAMES,
    )
    _close(got, want, max(TOLERANCE[dtype], TOLERANCE[w_dtype]), GATED_NAMES)


def test_gated_xla_body_is_the_conv_between_two_multiplies():
    """The XLA body against ``_conv`` on the product, gated: the same
    float32 sums, to the bit."""
    (proj, w), _ = _gated_operands(1, 128, 2, 3, "float32", "float32")
    gate_in, gate_out, x = jnp.split(proj, 3, axis=-1)
    want = gate_out * ssd._conv(gate_in * x, w, jnp.zeros((128,)))
    np.testing.assert_array_equal(ssd._gated_conv(proj, w), want)
    # a row of the batch by itself: the second row's first tokens read
    # nothing of the first row's last
    alone = ssd._gated_conv(proj[1:], w)
    np.testing.assert_array_equal(ssd._gated_conv(proj, w)[1:], alone)


def test_gated_kernels_keep_the_operands_only(monkeypatch):
    """The residuals of the gated rule are the caller's two arrays, and
    no window of the in-projection is sliced out around the kernels."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    args, _ = _gated_operands(1, 128, 2, 3, "bfloat16", "bfloat16")
    y, residuals = kernels._gated_conv_fwd(*args, 128)
    assert y.dtype == jnp.bfloat16 and y.shape == (2, 512, 128)
    assert all(kept is a for kept, a in zip(residuals, args))
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: ssd.gated_conv(*a).astype(F32).sum(), range(2)
    ))(*args))
    assert text.count("pallas_call") == 2  # (the jaxpr keeps the dead y)
    outside = text.split("pallas_call")[0]
    assert "slice" not in outside and "f32[2,512,384]" not in outside
    # and behind it: [dB | dC | dx] leaves the kernel as one array
    assert "[2,512,128] = slice" not in text
    assert "[2,512,384] = concatenate" not in text
    assert "[2,512,384] = pad" not in text


# tokens, channels a gate, taps, devices, interpreted
GATED_XLA_BODY = {
    "gated-tier-1-widths": (64, 64, 3, 1, True),
    "gated-channels-off-the-lanes": (1024, 192, 3, 1, True),
    "gated-a-length-off-the-block": (768, 128, 3, 1, True),
    "gated-ten-taps": (1024, 128, 10, 1, True),
    "gated-a-mesh-of-two": (1024, 128, 3, 2, True),
    "gated-off-the-chip": (1024, 128, 3, 1, False),
    # the backward's one block of 3 x 32,768 columns does not fit VMEM
    "gated-too-wide-a-row": (1024, 32768, 3, 1, True),
}


@pytest.mark.parametrize("case", sorted(GATED_XLA_BODY))
def test_gated_shapes_the_kernels_do_not_tile_take_the_xla_body(
    monkeypatch, case
):
    seq, channels, taps, devices, interpreted = GATED_XLA_BODY[case]
    monkeypatch.setattr(pallas_attention, "INTERPRET", interpreted)
    mesh = _mesh(devices)
    assert kernels.gated_tile(seq, channels, taps, mesh=mesh) is None
    if channels > 4096:
        return  # the answer is what is held; no array of that width here
    k = jax.random.split(jax.random.key(1), 2)
    args = (
        jax.random.normal(k[0], (1, seq, 3 * channels)),
        jax.random.normal(k[1], (taps, channels)),
    )
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: ssd.gated_conv(*a, mesh=mesh).sum(), range(2)
    ))(*args))
    assert "pallas_call" not in text
    np.testing.assert_array_equal(
        ssd.gated_conv(*args, mesh=mesh), ssd._gated_conv(*args)
    )
