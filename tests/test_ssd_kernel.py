"""The Mamba-2 scan's Pallas kernels (``ops/pallas_ssd.py``) in interpret
mode on the CPU: against the XLA body of the same ``ssd_scan`` — the
output and the gradient of every input —, against the token-by-token
recurrence of the benchmark's plain reference, and that ``ssd_scan``
takes them only where it says it does.

What the chip's compiler makes of them is ``tests/test_tpu_compile.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import nemotron_h_plain as plain
from dlrover_tpu.models import decoder, get_config
from dlrover_tpu.observability import tracing
from dlrover_tpu.ops import pallas_attention, pallas_ssd, ssd

F32 = jnp.float32
# seq, heads, channels, groups, batch, dtype; state 128 throughout. The
# kernels' chunk is the first of ``pallas_ssd.CHUNKS`` (256, 128) that
# divides the length padded to the model's chunk of 128. A group of up
# to ``pallas_ssd.TURN`` (4) slabs of 128 lanes goes through in one grid
# step, its slabs numbered in Python; sixteen heads of 64 are eight
# slabs, two turns, the slabs numbered by the grid
SHAPES = {
    "one-chunk": (128, 4, 64, 2, 1, "float32"),
    "three-chunks": (384, 4, 64, 2, 1, "float32"),
    "two-chunks-of-256": (512, 4, 64, 2, 1, "float32"),
    "padded": (300, 4, 64, 2, 2, "float32"),
    "one-group": (256, 2, 64, 1, 1, "float32"),
    "heads-of-128": (384, 2, 128, 2, 1, "float32"),
    "four-heads-a-group": (384, 8, 64, 2, 1, "float32"),
    "two-turns-a-group": (256, 16, 64, 1, 1, "float32"),
    "two-turns-of-heads-of-128": (256, 8, 128, 1, 1, "float32"),
    "bfloat16": (384, 4, 64, 2, 1, "bfloat16"),
}
# max |kernel - XLA body| over max |XLA body|, an output or a gradient
# (dA reads 2e-5 in float32: the kernel's d cum is the difference of two
# sums that cancel over a chunk; a wrong or missing term reads 1e-2 and up)
TOLERANCE = {"float32": 1e-4, "bfloat16": 2e-2}


def _inputs(seq, heads, channels, groups, batch, dtype, state=128):
    k = jax.random.split(jax.random.key(3), 6)
    return (
        jax.random.normal(k[0], (batch, seq, heads, channels), dtype),
        jax.nn.softplus(jax.random.normal(k[1], (batch, seq, heads)) - 2.0),
        -jnp.exp(jax.random.uniform(k[2], (heads,), minval=0.0, maxval=2.7)),
        (0.3 * jax.random.normal(k[3], (batch, seq, groups, state))).astype(
            dtype
        ),
        (0.3 * jax.random.normal(k[4], (batch, seq, groups, state))).astype(
            dtype
        ),
    ), jax.random.normal(k[5], (batch, seq, heads, channels))


def _value_and_grads(args, weight, monkeypatch, kernels: bool):
    monkeypatch.setattr(pallas_attention, "INTERPRET", kernels)
    assert (ssd.kernel_chunk(
        args[0].shape[1], *args[0].shape[2:], *args[3].shape[2:], 128
    ) is not None) == kernels

    def loss(*a):
        y = ssd.ssd_scan(*a, 128, 2)
        return (y.astype(F32) * weight).sum(), y

    # one program (eager, every operation of both bodies is a dispatch
    # and a compile of its own); the switch is read as it is traced
    (_, y), grads = jax.jit(
        jax.value_and_grad(loss, range(5), has_aux=True)
    )(*args)
    return (y, *grads)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernels_are_the_xla_body(monkeypatch, shape):
    """y and the gradients of x, Δ, A, B and C: one chunk and several
    (the carried state forward, its cotangent backward), a length that
    is no multiple of the chunk (padded with Δ = 0), one group and two,
    heads that share a 128-lane slab and heads that fill one, a group
    in one grid step and in two."""
    args, weight = _inputs(*SHAPES[shape])
    got = _value_and_grads(args, weight, monkeypatch, kernels=True)
    want = _value_and_grads(args, weight, monkeypatch, kernels=False)
    for name, a, b in zip(("y", "dx", "dΔ", "dA", "dB", "dC"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        err = np.abs(a - b).max() / np.abs(b).max()
        assert err <= TOLERANCE[SHAPES[shape][-1]], (name, err)


def test_a_slab_a_turn_is_the_same_scan(monkeypatch):
    """Whatever the slabs a grid step (``pallas_ssd.TURN``), the same
    numbers: one slab a step, the form with the shortest body, against
    the group whole, bit for bit in the output."""
    args, weight = _inputs(*SHAPES["four-heads-a-group"])
    monkeypatch.setattr(pallas_ssd, "TURN", 1)
    one = _value_and_grads(args, weight, monkeypatch, kernels=True)
    monkeypatch.setattr(pallas_ssd, "TURN", 0)
    whole = _value_and_grads(args, weight, monkeypatch, kernels=True)
    np.testing.assert_array_equal(np.asarray(one[0]), np.asarray(whole[0]))
    for name, a, b in zip(("dx", "dΔ", "dA", "dB", "dC"), one[1:], whole[1:]):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max(), name


def test_a_kernel_is_traced_once_a_process(monkeypatch):
    """What a kernel costs before it runs is its body's trace and its
    lowering (``pallas_ssd``'s docstring): two checkpointed layers
    differentiated trace the forward kernel twice (the forward, which
    the primal and the forward rule share, and the state pass) and the
    backward kernel once — not once a layer, not once a rule —, and a
    second program of the same shapes traces nothing."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    traced = {"fwd": 0, "bwd": 0}

    def counting(name, kernel):
        def body(*refs, **statics):
            traced[name] += 1
            return kernel(*refs, **statics)

        return body

    monkeypatch.setattr(
        pallas_ssd, "_fwd_kernel", counting("fwd", pallas_ssd._fwd_kernel)
    )
    monkeypatch.setattr(
        pallas_ssd, "_bwd_kernel", counting("bwd", pallas_ssd._bwd_kernel)
    )
    # shapes no other test takes: the trace is kept by shape
    args, _ = _inputs(640, 4, 64, 2, 1, "float32")

    @jax.checkpoint
    def layer(x, *rest):
        return ssd.ssd_scan(x, *rest, 128, 2)

    def loss(x, *rest):
        return layer(layer(x, *rest), *rest).sum()

    jax.jit(jax.grad(loss, range(5))).trace(*args)
    assert traced == {"fwd": 2, "bwd": 1}
    jax.jit(lambda *a: layer(*a).sum()).trace(*args)
    assert traced == {"fwd": 2, "bwd": 1}


def test_the_kernel_chunk_is_the_first_that_divides(monkeypatch):
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    assert ssd.kernel_chunk(512, 4, 64, 2, 128, 128) == 256
    assert ssd.kernel_chunk(384, 4, 64, 2, 128, 128) == 128
    assert ssd.kernel_chunk(300, 4, 64, 2, 128, 128) == 128
    assert ssd.kernel_chunk(8192, 128, 64, 8, 128, 128) == 256


MIXER = dict(
    n_layer=2, layer_pattern="ME", d_model=64, n_head=4, n_kv_head=2,
    d_head=16, vocab_size=256, max_seq=256, mamba_num_heads=4,
    mamba_head_dim=64, ssm_state_size=128, n_groups=2, ssm_chunk=128,
    ssm_head_block=2, n_experts=16, expert_top_k=6, d_expert=48,
    moe_latent_size=32, d_shared_expert=96, n_experts_held=4,
    expert_offset=0, remat="full", dtype="float32",
)


def test_mixer_through_the_kernels_is_the_token_by_token_reference(
    monkeypatch,
):
    """A whole Mamba-2 mixer (``decoder._mamba_block``) whose scan runs
    the kernels, against ``benchmarks/references/nemotron_h_plain.py``'s:
    the recurrence one token at a time, another algorithm. 200 tokens:
    two kernel chunks, the second padded."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    cfg = get_config("nemotron-3-super", **MIXER)
    params = decoder.init(jax.random.key(1), cfg)
    mixer = jax.tree.map(lambda t: t[0], params["layers"]["mamba"]["ssm"])
    u = jax.random.normal(jax.random.key(2), (2, 200, cfg.d_model))
    sizes = {
        k: getattr(cfg, k) for k in (
            "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
            "n_groups", "conv_kernel", "ssm_norm_eps",
        )
    }
    tracing._counters.clear()
    got = decoder._mamba_block(u, mixer, cfg, None)
    assert tracing.counters()["ssm.scan_in_kernel"] == 1
    with jax.default_matmul_precision("highest"):
        want = plain._mamba(u, mixer, sizes)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
    )


def _mesh(n):
    from dlrover_tpu.parallel import MeshConfig, build_mesh

    return build_mesh(MeshConfig(dp=-1), devices=jax.devices()[:n])


# heads, channels, groups, state, devices, interpreted
XLA_BODY = {
    "tier-1-widths": (8, 8, 2, 16, 1, True),
    "state-of-64": (4, 64, 2, 64, 1, True),
    "heads-of-96": (4, 96, 2, 128, 1, True),
    "one-head-of-64-a-group": (2, 64, 2, 128, 1, True),
    "a-mesh-of-eight": (4, 64, 2, 128, 8, True),
    "off-the-chip": (4, 64, 2, 128, 1, False),
}


@pytest.mark.parametrize("case", sorted(XLA_BODY))
def test_shapes_the_kernels_do_not_tile_take_the_xla_body(monkeypatch, case):
    """Untileable widths, a multi-device mesh, and the CPU without
    interpret mode: ``ssd_scan`` lowers no ``pallas_call``; the same
    call at tileable widths on one device lowers three (the forward,
    and in the backward rule the starting states and the backward)."""
    heads, channels, groups, state, devices, interpreted = XLA_BODY[case]
    monkeypatch.setattr(pallas_attention, "INTERPRET", interpreted)
    mesh = _mesh(devices)
    assert ssd.kernel_chunk(
        256, heads, channels, groups, state, 128, mesh
    ) is None
    args, _ = _inputs(256, heads, channels, groups, 1, "float32", state)

    def calls(mesh, *a):
        text = str(jax.make_jaxpr(jax.grad(
            lambda *a: ssd.ssd_scan(*a, 128, 0, mesh).sum(), range(5)
        ))(*a))
        return text.count("pallas_call")

    assert calls(mesh, *args) == 0
    if interpreted:
        fit, _ = _inputs(256, 4, 64, 2, 1, "float32")
        assert calls(_mesh(1), *fit) == 3


def _step_counters(cfg, seq, devices=1):
    from dlrover_tpu.train import (
        TrainStepBuilder, batch_sharding, make_optimizer,
    )
    from dlrover_tpu.train.train_step import abstract_train_state

    mesh = _mesh(devices)
    opt = make_optimizer(learning_rate=1e-4, warmup_steps=1, decay_steps=10)
    builder = TrainStepBuilder(cfg, mesh, opt)
    state = abstract_train_state(cfg, mesh, opt, comm=builder.comm_resolved)
    batch = {
        k: jax.ShapeDtypeStruct(
            (devices, seq), jnp.int32, sharding=batch_sharding(mesh)
        )
        for k in ("tokens", "targets")
    }
    tracing._counters.clear()
    builder.build().lower(state, batch)
    return tracing.counters()


@pytest.mark.parametrize(
    "widths,interpreted,devices,engaged",
    [
        (dict(mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16,
              ssm_chunk=16), True, 1, 0),
        (MIXER, False, 1, 0),
        (MIXER, True, 1, 1),
    ],
    ids=["tier-1-widths", "off-the-chip", "tileable"],
)
def test_train_step_says_which_body_the_scan_took(
    monkeypatch, widths, interpreted, devices, engaged
):
    """``ssm.scan_in_kernel``, set by ``ssd_scan`` while the step is
    traced: 0 at tier-1's small widths and off the chip, 1 where the
    kernels tile (a step of this model on a mesh of several
    devices is refused by its routed blocks: the scan's own answer there
    is ``test_shapes_the_kernels_do_not_tile_take_the_xla_body``'s)."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", interpreted)
    cfg = get_config("nemotron-3-super", **{**MIXER, **widths})
    assert _step_counters(cfg, 256, devices)["ssm.scan_in_kernel"] == engaged


def test_a_model_without_a_mamba_layer_sets_no_counter():
    cfg = get_config("nemotron-3-super", **{**MIXER, "layer_pattern": "*E"})
    assert "ssm.scan_in_kernel" not in _step_counters(cfg, 256)
