"""HLO regression guard for the non-matmul byte budget.

Lowers a long-context shape (llama-1.4b, b1 x s8192, bf16 moments)
under ``remat: full``, the policy every benchmark cell runs, on CPU and
counts ``convert`` ops that materialize a
full ``[B, S, d_model]`` activation in f32. Every such convert is an
extra HBM round-trip at 4 bytes/elem, so an unexplained increase is
exactly the regression class this PR closes (norms that upcast and
write back, optimizer passes that re-expand activations, etc.).

The pin is an upper bound over the *declared* f32 sites in the current
program (located by lowering and grouping converts per HLO function):

  forward scan body:  ln1 + ln2 norm upcasts (2)
  remat replay body:  the same two norms recomputed for bwd (2)
  backward scan body: stream/cotangent upcasts in the norm bwds (4)
  top level:          final-norm upcast, fused-CE hidden upcast, and
                      the embed-grad accumulation upcast (3)

Anything beyond these 11 means a new full-activation f32 tensor crept
into the step program. The 11 are ``full``'s own: its replay body
recomputes the same two norms a graded tier's did, so the count is what
it was when this guard lowered one of those (PR 51 measured it: 11
under ``full``, 9 under ``none``, which has no replay body). Lowering only (no compile), so this stays in
tier-1 time budget (<2s).
"""

import re

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.models import get_config
from dlrover_tpu.parallel.mesh import single_device_mesh
from dlrover_tpu.train import TrainStepBuilder, make_optimizer
from dlrover_tpu.train.train_step import abstract_train_state

_B, _S = 1, 8192
_MAX_FULL_F32_CONVERTS = 11


@pytest.fixture(scope="module")
def lead_step_hlo():
    cfg = get_config(
        "llama-1.4b", max_seq=_S, remat="full", param_dtype="bfloat16"
    )
    mesh = single_device_mesh()
    opt = make_optimizer(
        learning_rate=1e-4,
        warmup_steps=10,
        decay_steps=1000,
        state_dtype="bfloat16",
    )
    state_abs = abstract_train_state(cfg, mesh, opt)
    batch_abs = {
        "tokens": jax.ShapeDtypeStruct((_B, _S), jnp.int32),
        "targets": jax.ShapeDtypeStruct((_B, _S), jnp.int32),
    }
    builder = TrainStepBuilder(cfg, mesh, opt)
    lowered = jax.jit(builder.step_fn, donate_argnums=(0,)).lower(
        state_abs, batch_abs
    )
    return cfg, lowered.as_text()


def test_no_new_full_activation_f32_converts(lead_step_hlo):
    cfg, txt = lead_step_hlo
    full = rf"stablehlo\.convert.*->\s*tensor<{_B}x{_S}x{cfg.d_model}xf32>"
    n = len(re.findall(full, txt))
    assert 0 < n <= _MAX_FULL_F32_CONVERTS, (
        f"{n} full-activation f32 converts in the lead-shape step "
        f"(budget {_MAX_FULL_F32_CONVERTS}). A new [B,S,d_model] f32 "
        "tensor entered the program — check norm/loss/optimizer edits "
        "for stray upcasts that round-trip the whole activation."
    )


def test_no_f32_residual_stream_carries(lead_step_hlo):
    """The scan carry (residual stream between layers) must stay in the
    compute dtype — an f32 carry would double the inter-layer HBM
    traffic for every one of the 24 layers."""
    cfg, txt = lead_step_hlo
    # while-loop carries show up as iota-indexed dynamic-update-slices
    # over a stacked [L, B, S, d] buffer; an f32 stacked stream buffer
    # would read tensor<24x1x8192x2048xf32>.
    stacked = rf"tensor<{cfg.n_layer}x{_B}x{_S}x{cfg.d_model}xf32>"
    assert not re.search(stacked, txt), (
        "found a stacked f32 residual-stream buffer in the lowered "
        "step — the layer scan carry was upcast to f32"
    )
