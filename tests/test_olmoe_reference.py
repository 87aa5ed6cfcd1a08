"""The program's OLMoE block against the benchmark's plain reference
(``benchmarks/references/olmoe_plain.py``) on seeded random weights, on
the CPU at a small size: whole-projection QK-norm, dropless top-k of
many experts with RAW softmax weights, float32 router. Forward under
teacher forcing and free-running, both router losses, and the gradient
of the whole objective. The chip comparison at the published widths is
the cell's own check (``olmoe-1chip-train-b2s4096``).

Tolerances. Both sides compute in float32 on the CPU (the program's
``dtype`` and ``param_dtype`` are set to float32), so what differs is
the order of the sums: flash-free attention against a blocked softmax,
a sorted grouped matmul plus scatter-add against 8 or 16 masked dense
passes. Logits of magnitude ~1 agree to a few 1e-6; 1e-4 of the largest
logit leaves room for the 2-layer depth and fails any change of the
arithmetic (the raw weights renormalised move logits by 1e-1, the
QK-norm left out by more). Gradients are sums over 64 tokens of such
terms: 1e-3 of each leaf's largest entry.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import olmoe_plain
from dlrover_tpu.models import decoder, get_config

LOGIT_TOL = 1e-4
LOSS_TOL = 1e-5
GRAD_TOL = 1e-3


def _cfg(n_experts, top_k, **kw):
    return get_config(
        "olmoe-1b-7b", n_layer=2, d_model=128, n_head=4, n_kv_head=4,
        d_ff=64, vocab_size=256, max_seq=32, n_experts=n_experts,
        expert_top_k=top_k, dtype="float32", param_dtype="float32", **kw,
    )


def _sizes(cfg):
    return {
        "norm_eps": 1e-6,  # fixed in the program's code
        **{
            k: getattr(cfg, k)
            for k in (
                "n_layer", "d_model", "n_head", "n_kv_head", "d_ff",
                "vocab_size", "rope_theta", "n_experts", "expert_top_k",
                "moe_renorm_topk", "moe_aux_coef", "moe_z_coef",
            )
        },
    }


def _setup(cfg, seed):
    k_p, k_s, k_t, k_y = jax.random.split(jax.random.key(seed), 4)
    params = decoder.init(k_p, cfg)
    # norm scales away from 1, so that a norm left out or misplaced shows
    scales = [
        path for path, _ in jax.tree_util.tree_leaves_with_path(params)
        if path[-1].key == "scale"
    ]
    keys = dict(zip(scales, jax.random.split(k_s, len(scales))))
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: 1.0 + 0.2 * jax.random.normal(keys[path], w.shape)
        if path in keys else w,
        params,
    )
    batch = {
        "tokens": jax.random.randint(k_t, (2, 32), 0, cfg.vocab_size),
        "targets": jax.random.randint(k_y, (2, 32), 0, cfg.vocab_size),
    }
    return params, batch


CASES = {"e8k2": (8, 2), "e16k4": (16, 4)}


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    cfg = _cfg(*CASES[request.param])
    assert cfg.qk_norm and not cfg.moe_renorm_topk
    assert cfg.moe_impl == "ragged"
    params, batch = _setup(cfg, seed=len(request.param) + cfg.n_experts)
    return cfg, _sizes(cfg), params, batch


def test_teacher_forced_logits_and_router_logits(case):
    cfg, sizes, params, batch = case
    logits, aux = decoder.forward(
        params, batch["tokens"], cfg, return_aux=True
    )
    choices = aux["moe_choices"]
    assert choices.shape == (2, 2, 32, cfg.expert_top_k)
    _, ref_logits, routed = olmoe_plain.loss_and_logits_routed(
        params, batch, sizes, 16, choices
    )
    err = float(jnp.max(jnp.abs(logits - ref_logits)))
    assert err <= LOGIT_TOL * float(jnp.max(jnp.abs(ref_logits))), err
    # in float32 the program routes as the reference does
    own = jax.lax.top_k(routed["router_logits"], cfg.expert_top_k)[1]
    np.testing.assert_array_equal(
        np.sort(np.asarray(choices), -1), np.sort(np.asarray(own), -1)
    )


def test_free_running_loss_and_router_losses(case):
    cfg, sizes, params, batch = case
    _, metrics = decoder.loss_fn(params, batch, cfg=cfg)
    ref_loss, ref_logits = olmoe_plain.loss_and_logits(
        params, batch, sizes, 16
    )
    routed = olmoe_plain.forward(params, batch["tokens"], sizes, 16)[1]
    assert abs(float(metrics["loss"]) - float(ref_loss)) <= LOSS_TOL * float(
        ref_loss
    )
    for name in ("moe_lb_loss", "moe_z_loss"):
        got, want = float(metrics[name]), float(routed[name])
        assert want > 0 and abs(got - want) <= LOSS_TOL * want, (name, got, want)


def test_gradients_of_the_objective(case):
    cfg, sizes, params, batch = case

    def program(p):
        return decoder.loss_fn(p, batch, cfg=cfg)[0]

    def reference(p):
        logits, routed = olmoe_plain.forward(p, batch["tokens"], sizes, 16)
        return (
            olmoe_plain._mean_ce(logits, batch["targets"])
            + routed["moe_lb_loss"] + routed["moe_z_loss"]
        )

    got = jax.grad(program)(params)
    want = jax.grad(reference)(params)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        w = flat_want[path]
        scale = float(jnp.max(jnp.abs(w)))
        if path[1].key == "mlp":  # decoder.init's dense MLP: a routed
            assert not scale and not g.any()  # model never reads it
            continue
        assert scale > 0, path  # every leaf, the two QK-norm scales too
        err = float(jnp.max(jnp.abs(g - w)))
        assert err <= GRAD_TOL * scale, (jax.tree_util.keystr(path), err, scale)


@pytest.mark.parametrize("defect", ["renormalised", "no_qk_norm"])
def test_reference_tells_the_arithmetic_apart(case, defect):
    """The tolerance has teeth: the two things OLMoE changes, undone in
    the program, are far outside it."""
    cfg, sizes, params, batch = case
    wrong = dataclasses.replace(
        cfg, **{"renormalised": {"moe_renorm_topk": True},
                "no_qk_norm": {"qk_norm": False}}[defect]
    )
    logits, aux = decoder.forward(
        params, batch["tokens"], wrong, return_aux=True
    )
    _, ref_logits, _ = olmoe_plain.loss_and_logits_routed(
        params, batch, sizes, 16, aux["moe_choices"]
    )
    err = float(jnp.max(jnp.abs(logits - ref_logits)))
    assert err > 100 * LOGIT_TOL * float(jnp.max(jnp.abs(ref_logits)))
