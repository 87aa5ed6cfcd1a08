"""Defects a program of Kimi-Linear's architecture can have, one per
part that PR 65 adds, each planted by patching the program from outside
(``models/decoder.py``, ``ops/gated_delta.py``, ``ops/ssd.py``,
``parallel/moe.py``) in ``qwen3next_defects.py``'s manner: each a WRONG
MODEL that the routed comparison has to refuse at the cell's limits,
through the LOGITS — and, for a uniform scale of the rule's read-out,
which the per-head norm behind it hides from the logits, through
``kda_readout_ms``. The tests run them at a tiny size on the CPU
(``test_kimilinear_cell.py``, ``tests/test_kimi_linear_reference.py``);
on the chip

    python3 benchmarks/tests/kimilinear_defects.py <defect> --workload \\
        kimilinear-ep16-train-b1s16384 --seed <n> --seconds 5 --trace 0

runs the cell itself with the defect planted: its result has to read
``correct: false`` by one of ``CAUGHT_BY[defect]``.

Each ``plant(setattr)`` takes a ``setattr``-like callable
(``monkeypatch.setattr`` in a test).
"""

import dataclasses


def _rule_with(patch, change):
    """``gated_delta_rule`` called on ``change(q, k, v, g, beta)``."""
    from dlrover_tpu.ops import gated_delta

    rule = gated_delta.gated_delta_rule
    patch(
        gated_delta, "gated_delta_rule",
        lambda q, k, v, g, beta, **kw: rule(
            *change(q, k, v, g, beta), **kw
        ),
    )


def decay_averaged_over_channels(patch):
    """One decay a head, the mean of its channels' log-decays: the
    scalar rule (Gated DeltaNet) under this model's name."""
    import jax.numpy as jnp

    _rule_with(patch, lambda q, k, v, g, beta: (
        q, k, v,
        jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape), beta,
    ))


def decay_after_the_write(patch):
    """``S_t = Diag(α_t) (S_{t-1} + β_t k_t (v_t − S_{t-1}ᵀ k_t)ᵀ)``: the
    token's own write decays with the rest, where the decay comes
    BEFORE the write. The same numbers as the sound rule on the decays
    one token late, read out by ``α_t ⊙ q_t``."""
    import jax.numpy as jnp

    def late(q, k, v, g, beta):
        shifted = jnp.pad(g, ((0, 0), (1, 0), (0, 0), (0, 0)))[:, :-1]
        return (q * jnp.exp(g)).astype(q.dtype), k, v, shifted, beta

    _rule_with(patch, late)


def rope_in_the_latent_layer(patch):
    """The latent layer's 64 shared key channels and q's last 64 turned
    by rope (theta ``rope_theta``), where ``mla_use_nope`` turns none."""
    from dlrover_tpu.models import decoder

    latent = decoder._latent_qkv
    patch(
        decoder, "_latent_qkv",
        lambda x, attn, cfg, positions, rope=None: latent(
            x, attn, dataclasses.replace(cfg, pos="rope"), positions
        ),
    )


def silu_for_the_sigmoid_gate(patch):
    """``rms_head(o) w ⊙ silu(z)`` (Qwen3-Next's gate) for ``⊙
    sigmoid(z)``."""
    from dlrover_tpu.ops import ssd

    norm = ssd.gated_group_norm
    patch(
        ssd, "gated_group_norm",
        lambda y, z, scale, groups, eps, norm_before_gate=False, gate=None:
        norm(y, z, scale, groups, eps, norm_before_gate),
    )


def query_scale_left_out(patch):
    """q is normed and not divided by sqrt(key channels): the read-out
    is sqrt(Dk) times too large in every head, which the norm a head
    behind it takes out again."""
    from dlrover_tpu.models import decoder

    l2 = decoder._l2_heads
    patch(decoder, "_l2_heads", lambda t, scale=1.0, eps=1e-6: l2(t))


def routed_scaling_factor_one(patch):
    """The renormalised weights go on as they are, not times 2.446."""
    from dlrover_tpu.parallel import moe

    route = moe._route
    patch(
        moe, "_route",
        lambda x, params, cfg, rng: route(
            x, params, dataclasses.replace(cfg, routed_scaling_factor=1.0),
            rng,
        ),
    )


LOGITS = ("logits_vs_reference", "logits_rms_vs_reference")
READOUT = ("kda_readout_ms_vs_reference",)
# defect -> the checks of which at least one has to read not ok
CAUGHT_BY = {
    "decay_averaged_over_channels": LOGITS,
    "decay_after_the_write": LOGITS,
    "rope_in_the_latent_layer": LOGITS,
    "silu_for_the_sigmoid_gate": LOGITS,
    "query_scale_left_out": READOUT,
    "routed_scaling_factor_one": LOGITS,
}
PLANT = {
    "decay_averaged_over_channels": decay_averaged_over_channels,
    "decay_after_the_write": decay_after_the_write,
    "rope_in_the_latent_layer": rope_in_the_latent_layer,
    "silu_for_the_sigmoid_gate": silu_for_the_sigmoid_gate,
    "query_scale_left_out": query_scale_left_out,
    "routed_scaling_factor_one": routed_scaling_factor_one,
}


if __name__ == "__main__":
    import os
    import sys

    sys.path[0] = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    from benchmarks import run

    PLANT[sys.argv[1]](setattr)
    sys.exit(run.main(sys.argv[2:]))
