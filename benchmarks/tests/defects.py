"""Defects a routed program can have, each injected by patching the
program from outside: what the routed comparison (``lib/routed.py``)
has to catch, and the check that has to catch it. ``test_rehearsal.py``
runs them at a tiny size on the CPU; the chip rehearsal (PERF.md
section 6, PR 28) ran the same patches at Mixtral's widths.

Each ``inject(setattr)`` takes a ``setattr``-like callable
(``monkeypatch.setattr`` in a test) and patches ``parallel/moe.py``.
The layer scan gives a router no layer number, so a defect is in every
layer, not in one.
"""

EVERY = 100  # one token in a hundred


def kplus1(patch):
    """The (k+1)-th best expert instead of the k-th, on 1% of tokens."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.parallel import moe

    def topk_weights(probs, k, renormalize):
        vals, idx = jax.lax.top_k(probs, k + 1)
        token = jnp.arange(probs[..., 0].size).reshape(probs.shape[:-1])
        hit = (token % EVERY == 7)[..., None]
        last = jnp.arange(k) == k - 1
        vals = jnp.where(hit & last, vals[..., 1:], vals[..., :-1])
        idx = jnp.where(hit & last, idx[..., 1:], idx[..., :-1])
        if renormalize and k > 1:
            vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
        return vals, idx

    patch(moe, "_topk_weights", topk_weights)


def raw_weights(patch):
    """Raw top-k probabilities where the configuration renormalises."""
    from dlrover_tpu.parallel import moe

    rule = moe._topk_weights
    patch(
        moe, "_topk_weights",
        lambda probs, k, renormalize: rule(probs, k, False),
    )


def w_down_scaled(patch, factor=1.25):
    """One expert's down projection a quarter too large, in the program
    only. At 5% (``factor=1.05``) the comparison PASSES it, on the chip
    (teacher-forced logits 2.4e-2..2.7e-2 at the maximum, rms
    1.2e-2..1.4e-2, against 1.3e-2 and 8.8e-3 sound) as at the tiny
    size: one expert of six in one layer, 5% off, is inside what
    LOGIT_TOL allows bf16. About 10% is where it starts to fail."""
    from dlrover_tpu.parallel import moe

    ffn = moe._ragged_ffn

    def scaled(xl, moe_local, gate_idx, weights, dtype):
        w = moe_local["w_down"]
        moe_local = dict(moe_local, w_down=w.at[0].multiply(factor))
        return ffn(xl, moe_local, gate_idx, weights, dtype)

    patch(moe, "_ragged_ffn", scaled)


def lb_off_1pct(patch):
    """The load-balancing loss 1% too large, in the program only."""
    from dlrover_tpu.parallel import moe

    ragged_aux = moe._ragged_aux

    def aux(*args, **kwargs):
        out = ragged_aux(*args, **kwargs)
        return dict(out, moe_lb_loss=1.01 * out["moe_lb_loss"])

    patch(moe, "_ragged_aux", aux)


def router_8bit(patch):
    """Router logits rounded to 8 bits (e4m3) before the softmax."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.parallel import moe

    def route(x, moe_params, cfg, rng):
        logits = x @ moe_params["w_gate"].astype(x.dtype)
        logits = logits.astype(jnp.float8_e4m3fn).astype(x.dtype)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        weights, idx = moe._topk_weights(probs, cfg.expert_top_k, True)
        return logits, probs, weights, idx

    patch(moe, "_route", route)


# defect -> the checks of which at least one has to read not ok
CAUGHT_BY = {
    "kplus1": ("routing_regret",),
    "raw_weights": (
        "logits_vs_reference", "logits_rms_vs_reference", "loss_vs_reference",
    ),
    "w_down_scaled": ("logits_vs_reference", "logits_rms_vs_reference"),
    "router_8bit": ("routing_regret",),
    "lb_off_1pct": ("moe_lb_loss_vs_reference",),
}
INJECT = {
    "kplus1": kplus1, "raw_weights": raw_weights,
    "w_down_scaled": w_down_scaled, "router_8bit": router_8bit,
    "lb_off_1pct": lb_off_1pct,
}
