"""Defects a program of Mellum2's architecture can have, one per thing
that PR 70 adds or that its configuration states, each planted by
patching the program from outside (``models/decoder.py``,
``models/config.py``, ``parallel/moe.py``) in ``trinity_defects.py``'s
manner: what the routed comparison has to catch. The rope's defects
have no check of their own and have to show through the LOGITS; the
router's show there or in the regret. The tests run them at a tiny size
on the CPU (``test_mellum_cell.py``,
``tests/test_mellum_reference.py``); on the chip

    python3 benchmarks/tests/mellum_defects.py <defect> --workload \\
        mellum2-ep4-train-b1s32768 --seed <n> --seconds 5 --trace 0

runs the cell itself with the defect planted: its result has to read
``correct: false`` by one of ``CAUGHT_BY[defect]``.

Each ``plant(setattr)`` takes a ``setattr``-like callable
(``monkeypatch.setattr`` in a test).
"""


def _config():
    from dlrover_tpu.models.config import ModelConfig

    return ModelConfig


def _every_kind_under(patch, rope):
    sound = _config().kind_rope
    patch(
        _config(), "kind_rope",
        lambda self, kind="": rope if sound(self, kind) else "",
    )


def full_layers_under_plain_rope(patch):
    """The full layers are turned by the window layers' table: theta
    alone, no blend, no amplitude."""
    _every_kind_under(patch, "plain")


def window_layers_under_scaled_rope(patch):
    """The window layers are turned by the full layers' table: YaRN's
    frequencies and its amplitude."""
    _every_kind_under(patch, "scaled")


def _scaling(patch, change):
    """The scaled table's five numbers passed through ``change``."""
    sound = _config().rope_scaling.fget
    patch(
        _config(), "rope_scaling",
        property(lambda self: sound(self) and change(*sound(self))),
    )


def amplitude_left_out(patch):
    """cos and sin of the scaled table at amplitude 1: the full layers'
    scores lose the factor 1.6314."""
    _scaling(patch, lambda f, n, fast, slow, m: (f, n, fast, slow, 1.0))


def every_pair_interpolated(patch):
    """Position interpolation where YaRN belongs: every pair turns
    ``factor`` times slower, the fast ones too."""
    from dlrover_tpu.models import decoder

    sound = decoder._rope_frequencies

    def interpolated(head_dim, theta, scaling=None):
        freqs = sound(head_dim, theta)
        return freqs if scaling is None else freqs / scaling[0]

    patch(decoder, "_rope_frequencies", interpolated)


def ramp_bounds_swapped(patch):
    """The ramp runs the other way: the fast pairs are interpolated
    whole and the slow ones keep their frequency."""
    from dlrover_tpu.models import decoder

    sound = decoder._rope_frequencies

    def swapped(head_dim, theta, scaling=None):
        kept = sound(head_dim, theta)
        if scaling is None:
            return kept
        slowed = kept / scaling[0]
        ramp = (kept - sound(head_dim, theta, scaling)) / (kept - slowed)
        return kept * ramp + slowed * (1.0 - ramp)

    patch(decoder, "_rope_frequencies", swapped)


def window_twice_as_wide(patch):
    """A window layer sees 2 x ``attn_window`` keys (2,048 on the
    cell)."""
    sound = _config().kind_window
    patch(
        _config(), "kind_window",
        lambda self, kind="": 2 * (sound(self, kind) or 0),
    )


def raw_weights(patch):
    """The top-8 probabilities as they are, where ``norm_topk_prob``
    divides them by their sum."""
    from dlrover_tpu.parallel import moe

    rule = moe._topk_weights
    patch(
        moe, "_topk_weights",
        lambda probs, k, renormalize: rule(probs, k, False),
    )


def router_bf16(patch):
    """Router logits rounded to bf16 before the softmax, where the
    configuration states float32."""
    import jax.numpy as jnp

    from dlrover_tpu.parallel import moe

    sound = moe._router_logits

    def rounded(x, moe_params, cfg, rng):
        logits = sound(x, moe_params, cfg, rng)
        return logits.astype(jnp.bfloat16).astype(jnp.float32)

    patch(moe, "_router_logits", rounded)


LOGITS = ("logits_vs_reference", "logits_rms_vs_reference")
PLANT = {
    "full_layers_under_plain_rope": full_layers_under_plain_rope,
    "amplitude_left_out": amplitude_left_out,
    "every_pair_interpolated": every_pair_interpolated,
    "window_layers_under_scaled_rope": window_layers_under_scaled_rope,
    "raw_weights": raw_weights,
    "router_bf16": router_bf16,
    "window_twice_as_wide": window_twice_as_wide,
    "ramp_bounds_swapped": ramp_bounds_swapped,
}
# defect -> the checks of which at least one has to read not ok
CAUGHT_BY = {
    **dict.fromkeys(PLANT, LOGITS),
    "raw_weights": LOGITS + ("loss_vs_reference",),
    "router_bf16": ("routing_regret",) + LOGITS,
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
