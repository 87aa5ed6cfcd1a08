"""Defects a program of LFM2's architecture can have, one per thing that
PR 73 adds or that its configuration states, each planted by patching
the program from outside (``ops/ssd.py``, ``models/decoder.py``,
``parallel/moe.py``) in ``mellum_defects.py``'s manner: what the routed
comparison has to catch. The gated conv has no check of its own and its
defects have to show through the LOGITS; the router's show there or in
the loss. The conv's are planted by stand-ins for ``ssd.gated_conv(proj,
weight, mesh=None)``, the one door the mixer goes through, whichever
body is behind it. The tests run them at a tiny size on the CPU
(``test_lfm2_cell.py``, ``tests/test_lfm2_moe_reference.py``); on the
chip

    python3 benchmarks/tests/lfm2_defects.py <defect> --workload \\
        lfm2-ep4-train-b8s4096 --seed <n> --seconds 5 --trace 0

runs the cell itself with the defect planted: its result has to read
``correct: false`` by one of ``CAUGHT_BY[defect]``.

Each ``plant(setattr)`` takes a ``setattr``-like callable
(``monkeypatch.setattr`` in a test).
"""


def _gated(patch, wrong):
    """``ssd.gated_conv`` replaced by ``wrong(sound, proj, weight)``,
    ``sound`` the function it replaces (the mesh handed on)."""
    from dlrover_tpu.ops import ssd

    sound = ssd.gated_conv
    patch(
        ssd, "gated_conv",
        lambda proj, weight, mesh=None: wrong(
            lambda p, w: sound(p, w, mesh=mesh), proj, weight
        ),
    )


def _with_ones(proj, window):
    """[B | C | x] with one window of ones: that gate dropped."""
    import jax.numpy as jnp

    parts = list(jnp.split(proj, 3, axis=-1))
    parts[window] = jnp.ones_like(parts[window])
    return jnp.concatenate(parts, axis=-1)


def b_gate_dropped(patch):
    """The conv runs over x and not over B * x."""
    _gated(patch, lambda sound, proj, w: sound(_with_ones(proj, 0), w))


def c_gate_dropped(patch):
    """The conv's output goes to the out-projection ungated."""
    _gated(patch, lambda sound, proj, w: sound(_with_ones(proj, 1), w))


def taps_reversed(patch):
    """w_0 multiplies the present token and w_2 the one two back."""
    _gated(patch, lambda sound, proj, w: sound(proj, w[::-1]))


def _after_the_conv(patch, change):
    """y = C * change(c), c the conv's own output."""
    import jax.numpy as jnp

    def wrong(sound, proj, w):
        c = sound(_with_ones(proj, 1), w)
        gate = jnp.split(proj, 3, axis=-1)[1]
        return (gate * change(c)).astype(proj.dtype)

    _gated(patch, wrong)


def conv_one_token_ahead(patch):
    """The conv is centred: token t reads z of t - 1, t and t + 1, the
    future among them."""
    import jax.numpy as jnp

    _after_the_conv(
        patch,
        lambda c: jnp.concatenate(
            [c[:, 1:], jnp.zeros_like(c[:, :1])], axis=1
        ),
    )


def silu_after_the_conv(patch):
    """An activation where LFM2 has none: y = C * silu(conv(B * x)), the
    Mamba mixers' habit."""
    import jax

    _after_the_conv(patch, jax.nn.silu)


def _with_config(patch, module, name, **changes):
    """``module.name(..., cfg, ...)`` called with ``cfg`` changed."""
    import dataclasses

    from dlrover_tpu.models.config import ModelConfig

    sound = getattr(module, name)

    def wrong(*args, **kwargs):
        args = tuple(
            dataclasses.replace(a, **changes) if isinstance(a, ModelConfig)
            else a
            for a in args
        )
        return sound(*args, **kwargs)

    patch(module, name, wrong)


def qk_norm_skipped(patch):
    """q and k go to rope as projected: no RMSNorm a head."""
    from dlrover_tpu.models import decoder

    _with_config(patch, decoder, "_project_qkv", qk_head_norm=False)


def softmax_for_sigmoid(patch):
    """The router's scores are a softmax over the 32 logits: the same
    four experts, other weights."""
    from dlrover_tpu.parallel import moe

    _with_config(patch, moe, "_route", moe_score="softmax")


def raw_weights(patch):
    """The four scores as they are, where ``norm_topk_prob`` divides
    them by their sum."""
    from dlrover_tpu.parallel import moe

    rule = moe._topk_weights
    patch(
        moe, "_topk_weights",
        lambda probs, k, renormalize: rule(probs, k, False),
    )


LOGITS = ("logits_vs_reference", "logits_rms_vs_reference")
PLANT = {
    "b_gate_dropped": b_gate_dropped,
    "c_gate_dropped": c_gate_dropped,
    "taps_reversed": taps_reversed,
    "conv_one_token_ahead": conv_one_token_ahead,
    "qk_norm_skipped": qk_norm_skipped,
    "softmax_for_sigmoid": softmax_for_sigmoid,
    "raw_weights": raw_weights,
    "silu_after_the_conv": silu_after_the_conv,
}
# defect -> the checks of which at least one has to read not ok
CAUGHT_BY = {
    **dict.fromkeys(PLANT, LOGITS),
    "softmax_for_sigmoid": LOGITS + ("loss_vs_reference",),
    "raw_weights": LOGITS + ("loss_vs_reference",),
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
