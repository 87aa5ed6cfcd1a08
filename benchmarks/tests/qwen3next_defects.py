"""Defects a program of Qwen3-Next's architecture can have, one per part
that PR 63 adds, each planted by patching the program from outside
(``models/decoder.py``, ``models/config.py``, ``ops/gated_delta.py``,
``ops/ssd.py``, ``parallel/moe.py``) in ``defects.py``'s manner: what
the routed comparison has to catch through the LOGITS — and, for a
uniform scale of the delta rule's read-out, which the per-head norm
behind it hides from the logits, through ``gdn_readout_ms``. The tests
run them at a tiny size on the CPU (``test_qwen3next_cell.py``,
``tests/test_qwen3_next_reference.py``); on the chip

    python3 benchmarks/tests/qwen3next_defects.py <defect> --workload \\
        qwen3next-ep16-train-b1s16384 --seed <n> --seconds 5 --trace 0

runs the cell itself with the defect planted: its result has to read
``correct: false`` by one of ``CAUGHT_BY[defect]``.

Each ``plant(setattr)`` takes a ``setattr``-like callable
(``monkeypatch.setattr`` in a test).
"""


def _rule_with(patch, change):
    """``gated_delta_rule`` called on ``change(q, k, v, g, beta)``."""
    from dlrover_tpu.ops import gated_delta

    rule = gated_delta.gated_delta_rule
    patch(
        gated_delta, "gated_delta_rule",
        lambda q, k, v, g, beta, chunk=64: rule(
            *change(q, k, v, g, beta), chunk
        ),
    )


def decay_left_out(patch):
    """The un-gated delta rule: α ≡ 1, the state never forgets."""
    _rule_with(patch, lambda q, k, v, g, beta: (q, k, v, 0.0 * g, beta))


def write_strength_one(patch):
    """β ≡ 1: every token overwrites what its key held."""
    _rule_with(
        patch, lambda q, k, v, g, beta: (q, k, v, g, 0.0 * beta + 1.0)
    )


def key_heads_tiled(patch):
    """Value head h reads key head h mod Hk (the key heads tiled over
    the value heads) where it reads h // R (each repeated for its R
    neighbours)."""
    from dlrover_tpu.ops import gated_delta

    rule = gated_delta.gated_delta_rule

    def tiled(q, k, v, g, beta, chunk=64):
        hk, hv = k.shape[2], v.shape[2]
        # position p of the permuted value heads holds head h with
        # h mod Hk = p // R
        order = [j + r * hk for j in range(hk) for r in range(hv // hk)]
        back = sorted(range(hv), key=order.__getitem__)
        o = rule(
            q, k, v[:, :, order], g[:, :, order], beta[:, :, order], chunk
        )
        return o[:, :, back]

    patch(gated_delta, "gated_delta_rule", tiled)


def _l2_with(patch, change):
    """``decoder._l2_heads(t, scale)`` as ``change(l2, t, scale)``."""
    from dlrover_tpu.models import decoder

    l2 = decoder._l2_heads
    patch(
        decoder, "_l2_heads",
        lambda t, scale=1.0, eps=1e-6: change(l2, t, scale),
    )


def key_norm_left_out(patch):
    """k goes into the rule as the conv left it: no L2 norm (q, the call
    with a scale, keeps its)."""
    _l2_with(
        patch, lambda l2, t, scale: t if scale == 1.0 else l2(t, scale)
    )


def query_scale_left_out(patch):
    """q is normed and not divided by sqrt(key channels): the read-out
    is sqrt(Dk) times too large in every head, which the norm a head
    behind it takes out again."""
    _l2_with(patch, lambda l2, t, scale: l2(t))


def query_norm_left_out(patch):
    """q is scaled and not L2-normed: the read-out of a token is off by
    its |q|, which the norm a head takes out but for eps."""
    _l2_with(
        patch, lambda l2, t, scale: t * scale if scale != 1.0 else l2(t)
    )


def gate_before_the_norm(patch):
    """``rms_head(o ⊙ silu(z)) w`` (Mamba-2's order) for ``rms_head(o) w
    ⊙ silu(z)``."""
    from dlrover_tpu.ops import ssd

    norm = ssd.gated_group_norm
    patch(
        ssd, "gated_group_norm",
        lambda y, z, scale, groups, eps, norm_before_gate=False: norm(
            y, z, scale, groups, eps
        ),
    )


def rope_on_every_channel(patch):
    """Rope turns all of a head's channels, not its first quarter."""
    from dlrover_tpu.models.config import ModelConfig

    patch(ModelConfig, "rope_dim", property(lambda self: self.head_dim))


def norms_not_zero_centred(patch):
    """A trunk norm multiplies by ``w`` where it multiplies by
    ``1 + w``."""
    from dlrover_tpu.models import decoder

    patch(decoder, "_multiplier", lambda scale, cfg: scale)


def shared_gate_left_out(patch):
    """The shared expert's output is added ungated."""
    from dlrover_tpu.parallel import moe

    shared_expert = moe._shared_expert

    def ungated(x, shared, mesh):
        return shared_expert(
            x, {k: w for k, w in shared.items() if k != "w_own_gate"}, mesh
        )

    patch(moe, "_shared_expert", ungated)


LOGITS = ("logits_vs_reference", "logits_rms_vs_reference")
READOUT = ("gdn_readout_ms_vs_reference",)
# defect -> the checks of which at least one has to read not ok
CAUGHT_BY = {
    "decay_left_out": LOGITS,
    "write_strength_one": LOGITS,
    "key_heads_tiled": LOGITS,
    "key_norm_left_out": LOGITS,
    "query_scale_left_out": READOUT,
    "query_norm_left_out": READOUT,
    "gate_before_the_norm": LOGITS,
    "rope_on_every_channel": LOGITS,
    "norms_not_zero_centred": LOGITS,
    "shared_gate_left_out": LOGITS,
}
PLANT = {
    "decay_left_out": decay_left_out,
    "write_strength_one": write_strength_one,
    "key_heads_tiled": key_heads_tiled,
    "key_norm_left_out": key_norm_left_out,
    "query_scale_left_out": query_scale_left_out,
    "query_norm_left_out": query_norm_left_out,
    "gate_before_the_norm": gate_before_the_norm,
    "rope_on_every_channel": rope_on_every_channel,
    "norms_not_zero_centred": norms_not_zero_centred,
    "shared_gate_left_out": shared_gate_left_out,
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
