"""Defects a program of Trinity-Mini's architecture can have, one per
part that PR 47 adds, each planted by patching the program from outside
(``models/decoder.py``, ``models/config.py``) in ``defects.py``'s
manner: what the routed comparison has to catch through the LOGITS,
since none of these parts has a check of its own. The tests run them at
a tiny size on the CPU (``test_trinity_cell.py``,
``tests/test_trinity_reference.py``); on the chip

    python3 benchmarks/tests/trinity_defects.py <defect> --workload \\
        trinitymini-ep8-train-b1s16384 --seed <n> --seconds 5 --trace 0

runs the cell itself with the defect planted: its result has to read
``correct: false`` by one of ``CAUGHT_BY[defect]``.

Each ``plant(setattr)`` takes a ``setattr``-like callable
(``monkeypatch.setattr`` in a test).
"""


def gate_left_out(patch):
    """The attention's output goes to ``W_o`` ungated."""
    from dlrover_tpu.models import decoder

    patch(decoder, "_gate_output", lambda out, x, w_gate: out)


def rope_on_full_layers(patch):
    """Rope turns q and k in the full layers too."""
    from dlrover_tpu.models.config import ModelConfig

    patch(ModelConfig, "kind_rope", lambda self, kind="": self.pos == "rope")


def window_twice_as_wide(patch):
    """A window layer sees 2 x ``attn_window`` keys (4,096 on the
    cell)."""
    from dlrover_tpu.models.config import ModelConfig

    patch(
        ModelConfig, "kind_window",
        lambda self, kind="": 0 if kind == "F" else 2 * self.attn_window,
    )


def attention_output_norm_left_out(patch):
    """``x + attn`` where ``x + norm(attn)`` belongs: ``ln1_post`` is
    skipped in every layer."""
    from dlrover_tpu.models import decoder

    norm, body = decoder._norm_block, decoder._layer_body
    skipped = []  # the ``ln1_post`` of every layer body traced so far

    def layer_body(x, layer, *args, **kwargs):
        skipped.append(layer["ln1_post"])
        return body(x, layer, *args, **kwargs)

    def norm_block(x, ln, cfg, residual=None):
        if any(ln is one for one in skipped):
            return x
        return norm(x, ln, cfg, residual=residual)

    patch(decoder, "_layer_body", layer_body)
    patch(decoder, "_norm_block", norm_block)


def embedding_scale_left_out(patch):
    """The token embeddings reach the first layer unscaled: the lookup
    hands back rows divided by sqrt(d), which the forward's own factor
    then cancels."""
    import jax.numpy as jnp

    from dlrover_tpu.models import decoder

    embed = decoder._embed_tokens

    def unscaled(params, tokens, mesh, dt):
        x = embed(params, tokens, mesh, jnp.float32)
        return (x / x.shape[-1] ** 0.5).astype(dt)

    patch(decoder, "_embed_tokens", unscaled)


LOGITS = ("logits_vs_reference", "logits_rms_vs_reference")
PLANT = {
    "gate_left_out": gate_left_out,
    "rope_on_full_layers": rope_on_full_layers,
    "window_twice_as_wide": window_twice_as_wide,
    "attention_output_norm_left_out": attention_output_norm_left_out,
    "embedding_scale_left_out": embedding_scale_left_out,
}
# defect -> the checks of which at least one has to read not ok
CAUGHT_BY = dict.fromkeys(PLANT, LOGITS)


if __name__ == "__main__":
    import os
    import sys

    sys.path[0] = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    from benchmarks import run

    PLANT[sys.argv[1]](setattr)
    sys.exit(run.main(sys.argv[2:]))
