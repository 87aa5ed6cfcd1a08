"""Defects a program of Jamba2-3B's architecture can have, each injected
by patching the program from outside (``models/decoder.py``,
``ops/selective_scan.py``, ``ops/ssd.py``), in ``nemotron_defects.py``'s
manner: what the ``dense`` comparison has to catch through the logits
and the loss, since the selective-scan layer has no check of its own.
``test_jamba_cell.py`` runs them at a tiny size on the CPU, float32 on
both sides.

Each ``inject(setattr)`` takes a ``setattr``-like callable
(``monkeypatch.setattr`` in a test).
"""


def inner_norm_dropped(patch):
    """B goes into the scan as projected: its RMSNorm (``b_norm``) is
    left out, the other two stay."""
    from dlrover_tpu.models import decoder

    norm, block = decoder._norm, decoder._mamba1_block

    def norm_or_not(x, scale, *rest):
        return x if scale is None else norm(x, scale, *rest)

    def without(h, ssm, cfg, mesh):
        return block(h, dict(ssm, b_norm={"scale": None}), cfg, mesh)

    patch(decoder, "_norm", norm_or_not)
    patch(decoder, "_mamba1_block", without)


def u_and_z_swapped(patch):
    """``[z | u] = h W_in``: the gate's half feeds the conv and the
    scan, the other the gate."""
    import jax.numpy as jnp

    from dlrover_tpu.models import decoder

    block = decoder._mamba1_block

    def swapped(h, ssm, cfg, mesh):
        w = ssm["w_in"]
        half = w.shape[-1] // 2
        flipped = jnp.concatenate([w[..., half:], w[..., :half]], axis=-1)
        return block(h, dict(ssm, w_in=flipped), cfg, mesh)

    patch(decoder, "_mamba1_block", swapped)


def bf16_decays(patch):
    """Every token's decay ``exp(Δ A)`` rounded to bf16 before it
    multiplies the state: what a scan written in the compute dtype
    does. A decay near 1 keeps three digits, and multiplies thousands
    of times."""
    import jax.numpy as jnp

    from dlrover_tpu.ops import selective_scan

    class Rounded:
        """``jnp`` as ``ops/selective_scan.py`` sees it, but for ``exp``."""

        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def exp(x):
            return jnp.exp(x).astype(jnp.bfloat16).astype(x.dtype)

    patch(selective_scan, "jnp", Rounded())


def skip_left_out(patch):
    """``y = scan(...)`` without ``+ D u``."""
    import jax.numpy as jnp

    from dlrover_tpu.models import decoder

    block = decoder._mamba1_block

    def without(h, ssm, cfg, mesh):
        return block(
            h, dict(ssm, d_skip=jnp.zeros_like(ssm["d_skip"])), cfg, mesh
        )

    patch(decoder, "_mamba1_block", without)


def conv_bias_left_out(patch):
    """``silu(conv(u))`` for ``silu(conv(u) + b)``."""
    import jax.numpy as jnp

    from dlrover_tpu.ops import ssd

    conv = ssd.causal_conv

    def without(x, weight, bias):
        return conv(x, weight, jnp.zeros_like(bias))

    patch(ssd, "causal_conv", without)


def one_decay_a_channel(patch):
    """``A[c, n] = A[c, 0]``: a decay a channel, shared by its states —
    Mamba-2's form, which has a matmul form, run on Mamba-1's
    weights."""
    import jax.numpy as jnp

    from dlrover_tpu.ops import selective_scan

    scan = selective_scan.selective_scan

    def shared(u, delta, a, b, c, **kw):
        return scan(u, delta, jnp.broadcast_to(a[:, :1], a.shape), b, c, **kw)

    patch(selective_scan, "selective_scan", shared)


LOGITS = (
    "logits_vs_reference", "logits_rms_vs_reference", "loss_vs_reference",
)
# defect -> the checks of which at least one has to read not ok
CAUGHT_BY = {
    "inner_norm_dropped": LOGITS,
    "u_and_z_swapped": LOGITS,
    "bf16_decays": LOGITS,
    "skip_left_out": LOGITS,
    "conv_bias_left_out": LOGITS,
    "one_decay_a_channel": LOGITS,
}
INJECT = {
    "inner_norm_dropped": inner_norm_dropped,
    "u_and_z_swapped": u_and_z_swapped,
    "bf16_decays": bf16_decays,
    "skip_left_out": skip_left_out,
    "conv_bias_left_out": conv_bias_left_out,
    "one_decay_a_channel": one_decay_a_channel,
}
