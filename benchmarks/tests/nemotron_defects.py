"""Defects a program of Nemotron-3-Super's architecture can have, each
injected by patching the program from outside (``ops/ssd.py``,
``models/decoder.py``), in ``defects.py``'s manner: what the routed
comparison has to catch through the LOGITS, since the state-space layer
has no check of its own. ``test_nemotron_cell.py`` runs them at a tiny
size on the CPU, float32 on both sides.

Each ``inject(setattr)`` takes a ``setattr``-like callable
(``monkeypatch.setattr`` in a test).
"""


def bf16_decays(patch):
    """The cumulative log-decays of a chunk summed in bf16: what a scan
    written in the compute dtype does. A chunk's last ``cum`` is a few
    units, and bf16 keeps three digits of it."""
    import jax.numpy as jnp

    from dlrover_tpu.ops import ssd

    cumsum = jnp.cumsum

    class Rounded:
        """``jnp`` as ``ops/ssd.py`` sees it, but for ``cumsum``."""

        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def cumsum(x, axis=None):
            out = cumsum(x.astype(jnp.bfloat16), axis=axis)
            return out.astype(x.dtype)

    patch(ssd, "jnp", Rounded())


def gate_after_the_norm(patch):
    """``group_norm(y) ⊙ silu(z)`` for ``group_norm(y ⊙ silu(z))``."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops import ssd

    def norm_then_gate(y, z, scale, groups, eps):
        v = y.reshape(y.shape[:-1] + (groups, -1))
        v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
        return v.reshape(y.shape) * scale * jax.nn.silu(z)

    patch(ssd, "gated_group_norm", norm_then_gate)


def chunk_state_dropped(patch):
    """The state a chunk leaves is dropped at ONE boundary: the third
    chunk starts from nothing."""
    import jax.numpy as jnp

    from dlrover_tpu.ops import ssd

    scan = ssd.ssd_scan

    def cut_once(x, dt, a, b_mat, c_mat, chunk, head_block=0):
        at = 2 * chunk
        parts = [
            scan(*(t[:, sl] for t in (x, dt)), a,
                 *(t[:, sl] for t in (b_mat, c_mat)), chunk, head_block)
            for sl in (slice(0, at), slice(at, None))
        ]
        return jnp.concatenate(parts, axis=1)

    patch(ssd, "ssd_scan", cut_once)


def rope_on(patch):
    """Rotary embedding applied in the attention layer, which has none."""
    import dataclasses

    from dlrover_tpu.models import decoder

    qkv = decoder._project_qkv

    def with_rope(x, layer, cfg, positions, **kw):
        return qkv(
            x, layer, dataclasses.replace(cfg, pos="rope"), positions,
            **dict(kw, rope=None),
        )

    patch(decoder, "_project_qkv", with_rope)


LOGITS = ("logits_vs_reference", "logits_rms_vs_reference")
# defect -> the checks of which at least one has to read not ok
CAUGHT_BY = {
    "bf16_decays": LOGITS,
    "gate_after_the_norm": LOGITS,
    "chunk_state_dropped": LOGITS,
    "rope_on": LOGITS,
}
INJECT = {
    "bf16_decays": bf16_decays,
    "gate_after_the_norm": gate_after_the_norm,
    "chunk_state_dropped": chunk_state_dropped,
    "rope_on": rope_on,
}
