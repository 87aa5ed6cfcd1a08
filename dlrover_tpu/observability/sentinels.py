"""In-graph health sentinels: numeric-health scalars computed INSIDE the
jitted train step.

Reference pattern: MegaScale/xpu_timer-style always-on health gauges —
cheap signals every step, expensive captures only when something trips
(SURVEY §L6/L7).  The sentinels here are a handful of scalar reductions
over tensors the step already materializes (grads, updates, params, the
fp8 amax histories), appended to the step's metrics dict so they ride
the EXISTING async metrics drain: zero extra device-to-host transfers,
zero extra dispatches (pinned by the dispatch guard in
tests/test_sentinels.py).

Keys (all float32 scalars in the step's metrics dict):

* ``sent_nonfinite``      — count of non-finite gradient entries.
* ``sent_ovf_f16``        — fraction of finite grad entries that would
                            overflow float16 (|g| > 65504).
* ``sent_und_f16``        — fraction of finite NONZERO grad entries
                            below float16's min normal (6.1e-5).
* ``sent_ovf_bf16``       — same vs bfloat16's max finite (~3.39e38).
* ``sent_und_bf16``       — same vs bfloat16's min normal (~1.18e-38).
* ``sent_update_ratio``   — ‖update‖₂ / ‖params‖₂ (the effective
                            relative step size; spikes mean the
                            optimizer is about to punch the weights).
* ``sent_loss_nonfinite`` — 1.0 when the step loss is NaN/Inf.
* ``sent_fp8_sat``        — fraction of fp8 delayed-scaling amax
                            histories whose NEWEST entry exceeds the
                            whole window the scale was derived from
                            (the step clipped against a stale scale);
                            only present when ``cfg.fp8`` is active.
* ``sent_sanitizer_skips``— cumulative skipped/zeroed-update count from
                            ``numeric.sanitize_grads`` when the
                            optimizer chain carries one.

Parity contract (pinned in tests/test_sentinels.py): the counts and
fractions are IDENTICAL between the replicated step and the zero1/zero2
sharded steps.  Counts are exact small integers summed in f32 (exact
below 2**24 per partial sum); fraction denominators are STATIC Python
ints (total param count), so the zero padding in the ZeRO flat stream —
finite, excluded from the underflow test by the ``g != 0`` condition —
cannot skew them.  Norm-based sentinels (``sent_update_ratio``,
``grad_norm``) reduce in a different order on the flat stream and are
tolerance-pinned instead.

Cost: each sentinel is one fused elementwise map + reduction over data
the step already touches, so XLA can fold them into existing HBM passes.
What they add to a step on the chip is not measured: no benchmark cell
turns them on.
"""

from typing import Dict, Optional

import jax
import jax.numpy as jnp

# dtype range thresholds the overflow/underflow fractions test against.
F16_MAX = 65504.0
F16_TINY = 6.103515625e-05     # float16 min normal
BF16_MAX = 3.3895313892515355e38
BF16_TINY = 1.1754943508222875e-38  # bfloat16 min normal (== f32 tiny)

# order of the count lanes grad_counts packs (stable across the packed
# psum in the sharded region and the metrics unpack)
COUNT_KEYS = (
    "sent_nonfinite",
    "sent_ovf_f16",
    "sent_und_f16",
    "sent_ovf_bf16",
    "sent_und_bf16",
)


def _leaf_counts(g) -> jnp.ndarray:
    """[5] f32 count vector for one gradient array (lanes: COUNT_KEYS).

    Exact zeros are excluded from the underflow lanes so the ZeRO flat
    stream's bucket padding (zeros) counts identically to the unpadded
    per-leaf tree.
    """
    g = g.astype(jnp.float32)
    ag = jnp.abs(g)
    finite = jnp.isfinite(g)
    nonzero = g != 0.0

    def cnt(mask):
        return jnp.sum(mask.astype(jnp.float32))

    return jnp.stack(
        [
            cnt(~finite),
            cnt(finite & (ag > F16_MAX)),
            cnt(finite & nonzero & (ag < F16_TINY)),
            cnt(finite & (ag > BF16_MAX)),
            cnt(finite & nonzero & (ag < BF16_TINY)),
        ]
    )


def grad_counts(grads) -> jnp.ndarray:
    """[5] f32 counts over a gradient pytree (or a single flat array)."""
    leaves = jax.tree.leaves(grads)
    total = _leaf_counts(leaves[0])
    for leaf in leaves[1:]:
        total = total + _leaf_counts(leaf)
    return total


def static_size(tree) -> int:
    """Total element count of a pytree — a Python int, usable as the
    static fraction denominator on every sharding path."""
    return int(sum(int(x.size) for x in jax.tree.leaves(tree)))


def counts_to_metrics(counts, denom: int) -> Dict[str, jnp.ndarray]:
    """Unpack a [5] count vector into the sentinel metrics dict.

    ``sent_nonfinite`` stays a raw count (any non-zero value is already
    an incident); the range lanes become fractions of ``denom`` — the
    STATIC total param count, identical on replicated and sharded paths.
    """
    inv = jnp.float32(1.0 / max(int(denom), 1))
    out = {"sent_nonfinite": counts[0]}
    for i, key in enumerate(COUNT_KEYS[1:], start=1):
        out[key] = counts[i] * inv
    return out


def update_ratio(updates, params) -> jnp.ndarray:
    """‖updates‖₂ / ‖params‖₂ with a zero-safe denominator."""
    import optax

    un = optax.global_norm(updates)
    pn = optax.global_norm(params)
    return un / jnp.maximum(pn, jnp.float32(1e-12))


def loss_nonfinite(loss) -> jnp.ndarray:
    return (~jnp.isfinite(loss)).astype(jnp.float32)


def fp8_saturation(fp8_state) -> jnp.ndarray:
    """Fraction of amax histories where this step's amax (the freshly
    pushed newest slot, ``h[..., -1]``) exceeds the max of the window
    the quantization scale was derived from (``h[..., :-1]``).

    Self-contained on the step's OUTPUT fp8 state, which is bitwise
    identical across the replicated and sharded paths (pinned in
    tests/test_fp8_sharded.py), so the sentinel inherits that parity.
    """
    import numpy as np

    leaves = jax.tree.leaves(fp8_state)
    n_hist = sum(int(np.prod(l.shape[:-1])) for l in leaves) or 1
    sat = jnp.float32(0.0)
    for h in leaves:
        newest = h[..., -1]
        window = jnp.max(h[..., :-1], axis=-1)
        sat = sat + jnp.sum((newest > window).astype(jnp.float32))
    return sat / jnp.float32(n_hist)


def sanitizer_count(opt_state) -> Optional[jnp.ndarray]:
    """The cumulative skipped/zeroed-update counter from
    ``numeric.sanitize_grads``'s state inside an optimizer-state tree,
    or None when the chain carries no sanitizer."""
    from dlrover_tpu.observability.numeric import _SanitizerState

    nodes = jax.tree.leaves(
        opt_state, is_leaf=lambda x: isinstance(x, _SanitizerState)
    )
    found = [
        n.nonfinite_count for n in nodes if isinstance(n, _SanitizerState)
    ]
    if not found:
        return None
    total = found[0]
    for c in found[1:]:
        total = total + c
    return total.astype(jnp.float32)
