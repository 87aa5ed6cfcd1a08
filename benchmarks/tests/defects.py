"""Defects a routed program can have, each injected by patching the
program from outside: what the routed comparison (``lib/routed.py``)
has to catch, and the check that has to catch it. ``test_rehearsal.py``
runs them at a tiny size on the CPU; the chip rehearsal (PERF.md
section 6, PR 28) ran the same patches at Mixtral's widths.

Each ``inject(setattr)`` takes a ``setattr``-like callable
(``monkeypatch.setattr`` in a test) and patches ``parallel/moe.py``, or
for the objective's terms ``models/decoder.py``'s loss head.
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


def _step_metrics(patch, change):
    """Pass the program's step metrics (``decoder._loss_from_head``'s
    second return) through ``change``."""
    from dlrover_tpu.models import decoder

    head = decoder._loss_from_head

    def changed(*args, **kwargs):
        loss, metrics = head(*args, **kwargs)
        return loss, change(dict(metrics))

    patch(decoder, "_loss_from_head", changed)


def term_dropped(patch):
    """A term of the objective that the reference carries, the router
    z-loss, gone from the program's step metrics. Until PR 33 the
    comparison skipped a term the program did not report."""
    _step_metrics(
        patch,
        lambda m: {k: v for k, v in m.items() if k != "moe_z_loss"},
    )


def extra_prediction(patch, factor=1.0, cross_entropy=True):
    """A stand-in for an extra prediction module's term, on both sides.
    The tests' reference carries ``mtp_loss``, its teacher-forced mean
    cross-entropy, and lists it in ``CROSS_ENTROPY_TERMS`` where
    ``cross_entropy``; the program reports ``factor`` x its own
    cross-entropy under that name, or nothing where ``factor`` is None.
    Sound at 1.0."""
    from benchmarks.tests import moe_plain

    forced = moe_plain.loss_and_logits_routed

    def with_term(params, batch, sizes, q_block, choices):
        loss, logits, routed = forced(params, batch, sizes, q_block, choices)
        return loss, logits, dict(routed, mtp_loss=loss)

    patch(moe_plain, "loss_and_logits_routed", with_term)
    patch(
        moe_plain, "CROSS_ENTROPY_TERMS",
        ("mtp_loss",) if cross_entropy else (), raising=False,
    )
    if factor is not None:
        _step_metrics(patch, lambda m: dict(m, mtp_loss=factor * m["loss"]))


def ce_term_off(patch):
    """The extra module's cross-entropy 0.1% too large, in the program
    only: what a target shifted on a few rows or a weight a little off
    looks like. ``LOSS_TOL`` (2e-4) fails it; at ``ROUTER_LOSS_TOL``
    (2e-3), where an unlisted term is held, it passes. ISSUE 33 wrote
    "1% off", which fails at both limits and shows nothing."""
    extra_prediction(patch, factor=1.001)


# defect -> the checks of which at least one has to read not ok
CAUGHT_BY = {
    "kplus1": ("routing_regret",),
    "raw_weights": (
        "logits_vs_reference", "logits_rms_vs_reference", "loss_vs_reference",
    ),
    "w_down_scaled": ("logits_vs_reference", "logits_rms_vs_reference"),
    "router_8bit": ("routing_regret",),
    "lb_off_1pct": ("moe_lb_loss_vs_reference",),
    "term_dropped": ("moe_z_loss_vs_reference",),
    "ce_term_off": ("mtp_loss_vs_reference",),
}
INJECT = {
    "kplus1": kplus1, "raw_weights": raw_weights,
    "w_down_scaled": w_down_scaled, "router_8bit": router_8bit,
    "lb_off_1pct": lb_off_1pct, "term_dropped": term_dropped,
    "ce_term_off": ce_term_off,
}


# ---- defects of a selecting attention --------------------------------------
# What the ``selected`` comparison (``lib/selected.py``) has to catch,
# each injected by patching the stand-in (``tests/sparse_standin.py``:
# the program has no indexer to patch). ``test_selected.py`` runs them
# at a tiny size on the CPU; the chip rehearsal (PERF.md section 4, PR 36)
# ran the same patches at Keye-VL-2.0's language widths.

def _some_rows(qpos, k, last):
    """One query in a hundred, among those that have a choice to make."""
    return (qpos % EVERY == 7) & (qpos >= k) & (qpos < last)


def _lowest_chosen(index, chosen, n):
    """bool: the ``n`` chosen keys of lowest index score, per query."""
    import jax.numpy as jnp

    order = jnp.argsort(jnp.where(chosen, index, jnp.inf), axis=-1)
    return jnp.argsort(order, axis=-1) < n


def recent_for_topk(patch):
    """The last k keys instead of the learned ones: a sliding window
    under the selection's name."""
    import jax.numpy as jnp

    from benchmarks.tests import sparse_standin

    def select(index, k, qpos):
        kpos = jnp.arange(index.shape[-1])[None, :]
        recent = (kpos <= qpos) & (kpos > qpos - k)
        return jnp.broadcast_to(recent[None], index.shape)

    patch(sparse_standin, "_select", select)


def index_8bit(patch):
    """The indexer's query and key rounded to 8 bits (e4m3) before the
    score product, as DeepSeek-V3.2 runs its indexer. The comparison
    PASSES it, on the chip (``selection_regret`` 0.23..0.34 against 0.5,
    ``selection_moved`` 1.6..1.8% against 3%, where the sound bf16
    stand-in reads 0.020..0.027 and 0.18% at 1 layer but 0.158 and 1.06%
    at 8: an 8-bit indexer costs what a few more layers of bf16 rounding
    cost) as at the tiny size. The two selection checks are no check of
    precision; a later ``perf_opt`` that moves the indexer to 8 bits is
    judged by them as sound, and by ``indexer_loss_vs_reference`` only
    just (2.0e-3..2.5e-3 against 2e-3 on the chip)."""
    import jax.numpy as jnp

    from benchmarks.tests import sparse_standin

    def rounded(qi, ki):
        return tuple(
            a.astype(jnp.float8_e4m3fn).astype(a.dtype) for a in (qi, ki)
        )

    patch(sparse_standin, "_index_inputs", rounded)


def relu_dropped(patch):
    """The index score without its ReLU."""
    from benchmarks.tests import sparse_standin

    patch(sparse_standin, "_index_act", lambda dots: dots)


def head_weights_dropped(patch):
    """Every index head weighted alike (w = 1)."""
    import jax.numpy as jnp

    from benchmarks.tests import sparse_standin

    patch(sparse_standin, "_head_weights", jnp.ones_like)


def selection_short(patch):
    """64 keys too few (a quarter of the selection where that is
    smaller) on 1% of the rows."""
    from benchmarks.tests import sparse_standin

    select = sparse_standin._select

    def short(index, k, qpos):
        chosen = select(index, k, qpos)
        drop = _lowest_chosen(index, chosen, max(1, min(64, k // 4)))
        rows = _some_rows(qpos, k, index.shape[-1])
        return chosen & ~(drop & rows[None])

    patch(sparse_standin, "_select", short)


def future_key(patch):
    """The key after the query's own in place of the lowest chosen one,
    on 1% of the rows."""
    import jax.numpy as jnp

    from benchmarks.tests import sparse_standin

    select = sparse_standin._select

    def ahead(index, k, qpos):
        chosen = select(index, k, qpos)
        rows = _some_rows(qpos, k, index.shape[-1] - 1)[None]
        nxt = jnp.arange(index.shape[-1])[None, :] == qpos + 1
        return (chosen & ~(_lowest_chosen(index, chosen, 1) & rows)) | (
            nxt[None] & rows
        )

    patch(sparse_standin, "_select", ahead)


def selection_ignored(patch):
    """A valid selection handed over while the attention runs over
    every visible key: the sparse attention not applied at all."""
    import jax.numpy as jnp

    from benchmarks.tests import sparse_standin

    patch(
        sparse_standin, "_attended",
        lambda chosen, visible: jnp.broadcast_to(visible, chosen.shape),
    )


def indexer_loss_off(patch):
    """The indexer's alignment loss 1% too large, in the program only."""
    from benchmarks.tests import sparse_standin

    patch(sparse_standin, "_reported_indexer_loss", lambda v: 1.01 * v)


# defect -> the checks of which at least one has to read not ok; none:
# a defect the comparison passes, written down as passing
SELECTED_CAUGHT_BY = {
    "recent_for_topk": ("selection_regret",),
    "index_8bit": (),
    "relu_dropped": ("selection_regret",),
    "head_weights_dropped": ("selection_regret",),
    "selection_short": ("selection_valid",),
    "future_key": ("selection_valid",),
    "selection_ignored": ("logits_rms_vs_reference",),
    "indexer_loss_off": ("indexer_loss_vs_reference",),
}
SELECTED_INJECT = {
    "recent_for_topk": recent_for_topk, "index_8bit": index_8bit,
    "relu_dropped": relu_dropped,
    "head_weights_dropped": head_weights_dropped,
    "selection_short": selection_short, "future_key": future_key,
    "selection_ignored": selection_ignored,
    "indexer_loss_off": indexer_loss_off,
}
