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
    judged by them as sound, and by ``indexer_loss_vs_reference`` too
    since PR 43 (2.0e-3..2.5e-3 on the chip: over ``ROUTER_LOSS_TOL``,
    where the term was held until then, under ``INDEXER_LOSS_TOL``
    3.3e-3)."""
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


# ---- defects of an attention that selects by BLOCKS ------------------------
# What the ``selected`` comparison has to catch of a selection made in
# units of ``select_block`` keys, ``select_groups`` selections a layer,
# with units the model's rule forces (``lib/selected.py``), each
# injected by patching the block stand-in (``tests/block_standin.py``:
# the program selects keys, one selection a layer). ``test_selected.py``
# runs them at a tiny size on the CPU; the chip rehearsal (PERF.md
# section 4, PR 56) ran the same patches at MiniCPM-SALA's sparse layer.

def recent_blocks(patch):
    """Beside the forced units the most recent free blocks, not the
    best: a wider local window under the selection's name."""
    import jax.numpy as jnp

    from benchmarks.tests import block_standin

    select = block_standin._select_units

    def recent(index, forced, k, qpos):
        unit = jnp.arange(index.shape[-1], dtype=index.dtype)
        nearest = jnp.where(jnp.isfinite(index), unit, -jnp.inf)
        return select(nearest, forced, k, qpos)

    patch(block_standin, "_select_units", recent)


def one_head_scores(patch):
    """A KV head's blocks scored by its first query head alone, not by
    the sum over its query heads."""
    from benchmarks.tests import block_standin

    patch(block_standin, "_head_sum", lambda p: p[:, :, 0])


def block_mean(patch):
    """A block scored by the mean of its pooled keys, not their max."""
    import jax.numpy as jnp

    from benchmarks.tests import block_standin

    patch(block_standin, "_block_reduce", lambda p: jnp.mean(p, axis=-1))


def pool_no_overlap(patch):
    """Pooled keys every ``pool_window`` keys (stride 32 for 16): every
    other pooled key is not there, and the windows do not overlap."""
    import jax.numpy as jnp

    from benchmarks.tests import block_standin

    def every_other(ended, sizes):
        step = sizes["pool_window"] // sizes["pool_stride"]
        return ended & (jnp.arange(ended.shape[-1]) % step == 0)[None, :]

    patch(block_standin, "_live_pooled", every_other)


def initial_dropped(patch):
    """The initial block left to its score like any other."""
    from benchmarks.tests import block_standin

    forced = block_standin._forced_units

    def no_initial(qpos, n_units, sizes):
        return forced(qpos, n_units, dict(sizes, select_init_blocks=0))

    patch(block_standin, "_forced_units", no_initial)


def local_dropped(patch):
    """The local window's blocks left to their scores, but for the
    query's own."""
    from benchmarks.tests import block_standin

    forced = block_standin._forced_units

    def no_local(qpos, n_units, sizes):
        return forced(
            qpos, n_units, dict(sizes, select_local=sizes["select_block"])
        )

    patch(block_standin, "_forced_units", no_local)


def _lowest_free(index, chosen, forced):
    """bool: each query's chosen free unit of lowest score."""
    import jax.numpy as jnp

    free = jnp.where(chosen & ~forced, index, jnp.inf)
    lowest = jnp.argmin(free, axis=-1)[..., None]
    return (jnp.arange(index.shape[-1]) == lowest) & jnp.isfinite(free)


def _some_queries(qpos):
    """One query in a hundred, [Q, 1]."""
    return (qpos % EVERY == 7)[:, None]


def short_row(patch):
    """One block too few on 1% of the rows that chose any."""
    from benchmarks.tests import block_standin

    select = block_standin._select_units

    def short(index, forced, k, qpos):
        chosen = select(index, forced, k, qpos)
        drop = _lowest_free(index, chosen, forced)
        return chosen & ~(drop & _some_queries(qpos))

    patch(block_standin, "_select_units", short)


def future_block(patch):
    """The block after the query's own in place of its lowest chosen
    one, on 1% of the rows that chose any."""
    import jax.numpy as jnp

    from benchmarks.tests import block_standin

    select = block_standin._select_units

    def ahead(index, forced, k, qpos):
        chosen = select(index, forced, k, qpos)
        drop = _lowest_free(index, chosen, forced)
        rows = _some_queries(qpos) & jnp.any(drop, -1, keepdims=True)
        # a query sees units 0 .. own: as many as its finite scores
        seen = jnp.sum(jnp.isfinite(index), -1, keepdims=True)
        nxt = jnp.arange(index.shape[-1]) == seen
        return (chosen & ~(drop & rows)) | (nxt & rows)

    patch(block_standin, "_select_units", ahead)


def group0_for_both(patch):
    """Every KV head's queries attend under KV head 0's selection, and
    that is what is handed over for each."""
    import jax.numpy as jnp

    from benchmarks.tests import block_standin

    patch(
        block_standin, "_group_selection",
        lambda chosen: jnp.broadcast_to(chosen[:, :1], chosen.shape),
    )


def blocks_ignored(patch):
    """A valid selection handed over while the attention runs over
    every visible key: the sparse attention not applied at all."""
    import jax.numpy as jnp

    from benchmarks.tests import block_standin

    patch(
        block_standin, "_attended",
        lambda keys, visible: jnp.broadcast_to(visible, keys.shape),
    )


def pool_8bit(patch):
    """The query and the pooled keys rounded to 8 bits (e4m3) before
    their product. Its verdict is RECORDED, pass or fail (PERF.md
    section 4, PR 56): it decides what a later ``perf_opt`` may do."""
    import jax.numpy as jnp

    from benchmarks.tests import block_standin

    def rounded(q, pooled):
        return tuple(
            a.astype(jnp.float8_e4m3fn).astype(a.dtype) for a in (q, pooled)
        )

    patch(block_standin, "_pool_inputs", rounded)


# defect -> the checks of which at least one has to read not ok; None:
# a defect whose verdict is recorded, not required
BLOCK_CAUGHT_BY = {
    "recent_blocks": ("selection_regret",),
    "one_head_scores": ("selection_regret", "selection_moved"),
    "block_mean": ("selection_regret", "selection_moved"),
    "pool_no_overlap": ("selection_regret", "selection_moved"),
    "initial_dropped": ("selection_forced",),
    "local_dropped": ("selection_forced",),
    "short_row": ("selection_valid",),
    "future_block": ("selection_valid",),
    "group0_for_both": ("selection_regret",),
    "blocks_ignored": ("logits_rms_vs_reference", "logits_vs_reference"),
    "pool_8bit": None,
}
BLOCK_INJECT = {
    "recent_blocks": recent_blocks, "one_head_scores": one_head_scores,
    "block_mean": block_mean, "pool_no_overlap": pool_no_overlap,
    "initial_dropped": initial_dropped, "local_dropped": local_dropped,
    "short_row": short_row, "future_block": future_block,
    "group0_for_both": group0_for_both, "blocks_ignored": blocks_ignored,
    "pool_8bit": pool_8bit,
}
