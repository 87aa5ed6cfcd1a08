"""The comparison for a configuration whose attention SELECTS its keys
(a learned sparse attention: an indexer scores every visible key and
the attention runs over the k best): ``"check": {"kind": "selected"}``
in the configuration file.

A hard top-k of keys is a second discontinuity beside the experts'
top-k (``lib/routed.py``), and a worse one: with k of thousands of
candidates every query has near-ties at the boundary, so bf16 rounding
of what feeds the indexer moves a few keys of EVERY query, and the
attention's output then differs by those keys' share. No tolerance on
free-running logits tells that from a wrong selection. So the logits
are compared under TEACHER FORCING: the reference attends to exactly
the keys the program selected and computes everything else itself, its
own index scores among it. With the selections equal the function is
continuous again and the logits are held to the dense tolerances
(``runners/train.py``), unchanged.

A selection is a choice among UNITS. A unit is a key (``select_block``
absent or 1: the indexer above) or a BLOCK of ``select_block`` = b
consecutive keys (a block-sparse attention: pooled keys score the
blocks, a query attends to the keys it may see inside the k blocks it
chose). A layer makes ``select_groups`` = G selections (absent: 1), one
per KV head; each is a ROW of what the program hands over, judged as a
selection of its own. Three keys of the configuration's ``sizes`` state
the contract: ``index_topk`` = k units a query, ``select_block``,
``select_groups``. With neither of the last two the arithmetic is what it was before
PR 56, to the digit.

The selection is not taken on trust:

- exact (``selection_valid``): query t sees units 0 .. t // b; row t of
  every selection holds min(t // b + 1, k) of them, none above (at
  b = 1: min(t + 1, k) keys, none above the diagonal). The causal mask
  INSIDE the query's own block is the attention's, not the
  selection's;
- exact (``selection_forced``, only where the reference names FORCED
  units — those the model's rule takes whatever their score: an initial
  block, a local window): no forced unit the query sees is missing
  from its row. Regret, gap and moved are then taken over the units
  outside the forced ones, with k less their number to choose;
- regret (``selection_regret``): for each layer and query, at the
  reference's OWN index scores I on its own hidden state,

      regret = max(0, I_(k') - min over the chosen s of I_s) / std(I)

  with k' = min(t + 1, k), I_(k') the reference's k'-th largest visible
  score and the deviation taken over the query's visible scores. A
  query that selects as the reference would has regret 0; a moved
  near-tie has the tie's width; keys that the indexer never ranked high
  have a wide one. The MAXIMUM over layers and queries is held to
  ``SELECT_REGRET_TOL``;
- moved (``selection_moved``): the share of a query's chosen keys that
  lie under the reference's k'-th score, the mean over a layer's
  queries that have a choice to make (t >= k), the largest layer held
  to ``SELECT_MOVED_TOL``. The regret is one key's distance; this is
  how many went.

Where the model also routes (``sizes["n_experts"]``), the experts are
teacher-forced and judged as ``routed`` does, in the same run, through
the same functions: a router that chooses groups before experts too.

From the program this takes ``decoder.forward(..., return_aux=True)``:
the logits, ``aux["attn_selected"]``, bool ``[L_a x G, B, S, S / b]``
(row t of a selection true at the units query t attended to; G rows per
attention layer that selects, layer-major and group-minor, the layers
in trunk order, an extra prediction module's last; with b = 1 and
G = 1 that is ``[L_a, B, S, S]``), and ``aux["moe_choices"]`` where it
routes. The masks and the expert ids are all that reaches the
reference, which expands units to keys itself.
"""

import numpy as np

from benchmarks.lib import routed
from benchmarks.lib.device import Refused
from benchmarks.lib.routed import program_losses  # noqa: F401  (the runner's)

# Both limits below and FREE_LOSS_TOL are PROVISIONAL: no program hands
# a selection over yet, so they stand on a stand-in of the block
# (``tests/sparse_standin.py``: the equations as a program would compute
# them, bf16 parameters and activations, the index product on bf16
# operands summed in float32, the top-k in float32) judged against
# ``tests/sparse_plain.py`` on the chip (``tests/rehearse_selected.py``)
# at Keye-VL-2.0's language widths: d 2048, 32 / 4 heads of 128, indexer
# 16 x 64 with one key head, top-2048, B 1, S 8192, seeded weights. The
# ``model_config`` PR that brings the first ``selected`` configuration
# brings the program's own readings over a dozen seeds; where they do
# not lie under the limits with room, a ``benchmark`` issue comes before
# the cell.
#
# THE RULE for the two limits, NOT tuned to a cell: at least twice the
# largest value any sound rehearsal seed shows, and each defect listed
# for the check (``tests/defects.py``) reads at least twice the limit.
# If no number satisfies both, the comparison is the wrong design: stop
# and say so.
#
# ONE PAIR OF LIMITS FOR BOTH KINDS OF UNIT (PR 56). A selection by
# BLOCKS was read the same way before any program makes one: the block
# stand-in (``tests/block_standin.py``) against ``tests/block_plain.py``
# on the chip (``tests/rehearse_selected.py --shape sala``) at
# MiniCPM-SALA's sparse layer: d 4096, 32 / 2 heads of 128, pooled keys
# 32 every 16, 64 blocks of 64 keys a KV head of which the first and the
# local window's 32 are forced, B 1, S 16384, seeded weights. Its sound
# readings lie under both limits with the rule's factor of two, and its
# listed defects (``tests/defects.BLOCK_CAUGHT_BY``) over twice each, so
# nothing is chosen by ``select_block``; the numbers stand beside each
# limit below. PROVISIONAL for blocks as they were for keys: the
# ``model_config`` PR that brings the first such cell brings the
# program's own readings over a dozen seeds.
#
# Largest regret of a sound run, in standard deviations of a query's
# visible index scores. The rehearsal (PR 36, my chip runs; PERF.md
# section 4): what sets it is the error of the bf16 hidden state that
# feeds the indexer, so it GROWS WITH DEPTH and then levels off. By
# layer, at 8 layers: 0.021, 0.075, 0.113, 0.116, 0.140, 0.144, 0.153,
# 0.158. A run's maximum: 0.019..0.035 at 1 layer (36 seeds),
# 0.109..0.145 at 4 (12 seeds), 0.139..0.231 at 8 (12 seeds; median
# 0.171), 0.195 and 0.211 at 12; an extreme value over 8192 queries a
# layer. The median gap between the k-th and the (k+1)-th score is
# 3.7e-4..4.0e-4 everywhere: every query has near-ties, and
# ``REGRET_TOL``'s second clause ("at most half the median gap") can
# never hold for a selection of thousands. The defects: the last k keys
# for the learned ones 6.5..7.1, every index head weighted alike
# 6.8..7.1, the ReLU dropped 2.7..3.0. So
# 2 x 0.231 = 0.46 <= SELECT_REGRET_TOL <= 2.7 / 2 = 1.35. An indexer
# whose query and key are rounded to 8 bits (e4m3) reads 0.23..0.34, NO
# MORE than bf16 does eight layers deep: this limit PASSES it, and so
# does the next. Like ``ROUTER_LOSS_TOL`` this is no check of precision;
# it is there for a selection that is not the indexer's.
# BY BLOCKS (PR 56, my chip runs; PERF.md section 4), in deviations of a
# query's FREE unit scores: a run's maximum 0.0217..0.0291 at 1 layer (12
# seeds), 0.1017..0.1307 at 4 (6 seeds); median gap 0.019 everywhere (31
# free places among at most 223, where keys have 2048 among 8192). The
# defects: the most recent free blocks 5.38, group 0's selection for both
# 4.66, one head's scores 4.71 (5.40 at 4 layers), the mean for the max
# 1.63 (1.64), pooled keys without overlap 1.89 (1.64). So
# 2 x 0.1307 = 0.26 <= SELECT_REGRET_TOL <= 1.63 / 2 = 0.81. The
# pooled-key product in 8 bits reads 0.267 (0.326 at 4 layers): passed.
SELECT_REGRET_TOL = 0.6
# Largest layer mean of the share of a query's chosen keys that lie
# under the reference's k-th best score, over the queries with t >= k.
# Sound, by layer at 8 layers: 0.18%, 0.57%, 0.71%, 0.83%, 0.89%, 0.95%,
# 1.02%, 1.06%; a run's largest 0.174..0.179% at 1 layer, 0.79..0.83% at
# 4, 0.99..1.08% at 8, 1.17% at 12, the same to three digits from seed
# to seed. The defects: ReLU dropped 18%, head weights dropped 52%, the
# last k keys 54%, the selection ignored by the attention (deeper
# layers then see another hidden state) 50%; the 8-bit indexer
# 1.6..1.8%, which passes. So 2.35% <= SELECT_MOVED_TOL <= 9.2%.
# BY BLOCKS (PR 56), a ROW's mean (a row a KV head's selection), of the
# free places: 0.265..0.294% at 1 layer, 1.067..1.135% at 4. The defects:
# pooled keys without overlap 30.5%, the mean for the max 31.6%, one
# head's scores 61%, group 0's selection for both and the most recent
# blocks 68%. So 2.27% <= SELECT_MOVED_TOL <= 15%. The 8-bit pooled-key
# product reads 2.59% (2.80% at 4 layers): it PASSES with 7 to 14% of
# room, and a sound 4-layer run's 1.1% beside it leaves fresh seeds
# little; what a ``perf_opt`` that rounds that product has to show first.
SELECT_MOVED_TOL = 0.03
# The program's mean cross-entropy against the FREE-RUNNING reference
# (its own selection, its own routing), relative. ``routed``'s limit is
# reused by ISSUE 36's rule: the rehearsal's free losses (1.0e-6..1.0e-4
# at 1 layer, up to 1.3e-4 at 4, 1.5e-4 at 8 and at 12) lie under half
# of it. The moved keys bring other values, which does not average out
# as rounding does: the forced loss reads 9e-8..2.5e-5 on the same runs.
# By blocks (PR 56): free loss 1.7e-6..5.7e-5 at 1 layer, up to 7.5e-5
# at 4; forced 9.6e-8..1.2e-5.
FREE_LOSS_TOL = routed.FREE_LOSS_TOL
# The indexer's alignment loss (the step metric ``indexer_loss``) against
# the teacher-forced reference's, relative. Its own limit since PR 43, by
# the rule above: twice the largest reading of a sound run. The program's
# own readings on the Keye cell, 12 layers, every other check ok (PERF.md
# sections 4 and 7): 1.8e-5..1.22e-3 over the 29 runs of PRs 37 and 38,
# 4.0e-5..1.65e-3 over the 18 of PR 40, signed and centred on zero with
# a deviation of 6.5e-4. It arises in the bf16 residual stream that feeds
# the indexer, not in the indexer. At ``ROUTER_LOSS_TOL`` (2e-3, three
# deviations) one sound run in a few hundred failed. The control, the
# term 1% off (``tests/defects.indexer_loss_off``), reads 9.9e-3: three
# times this limit. ``ROUTER_LOSS_TOL`` stays for every other term.
INDEXER_LOSS_TOL = 3.3e-3


def units(sizes):
    """(k, b, G) of a configuration's ``sizes``: the units a query
    selects, the keys in a unit, the selections a layer makes."""
    return (
        sizes["index_topk"], sizes.get("select_block", 1),
        sizes.get("select_groups", 1),
    )


def program_logits_and_choices(params, tokens, cfg, sizes=None):
    """The program's forward on ``tokens``: (logits, choices) with
    ``choices["attn_selected"]`` bool [L_a x G, B, S, S / b] (b and G
    from ``sizes``; without it keys, one selection a layer) and, where
    the model routes, ``choices["moe_choices"]`` int32 [L, B, S, k]. A
    program that does not hand them over, or hands the selection over
    in another shape, cannot be judged by this comparison, and is
    refused before anything compiles."""
    import jax

    from dlrover_tpu.models import decoder

    def forward(params, tokens):
        return decoder.forward(params, tokens, cfg, return_aux=True)

    aux = jax.eval_shape(forward, params, tokens)[1]
    need = ["attn_selected"]
    if getattr(cfg, "n_experts", 0):
        need.append("moe_choices")
    missing = [name for name in need if name not in aux]
    if missing:
        raise Refused(
            "check.kind 'selected' needs the keys each query attended to: "
            "decoder.forward(..., return_aux=True)[1]['attn_selected'], bool "
            "[selecting layers, B, S, S] (and ['moe_choices'] where the "
            f"model routes); this program returns only {sorted(aux)}, not "
            f"{missing}"
        )
    block, groups = units(sizes)[1:] if sizes else (1, 1)
    got = aux["attn_selected"]
    fault = _shape_fault(got.dtype, got.shape, tokens.shape, block, groups)
    if fault:
        raise Refused(
            f"check.kind 'selected' with select_block {block} and "
            f"select_groups {groups} needs aux['attn_selected'] as {fault}"
        )
    logits, aux = jax.jit(forward)(params, tokens)
    return logits, {name: aux[name] for name in need}


def _shape_fault(dtype, shape, tokens_shape, block, groups):
    """None where ``attn_selected`` of this dtype and shape is G rows a
    selecting layer over units of ``block`` keys for tokens [B, S]; else
    the shape it should have had beside the one it has."""
    b, s = tokens_shape
    if s % block:
        return (
            f"rows over units of {block} keys, and the sequence, {s} "
            "tokens, is no whole number of them"
        )
    ok = (
        dtype == np.bool_ and len(shape) == 4 and shape[0] > 0
        and shape[0] % groups == 0 and tuple(shape[1:]) == (b, s, s // block)
    )
    if ok:
        return None
    return (
        f"bool [selecting layers x {groups}, {b}, {s}, {s // block}] "
        f"(layer-major, group-minor; a unit is {block} "
        f"key{'s' if block > 1 else ''}); this program hands over "
        f"{dtype} {list(shape)}"
    )


def selection_faults(selected, k, block=1):
    """Number of (selection, query) rows that are no selection: not
    min(t // block + 1, k) units, or a unit above the query's own.
    Exact. With ``block`` 1: min(t + 1, k) keys, none above the
    diagonal."""
    import jax
    import jax.numpy as jnp

    if selected.dtype != np.bool_ or selected.ndim != 4 or (
        selected.shape[-1] * block != selected.shape[-2]
    ):
        raise ValueError(
            f"attn_selected is bool [selections, B, S, S / {block}]; got "
            f"{selected.dtype} {selected.shape}"
        )

    @jax.jit
    def faults(selected):
        s, n_units = selected.shape[-2:]
        own = jnp.arange(s)[:, None] // block  # the query's own unit
        future = jnp.arange(n_units)[None, :] > own
        count = jnp.sum(selected, axis=-1, dtype=jnp.int32)
        want = jnp.minimum(own[:, 0] + 1, k)
        bad = (count != want) | jnp.any(selected & future, axis=-1)
        return jnp.sum(bad, dtype=jnp.int32)

    return int(faults(selected))


def selection_stats(scores, chosen, k, forced=None):
    """What a selection costs at the reference's scores, for one block
    of queries.

    scores: float32 [..., Q, U], the reference's own scores of Q queries
    against all U units (keys, or blocks of keys), ``-inf`` at the units
    a query may not see (above its own, outside a window); chosen: bool
    [..., Q, U], the units the program attended to; k: the selection's
    size; forced: None, or bool [..., Q, U], the units the model's rule
    takes whatever their score. Per query (arrays [..., Q]), with the
    FREE units those it sees outside the forced ones, and
    k' = min(free units, max(k - forced units it sees, 0)) (without
    ``forced``: min(visible units, k)):

    - ``regret``: max(0, I_(k') - min over the chosen free units of I)
      over the standard deviation of the query's free scores;
    - ``gap``: I_(k') - I_(k'+1) in the same unit, NaN for a query with
      no more than k' free units (it has no choice to make);
    - ``moved``: the share of the k' that were chosen with a score under
      I_(k') (a unit that ties with the k'-th counts as among the best);
    - ``forced_missing`` (only with ``forced``): the forced units the
      query sees that are not chosen.
    """
    import jax.numpy as jnp

    visible = jnp.isfinite(scores)
    out = {}
    if forced is not None:
        forced = forced & visible
        out["forced_missing"] = jnp.sum(forced & ~chosen, axis=-1)
        k = jnp.maximum(k - jnp.sum(forced, axis=-1), 0)
        # from here on the forced units are units the query does not see
        scores = jnp.where(forced, -jnp.inf, scores)
        chosen, visible = chosen & ~forced, visible & ~forced
    n_visible = jnp.sum(visible, axis=-1)
    size = jnp.minimum(n_visible, k)  # k'
    ranked = jnp.sort(scores, axis=-1, descending=True)

    def at(index):
        index = jnp.clip(index, 0, scores.shape[-1] - 1)
        return jnp.take_along_axis(ranked, index[..., None], axis=-1)[..., 0]

    kth, after = at(size - 1), at(size)
    mean = jnp.sum(jnp.where(visible, scores, 0.0), -1) / n_visible
    dev = jnp.where(visible, scores - mean[..., None], 0.0)
    std = jnp.sqrt(jnp.sum(dev * dev, -1) / n_visible)
    worst = jnp.min(jnp.where(chosen, scores, jnp.inf), axis=-1)
    # a query with nothing left to choose (k' = 0: every unit it sees is
    # forced, or the forced ones fill the selection) costs nothing
    regret = jnp.where(
        (std > 0) & (size > 0), jnp.maximum(kth - worst, 0.0) / std, 0.0
    )
    gap = jnp.where(
        (size < n_visible) & (size > 0), (kth - after) / std, jnp.nan
    )
    under = chosen & (scores < kth[..., None]) & (size > 0)[..., None]
    out.update(
        regret=regret, gap=gap,
        moved=jnp.sum(under, axis=-1) / jnp.maximum(size, 1),
    )
    return out


def selection_summary(stats):
    """``selection_stats`` stacked [rows, B, S] (a row a selection: G a
    selecting layer, layer-major and group-minor), reduced to what is
    judged and recorded. A row's ``moved`` is the mean over its queries
    that have a choice to make (a finite ``gap``); 0 where none has. The
    ``*_by_layer`` entries are one a ROW: the name dates from one
    selection a layer."""
    import jax.numpy as jnp

    regret, gap, moved = stats["regret"], stats["gap"], stats["moved"]
    chooses = jnp.isfinite(gap)
    moved_by_layer = jnp.sum(jnp.where(chooses, moved, 0.0), axis=(1, 2)) / (
        jnp.maximum(jnp.sum(chooses, axis=(1, 2)), 1)
    )
    out = {
        "select_regret_max": jnp.max(regret),
        "select_regret_max_by_layer": jnp.max(regret, axis=(1, 2)),
        # the median over the queries whose selection moved; 0 where none
        "select_regret_median_moved": jnp.nan_to_num(
            jnp.nanmedian(jnp.where(regret > 0, regret, jnp.nan))
        ),
        "select_gap_median": jnp.nan_to_num(jnp.nanmedian(gap)),
        "select_moved_max": jnp.max(moved_by_layer),
        "select_moved_by_layer": moved_by_layer,
    }
    if "forced_missing" in stats:  # the reference named forced units
        out["select_forced_missing"] = jnp.sum(stats["forced_missing"])
    return out


def compare(reference, params, batch, sizes, q_block, logits, choices,
            program, tolerances):
    """The teacher-forced comparison on one share of the batch.

    ``choices`` is what ``program_logits_and_choices`` handed over;
    ``program`` is ``program_losses`` of the same share.
    ``reference.loss_and_logits_selected(params, batch, sizes, q_block,
    choices)`` returns ``(mean cross-entropy, logits, forced)``:
    ``forced["selection"]`` is ``selection_stats`` of every (row, q
    block) stacked to [L_a x G, B, S] (with ``forced_missing`` where the
    reference passed the model's forced units), ``forced["router_logits"]`` is
    float32 [L, B, S, E] where the model routes (and
    ``forced["group_scores"]`` where its router chooses groups first:
    ``routed.compare``), and every other entry is a scalar term of the
    objective under the name of the program's step metric, coefficient
    included; ``indexer_loss`` is held to ``INDEXER_LOSS_TOL``.

    Returns (results, record): ``results`` as ``(name, ok, value,
    limit)`` for the checks, ``record`` for the ``BENCH reference`` line."""
    import jax

    routes = "moe_choices" in choices
    topk, block, groups = units(sizes)
    fault = _shape_fault(
        choices["attn_selected"].dtype, choices["attn_selected"].shape,
        batch["tokens"].shape, block, groups,
    )
    if fault:
        raise ValueError("attn_selected is " + fault)
    faults = selection_faults(choices["attn_selected"], topk, block)
    results = [("selection_valid", faults == 0, faults, 0)]
    record = {"selection_faults": faults}
    if routes:
        ids = routed.choice_faults(choices["moe_choices"], sizes["n_experts"])
        results.append(("choices_valid", ids == 0, ids, 0))
        record["choice_faults"] = ids
    if any(not ok for _name, ok, _value, _limit in results):
        # what names no selection cannot be forced on the reference
        return results, record

    @jax.jit
    def against_forced(params, batch, logits, choices):
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_logits, forced = reference.loss_and_logits_selected(
                params, batch, sizes, q_block, choices
            )
        logit_err, logit_rms = routed.logit_errors(logits, ref_logits)
        got = {
            "ref_loss": ref_loss, "logit_err": logit_err,
            "logit_rms": logit_rms,
            **selection_summary(forced.pop("selection")),
        }
        if routes:
            got.update(
                routed.forced_routing(forced, choices["moe_choices"], sizes)
            )
        return dict(got, objective_terms=forced)

    got = jax.tree.map(
        np.asarray, against_forced(params, batch, logits, choices)
    )
    regret, moved = (
        float(got["select_regret_max"]), float(got["select_moved_max"])
    )
    if "select_forced_missing" in got:
        missing = int(got["select_forced_missing"])
        results.append(("selection_forced", missing == 0, missing, 0))
        record["select_forced_missing"] = missing
    results += [
        ("selection_regret", regret <= SELECT_REGRET_TOL, regret,
         SELECT_REGRET_TOL),
        ("selection_moved", moved <= SELECT_MOVED_TOL, moved,
         SELECT_MOVED_TOL),
    ]
    if routes:
        results += routed.routing_checks(got, choices["moe_choices"], sizes)
    dense, loss_err = routed.forced_checks(got, program, tolerances)
    results += dense + routed.objective_checks(
        reference, got["objective_terms"], program, tolerances[2],
        {"indexer_loss": INDEXER_LOSS_TOL},
    )
    record.update(
        forced_ref_loss=float(got["ref_loss"]),
        forced_loss_err=loss_err,
        forced_logit_err=float(got["logit_err"]),
        forced_logit_rms=float(got["logit_rms"]),
        select_regret_max=regret,
        select_regret_max_by_layer=got["select_regret_max_by_layer"].tolist(),
        select_regret_median_moved=float(got["select_regret_median_moved"]),
        select_gap_median=float(got["select_gap_median"]),
        select_moved_by_layer=got["select_moved_by_layer"].tolist(),
        select_regret_tol=SELECT_REGRET_TOL,
        select_moved_tol=SELECT_MOVED_TOL,
        # what a ``*_by_layer`` entry is, where it is not a layer
        **({} if (block, groups) == (1, 1) else {"select_rows": {
            "block": block, "groups": groups,
            "order": "layer-major, group-minor",
        }}),
        **(routed.routing_record(got) if routes else {}),
        reference_terms={
            k: float(v) for k, v in got["objective_terms"].items()
        },
    )
    return results, record
