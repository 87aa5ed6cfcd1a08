"""The comparison for a routed (mixture-of-experts) configuration:
``"check": {"kind": "routed"}`` in the configuration file.

Hard top-k routing is discontinuous. Where a token's k-th and (k+1)-th
router logits lie closer than the rounding of what feeds the router, a
program in bf16 sends the token to the other expert than a float32
reference does, and that token's logits then differ by a whole expert's
share. No tolerance on free-running logits tells such a near-tie from a
wrong expert. So the logits are compared under TEACHER FORCING: the
reference sends each token to the experts the program chose and computes
everything else itself. With the choices equal the function is
continuous again, and every token's logits are held to the tolerances
of the dense comparison (``runners/train.py``), unchanged.

The choices are not taken on trust:

- exact: every id lies in ``[0, n_experts)`` and the k ids of a token
  are distinct;
- regret: for each layer and token, at the reference's OWN router logits
  l on the reference's own hidden state,

      regret = max(0, l_(k) - min over the chosen e of l_e) / std_e(l)

  with l_(k) the reference's k-th largest logit. A token routed as the
  reference would route it has regret 0; a moved near-tie has the tie's
  width; a wrong expert has a gap's width or more. The MAXIMUM over all
  layers and tokens is held to ``REGRET_TOL``.

A router of TWO STAGES (the experts stand in G groups of E/G; a token's
k experts are the best of its k_g best groups, a group's score being
the reference's to define, such as the sum of its two best experts)
has a second discontinuity, and the set it may choose from is the top-k
of no E-wide array. The reference then also returns its own
``group_scores``, and the program's groups P (read off its ids: expert
e stands in group ``e // (E/G)``) and its experts are judged apart:

- exact (``groups_valid``): no token's k experts lie in more than k_g
  groups;
- ``group_regret``: max(0, G_(k_g) - min over g in P of G_g) / std_g(G),
  with G_(k_g) the reference's k_g-th largest group score, its maximum
  held to ``GROUP_REGRET_TOL``;
- ``routing_regret`` as above, but over the CANDIDATES the program's
  groups leave: l_(k) is the k-th largest of the reference's scores
  over the experts of P (the deviation still over all E). Where P has
  fewer than k_g groups, a group the program chose and took no expert
  from cannot be seen: P is filled up to k_g - 1 groups with the
  reference's best groups outside P, and the last place goes to
  whichever group the program MAY have had there (any outside whose
  score lies within ``GROUP_REGRET_TOL`` of the reference's k_g-th)
  leaves its experts best off. The experts are judged given the
  groups, the groups by their own check.

One check never sees the program's choices: the program's mean loss
against the free-running reference (its own top-k), at
``FREE_LOSS_TOL``.

From the program this takes ``decoder.forward(..., return_aux=True)``:
the logits, and ``aux["moe_choices"]``, int32 ``[L, B, S, k]``, the
expert ids each token was sent to: one row per ROUTED layer in trunk
order (a dense layer has none; an extra prediction module's routed
layer comes last). The ids and nothing else reach the reference.

The objective's other terms are the reference's to name. Every scalar
the teacher-forced reference returns beside ``router_logits`` (a router
loss, an extra prediction module's cross-entropy) is a term the
program's step metrics MUST report under the same name: a term the
program does not report fails the run, it is not skipped.
"""

import numpy as np

from benchmarks.lib.device import Refused

# Largest regret a sound run may show, in standard deviations of a
# token's router logits. NOT tuned to a cell. The rule: at least twice
# the largest maximum over the chip rehearsal's seeds, and at most half
# the reference's median gap between the k-th and the (k+1)-th logit
# there (same unit). If the two cross, the comparison is the wrong
# design: stop and say so.
#
# The rehearsal (PR 28, my chip runs: Mixtral-8x7B's widths, 1 layer, 6
# of 8 experts, top-2, bf16, 2 x 4096 tokens, 21 seeds): the largest
# regret of a run 0.021..0.058 (median 0.029), wherever 0.21..0.34% of
# a layer's (token, choice) pairs had moved; the median gap
# 0.395..0.437. So 2 x 0.058 = 0.116 <= REGRET_TOL <= 0.395 / 2 = 0.198.
# What sets the regret is the error of the bf16 hidden state that feeds
# the router, about 1e-2 of a standard deviation, the same error
# LOGIT_TOL allows for; an extreme value over 8192 tokens. The defects
# there: the (k+1)-th expert for the k-th on 1% of tokens read 1.63 and
# 1.86, router logits rounded to 8 bits 0.19 and 0.30. A model with
# many narrow experts has gaps narrower than this limit (top-8 of 64:
# about 0.05): there it passes a swap of neighbours whose gap is under
# the limit and still fails a wrong expert, whose regret is a few gaps.
REGRET_TOL = 0.15

# Largest group regret a sound run may show, in standard deviations of a
# token's group scores, for a router that chooses groups before experts.
# NOT tuned to a cell. The rule is ``selected``'s (PR 36): at least twice
# the largest maximum over the chip rehearsal's sound seeds, and every
# defect named for the check (``tests/grouped_defects.py``) reads at
# least twice the limit. ``REGRET_TOL``'s second clause ("at most half
# the reference's median gap") CANNOT hold here, and ISSUE 43, which
# asked for it, said to stop where the two cross: they cross by a factor
# of three (PERF.md section 7 says why a limit stands all the same).
#
# The rehearsal (PR 43, my chip runs; ``tests/rehearse_grouped.py``):
# the stand-in ``tests/grouped_standin.py`` at Ling-3.0-flash's router
# widths (hidden 2560, 512 sigmoid experts of 768 in 8 groups of 64, a
# group's score the sum of its two best, 4 groups, top-8, x 2.5, a
# shared expert, 32 heads of 128), bf16, B 1, against
# ``tests/grouped_plain.py``; 86 sound seeds. A run's largest group
# regret: 0.048..0.099 at 1 layer x 8,192 tokens and 0.052..0.108 at
# 1 x 16,384 (20 seeds each, all 512 experts held), 0.058..0.187 at
# 2 x 8,192 and 0.082..0.175 at 2 x 16,384 (20 each, 256 held; the
# second layer reads higher than the first, 0.187 against 0.142),
# 0.100..0.130 at 4 x 8,192 (6 seeds, 128 held: no further growth); an
# extreme value over 8,192..65,536 (layer, token) places, medians
# 0.063..0.121. What sets it is the bf16 error of the two scores a
# group's score is made of, about 0.02 deviations of the eight group
# scores, which lie close: the reference's median gap between the 4th
# and the 5th is 0.259..0.279 everywhere, and a sound run has flipped a
# group on 1.3..2.1% of its tokens. So PR 28's clauses ask
# 2 x 0.187 = 0.37 <= limit <= 0.13. The defects, three seeds each at
# 1 and 2 layers x 16,384: the fifth group for the fourth on 1% of
# tokens 1.15..1.40, a group scored by its best expert 2.86..2.97, no
# group stage 2.89..3.01 (and 13,059..26,931 places in more than four
# groups), router logits rounded to 8 bits 1.00..1.69. So
# 0.37 <= GROUP_REGRET_TOL <= 1.15 / 2 = 0.57. At 0.4 a single wrong
# group passes wherever the token's own gap is under 0.4 (two tokens in
# three); what it fails is a rule that is wrong, which shows on many
# tokens, as ``REGRET_TOL`` does for many narrow experts. PROVISIONAL
# as ``selected``'s limits were: the ``model_config`` PR that brings the
# first such router brings the program's own readings over a dozen
# seeds; where they do not lie under the limit with room, a
# ``benchmark`` issue comes before the cell. The same number says which
# groups a program MAY have had (``grouped_routing_stats``).
#
# ``REGRET_TOL`` holds for such a router by its first clause: with the
# experts judged given the groups a sound run's largest reads
# 0.0069..0.0186 over the same 86 seeds (median gap 0.021..0.024 at
# top-8 of 256 candidates), the 2k-th candidate for the k-th on 1% of
# tokens 0.42..0.49, three groups for four 0.36..0.45; the (k+1)-th for
# the k-th 0.12..0.25 (five of six fail: a swap of neighbours).
GROUP_REGRET_TOL = 0.4

# A term of the objective that the routed reference computes too (every
# scalar it returns beside ``router_logits``, under the name of the
# program's step metric) is held to ROUTER_LOSS_TOL relative, unless
# the reference module lists it in ``CROSS_ENTROPY_TERMS``: a
# teacher-forced cross-entropy, such as an extra prediction module's,
# averages its rounding over the tokens as the main loss does and is
# held to the dense LOSS_TOL, ten times tighter. PROVISIONAL: no program
# has reported such a term yet, so LOSS_TOL for it stands on the main
# loss's readings and on none of its own. The PR that brings the first
# configuration whose reference lists a name there brings, on the chip,
# the term's error over a dozen seeds and the smallest that a control
# (the module's targets shifted by one more place, its weight 1% off)
# reads, and PERF.md section 7 says so; where LOSS_TOL does not lie
# between the two with room, that is a ``benchmark`` issue before the
# cell. The router losses come from the program's bf16 router logits:
# over the same 21 seeds moe_lb_loss read 1.2e-5..1.6e-4 and moe_z_loss
# 2.5e-6..5.7e-4, ten times the cross-entropy's error, and 8-bit router
# logits read no worse (2.7e-5..2.3e-4): this is no check of precision.
# It is there for a term that is dropped, scaled or shared out wrongly
# (a coefficient 1% off reads 1e-2; a share over tokens for one over
# (token, choice) pairs reads 1), set at three and a half times the
# largest seen.
ROUTER_LOSS_TOL = 2e-3

# The program's mean cross-entropy against the FREE-RUNNING reference,
# relative: the one check that never sees the program's choices. The
# tokens that moved (0.4..0.7% of them at the rehearsal) each bring
# another expert's loss, which does not average out as rounding does:
# 8.8e-8..1.3e-4 over 21 sound seeds, 2.0e-4 on weights two steps away
# from one of them, where the teacher-forced loss reads under 2e-5. So
# it cannot stand on the dense LOSS_TOL of 2e-4; 5e-4 still fails a
# dropped term of the loss, which is what a loss limit is for.
FREE_LOSS_TOL = 5e-4


def program_losses(params, batch, cfg):
    """The forward-only program's scalar step metrics, as floats: among
    them ``loss`` (what the step reports), ``ce_loss`` (the
    cross-entropy alone, where the program reports it apart from an
    objective that adds other terms) and whichever of the objective's
    terms it reports. ``compare`` looks up the names the reference
    carries; a term that is not here fails there."""
    import jax

    from dlrover_tpu.models import decoder

    @jax.jit
    def losses(params, batch):
        metrics = decoder.loss_fn(params, batch, cfg=cfg)[1]
        return {k: v for k, v in metrics.items() if v.ndim == 0}

    return {k: float(v) for k, v in losses(params, batch).items()}


def program_logits_and_choices(params, tokens, cfg, sizes=None):
    """The program's forward on ``tokens``: (logits, expert ids int32
    [n_layer, B, S, k]). A program that does not hand its choices over
    cannot be judged by this comparison, and is refused before anything
    compiles. ``sizes``, the configuration's, is what the runner passes
    to every teacher-forced kind; this one reads nothing from it."""
    import jax

    from dlrover_tpu.models import decoder

    def forward(params, tokens):
        return decoder.forward(params, tokens, cfg, return_aux=True)

    aux = jax.eval_shape(forward, params, tokens)[1]
    if "moe_choices" not in aux:
        raise Refused(
            "check.kind 'routed' needs the experts each token was sent to: "
            "decoder.forward(..., return_aux=True)[1]['moe_choices'], int32 "
            "[n_layer, B, S, k]; this program returns only "
            f"{sorted(aux)}"
        )
    logits, aux = jax.jit(forward)(params, tokens)
    return logits, aux["moe_choices"]


def choice_faults(choices, n_experts):
    """Number of (layer, token) places whose ids are not k distinct
    experts of ``[0, n_experts)``. Exact, on the host."""
    ids = np.asarray(choices)
    out_of_range = ((ids < 0) | (ids >= n_experts)).any(-1)
    ordered = np.sort(ids, axis=-1)
    repeated = (ordered[..., 1:] == ordered[..., :-1]).any(-1)
    return int((out_of_range | repeated).sum())


def group_faults(choices, n_experts, n_group, topk_group):
    """Number of (layer, token) places whose k experts lie in more than
    ``topk_group`` of the ``n_group`` groups (expert e stands in group
    ``e // (n_experts // n_group)``). Exact, on the host."""
    groups = np.asarray(choices) // (n_experts // n_group)
    met = (groups[..., None] == np.arange(n_group)).any(-2)
    return int((met.sum(-1) > topk_group).sum())


def routing_stats(router_logits, choices):
    """What the program's choices cost at the reference's router logits.

    router_logits: float32 [L, B, S, E], the reference's own, on its own
    hidden state; choices: int32 [L, B, S, k]. Returns a dict of arrays:
    ``regret`` [L, B, S] and ``gap`` [L, B, S] (the reference's k-th
    minus (k+1)-th logit), both in standard deviations of the token's
    logits, and ``moved`` [L], the share of a layer's (token, choice)
    pairs whose expert is not among the reference's k best."""
    import jax
    import jax.numpy as jnp

    k = choices.shape[-1]
    if router_logits.shape[-1] <= k:
        raise ValueError(
            f"top-{k} of {router_logits.shape[-1]} experts routes nothing"
        )
    best = jax.lax.top_k(router_logits, k + 1)[0]
    kth, after = best[..., k - 1], best[..., k]
    std = jnp.std(router_logits, axis=-1)
    chosen = jnp.take_along_axis(router_logits, choices, axis=-1)
    return {
        "regret": jnp.maximum(kth - jnp.min(chosen, -1), 0.0) / std,
        "gap": (kth - after) / std,
        "moved": jnp.mean(chosen < kth[..., None], axis=(1, 2, 3)),
    }


def grouped_routing_stats(scores, group_scores, choices, topk_group):
    """``routing_stats`` for a router that chooses ``topk_group`` groups
    and then k experts inside them.

    scores: float32 [L, B, S, E], the reference's own, what the expert
    top-k is taken over; group_scores: float32 [L, B, S, G], its own,
    what the group top-k is taken over (group g holds experts
    ``[g * E/G, (g + 1) * E/G)``); choices: int32 [L, B, S, k]. With P
    the groups the program's k experts lie in:

    - ``group_regret`` and ``group_gap`` [L, B, S]: the regret of P and
      the reference's gap at the group scores, in their standard
      deviations; ``groups_moved`` [L, B, S]: true where a group of P
      lies under the reference's ``topk_group``-th;
    - ``regret`` [L, B, S]: ``routing_stats``'s over the CANDIDATES the
      program's groups leave, the deviation over all E scores. Where P
      has ``topk_group`` groups or more they are the experts of P. Where
      it has fewer, a group the program chose and took no expert from
      cannot be seen, so P is filled: up to ``topk_group - 1`` groups
      with the reference's best groups outside P (ties to the lower
      group), and the last place with whichever group the program MAY
      have had there (any group outside whose score lies within
      ``GROUP_REGRET_TOL`` deviations of the reference's
      ``topk_group``-th, that one itself among them) leaves its experts
      best off. The letter of ISSUE 43 gave the last place to the
      reference's best group too; the chip rehearsal (PR 43) showed that
      it charges a near-tie between two GROUPS, flipped where neither
      shows in the ids, as one or two whole EXPERT gaps;
    - ``gap`` [L, B, S] and ``moved`` [L]: the reference's OWN k-th
      minus (k+1)-th score (its own groups), and the share of pairs
      whose expert is not among its own k."""
    import jax
    import jax.numpy as jnp

    k, n_exp, n_group = choices.shape[-1], scores.shape[-1], group_scores.shape[-1]
    if n_exp % n_group or not 0 < topk_group < n_group:
        raise ValueError(
            f"{topk_group} of {n_group} groups over {n_exp} experts is no "
            "group stage"
        )
    size = n_exp // n_group
    if topk_group * size <= k:
        raise ValueError(
            f"top-{k} of {topk_group} groups of {size} experts routes nothing"
        )
    group_ids = jnp.arange(n_group)
    group_of = choices // size
    met = jnp.any(group_of[..., None] == group_ids, axis=-2)  # P

    best_groups = jax.lax.top_k(group_scores, topk_group + 1)[0]
    kth_group, after_group = (
        best_groups[..., topk_group - 1], best_groups[..., topk_group]
    )
    std_group = jnp.std(group_scores, axis=-1)
    worst_met = jnp.min(jnp.where(met, group_scores, jnp.inf), axis=-1)
    own_groups = group_scores >= kth_group[..., None]  # ties: among the best

    def kth_and_next(groups):
        # the k-th and (k+1)-th largest score over the experts of ``groups``
        inside = jnp.repeat(groups, size, axis=-1)
        best = jax.lax.top_k(jnp.where(inside, scores, -jnp.inf), k + 1)[0]
        return best[..., k - 1], best[..., k]

    # every group of P, then the reference's best others, one place short
    filled = met
    if topk_group > 1:
        first = jax.lax.top_k(
            jnp.where(met, jnp.inf, group_scores), topk_group - 1
        )[1]
        filled = met | jnp.any(first[..., None] == group_ids, axis=-2)
    place_left = jnp.sum(filled, axis=-1) < topk_group
    may_have = ~filled & (
        group_scores
        >= (kth_group - GROUP_REGRET_TOL * std_group)[..., None]
    )

    def with_last(group):
        # the k-th score with ``group`` in the last place, where it may be
        kth = kth_and_next(filled | (group_ids == group))[0]
        return jnp.where(may_have[..., group], kth, jnp.inf)

    kth = jnp.where(
        place_left,
        jnp.min(jax.lax.map(with_last, group_ids), axis=0),
        kth_and_next(filled)[0],
    )
    own_kth, own_after = kth_and_next(own_groups)
    std = jnp.std(scores, axis=-1)
    chosen = jnp.take_along_axis(scores, choices, axis=-1)
    outside = ~jnp.take_along_axis(own_groups, group_of, axis=-1)
    return {
        "regret": jnp.maximum(kth - jnp.min(chosen, -1), 0.0) / std,
        "gap": (own_kth - own_after) / std,
        "moved": jnp.mean(
            outside | (chosen < own_kth[..., None]), axis=(1, 2, 3)
        ),
        "group_regret": jnp.maximum(kth_group - worst_met, 0.0) / std_group,
        "group_gap": (kth_group - after_group) / std_group,
        "groups_moved": jnp.any(outside, axis=-1),
    }


def forced_routing(forced, choices, sizes):
    """What is judged of the routing, from what the teacher-forced
    reference returned: ``forced["router_logits"]`` and, for a router of
    two stages, ``forced["group_scores"]`` are taken out of ``forced``
    (what stays are the objective's terms) and reduced by
    ``routing_summary``. Traced inside the comparison's one program."""
    scores = forced.pop("router_logits")
    group_scores = forced.pop("group_scores", None)
    n_group = sizes.get("moe_n_group", 1)
    topk_group = sizes.get("moe_topk_group", n_group)
    if group_scores is None:
        if topk_group < n_group:
            raise ValueError(
                f"the configuration routes over {topk_group} of {n_group} "
                "groups: its reference owes routed['group_scores']"
            )
        return routing_summary(routing_stats(scores, choices))
    if group_scores.shape[-1] != n_group:
        raise ValueError(
            f"group_scores over {group_scores.shape[-1]} groups, "
            f"sizes['moe_n_group'] = {n_group}"
        )
    return routing_summary(
        grouped_routing_stats(scores, group_scores, choices, topk_group)
    )


def logit_errors(logits, ref_logits):
    """(max |difference| over max |reference|, rms of the difference
    over rms of the reference): what LOGIT_TOL and LOGIT_RMS_TOL hold."""
    import jax.numpy as jnp

    diff = logits - ref_logits
    return (
        jnp.max(jnp.abs(diff)) / jnp.max(jnp.abs(ref_logits)),
        jnp.sqrt(jnp.sum(diff * diff) / jnp.sum(ref_logits * ref_logits)),
    )


def routing_summary(stats):
    """``routing_stats`` reduced to what is judged and recorded."""
    import jax.numpy as jnp

    regret = stats["regret"]
    groups = {}
    if "group_regret" in stats:  # a router of two stages
        groups = {
            "group_regret_max": jnp.max(stats["group_regret"]),
            "group_regret_max_by_layer": jnp.max(
                stats["group_regret"], axis=(1, 2)
            ),
            "group_gap_median": jnp.median(stats["group_gap"]),
            "groups_moved": jnp.mean(stats["groups_moved"]),
        }
    return {
        **groups,
        "regret_max": jnp.max(regret),
        "regret_max_by_layer": jnp.max(regret, axis=(1, 2)),
        # the median of the places that moved; 0 where none did
        "regret_median_moved": jnp.nan_to_num(
            jnp.nanmedian(jnp.where(regret > 0, regret, jnp.nan))
        ),
        "tokens_moved": jnp.mean(jnp.any(regret > 0, axis=0)),
        "gap_median": jnp.median(stats["gap"]),
        "moved_by_layer": stats["moved"],
    }


def routing_record(got):
    """``routing_summary``, read back, for the ``BENCH reference`` line."""
    record = {
        "regret_max": float(got["regret_max"]),
        "regret_max_by_layer": got["regret_max_by_layer"].tolist(),
        "regret_median_moved": float(got["regret_median_moved"]),
        "tokens_moved": float(got["tokens_moved"]),
        "gap_median": float(got["gap_median"]),
        "moved_by_layer": got["moved_by_layer"].tolist(),
        "regret_tol": REGRET_TOL,
    }
    if "group_regret_max" in got:
        record.update(
            group_regret_max=float(got["group_regret_max"]),
            group_regret_max_by_layer=(
                got["group_regret_max_by_layer"].tolist()
            ),
            group_gap_median=float(got["group_gap_median"]),
            groups_moved=float(got["groups_moved"]),
            group_regret_tol=GROUP_REGRET_TOL,
        )
    return record


def routing_checks(got, choices, sizes):
    """The checks on the routing, of what ``forced_routing`` gave, read
    back: ``routing_regret``, and before it ``groups_valid`` and
    ``group_regret`` where the reference judged groups."""
    results = []
    if "group_regret_max" in got:
        faults = group_faults(
            choices, sizes["n_experts"], sizes["moe_n_group"],
            sizes["moe_topk_group"],
        )
        worst = float(got["group_regret_max"])
        results += [
            ("groups_valid", faults == 0, faults, 0),
            ("group_regret", worst <= GROUP_REGRET_TOL, worst,
             GROUP_REGRET_TOL),
        ]
    worst = float(got["regret_max"])
    results.append(("routing_regret", worst <= REGRET_TOL, worst, REGRET_TOL))
    return results


def forced_checks(got, program, tolerances):
    """The three checks of ``dense`` against a teacher-forced reference
    (``got``: its ``ref_loss``, ``logit_err``, ``logit_rms``), and the
    loss's relative error."""
    logit_tol, logit_rms_tol, loss_tol = tolerances
    ce = program.get("ce_loss", program["loss"])
    loss_err = abs(ce - float(got["ref_loss"])) / abs(float(got["ref_loss"]))
    return [
        ("logits_vs_reference", float(got["logit_err"]) <= logit_tol,
         float(got["logit_err"]), logit_tol),
        ("logits_rms_vs_reference", float(got["logit_rms"]) <= logit_rms_tol,
         float(got["logit_rms"]), logit_rms_tol),
        ("loss_vs_reference", loss_err <= loss_tol, loss_err, loss_tol),
    ], loss_err


def objective_checks(reference, terms, program, loss_tol, own_limits=None):
    """One check for each term of the objective the teacher-forced
    reference carries: the program's step metric of that name against
    it, relative, at ``ROUTER_LOSS_TOL``, or at ``loss_tol`` where the
    module lists the name in ``CROSS_ENTROPY_TERMS``, or at the limit
    the comparison's kind has set for that name (``own_limits``). A term
    the program does not report fails."""
    results = []
    cross_entropies = getattr(reference, "CROSS_ENTROPY_TERMS", ())
    for name, want in terms.items():
        tol = loss_tol if name in cross_entropies else ROUTER_LOSS_TOL
        tol = (own_limits or {}).get(name, tol)
        if name not in program:
            results.append((
                name + "_vs_reference", False,
                "not among the program's step metrics", tol,
            ))
            continue
        want = float(want)
        err = abs(program[name] - want) / (abs(want) or 1.0)
        results.append((name + "_vs_reference", err <= tol, err, tol))
    return results


def against_forced(reference, sizes, q_block):
    """The comparison's one program: the teacher-forced reference, the
    logits' errors and the routing's summary."""
    import jax

    @jax.jit
    def against_forced(params, batch, logits, choices):
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_logits, routed = reference.loss_and_logits_routed(
                params, batch, sizes, q_block, choices
            )
        logit_err, logit_rms = logit_errors(logits, ref_logits)
        return {
            "ref_loss": ref_loss, "logit_err": logit_err,
            "logit_rms": logit_rms, **forced_routing(routed, choices, sizes),
            "objective_terms": routed,
        }

    return against_forced


def compare(reference, params, batch, sizes, q_block, logits, choices,
            program, tolerances):
    """The teacher-forced comparison on one share of the batch.

    ``program`` is ``program_losses`` of the same share.
    ``reference.loss_and_logits_routed(params, batch, sizes, q_block,
    choices)`` returns ``(mean cross-entropy, logits, routed)`` where
    ``routed["router_logits"]`` is float32 [L, B, S, E] (one row per
    routed layer, E the router's width, what the top-k is taken over:
    for a router of two stages the SCORES the expert top-k is taken
    over), ``routed["group_scores"]``, where the router chooses groups
    first, float32 [L, B, S, G], what the group top-k is taken over
    (``sizes["moe_n_group"]`` = G, ``sizes["moe_topk_group"]`` = k_g),
    and every other entry is a scalar term of the objective under the
    name of the program's step metric, coefficient included.

    Returns (results, record): ``results`` as ``(name, ok, value,
    limit)`` for the checks, ``record`` for the ``BENCH reference`` line."""
    import jax

    faults = choice_faults(choices, sizes["n_experts"])
    results = [("choices_valid", faults == 0, faults, 0)]
    if faults:
        # ids that name no expert cannot be forced on the reference
        return results, {"choice_faults": faults}

    got = jax.tree.map(
        np.asarray,
        against_forced(reference, sizes, q_block)(params, batch, logits, choices),
    )
    dense, loss_err = forced_checks(got, program, tolerances)
    results += [
        *routing_checks(got, choices, sizes),
        *dense,
        *objective_checks(
            reference, got["objective_terms"], program, tolerances[2]
        ),
    ]
    record = {
        "forced_ref_loss": float(got["ref_loss"]),
        "forced_loss_err": loss_err,
        "forced_logit_err": float(got["logit_err"]),
        "forced_logit_rms": float(got["logit_rms"]),
        **routing_record(got),
        "reference_terms": {
            k: float(v) for k, v in got["objective_terms"].items()
        },
    }
    return results, record
