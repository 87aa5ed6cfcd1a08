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


def program_logits_and_choices(params, tokens, cfg):
    """The program's forward on ``tokens``: (logits, expert ids int32
    [n_layer, B, S, k]). A program that does not hand its choices over
    cannot be judged by this comparison, and is refused before anything
    compiles."""
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
    return {
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
    return {
        "regret_max": float(got["regret_max"]),
        "regret_max_by_layer": got["regret_max_by_layer"].tolist(),
        "regret_median_moved": float(got["regret_median_moved"]),
        "tokens_moved": float(got["tokens_moved"]),
        "gap_median": float(got["gap_median"]),
        "moved_by_layer": got["moved_by_layer"].tolist(),
        "regret_tol": REGRET_TOL,
    }


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


def objective_checks(reference, terms, program, loss_tol):
    """One check for each term of the objective the teacher-forced
    reference carries: the program's step metric of that name against
    it, relative, at ``ROUTER_LOSS_TOL``, or at ``loss_tol`` where the
    module lists the name in ``CROSS_ENTROPY_TERMS``. A term the program
    does not report fails."""
    results = []
    cross_entropies = getattr(reference, "CROSS_ENTROPY_TERMS", ())
    for name, want in terms.items():
        tol = loss_tol if name in cross_entropies else ROUTER_LOSS_TOL
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


def compare(reference, params, batch, sizes, q_block, logits, choices,
            program, tolerances):
    """The teacher-forced comparison on one share of the batch.

    ``program`` is ``program_losses`` of the same share.
    ``reference.loss_and_logits_routed(params, batch, sizes, q_block,
    choices)`` returns ``(mean cross-entropy, logits, routed)`` where
    ``routed["router_logits"]`` is float32 [L, B, S, E] (one row per
    routed layer, E the router's width, what the top-k is taken over)
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

    @jax.jit
    def against_forced(params, batch, logits, choices):
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_logits, routed = reference.loss_and_logits_routed(
                params, batch, sizes, q_block, choices
            )
        logit_err, logit_rms = logit_errors(logits, ref_logits)
        stats = routing_stats(routed.pop("router_logits"), choices)
        return {
            "ref_loss": ref_loss, "logit_err": logit_err,
            "logit_rms": logit_rms, **routing_summary(stats),
            "objective_terms": routed,
        }

    got = jax.tree.map(np.asarray, against_forced(params, batch, logits, choices))
    regret_max = float(got["regret_max"])
    dense, loss_err = forced_checks(got, program, tolerances)
    results += [
        ("routing_regret", regret_max <= REGRET_TOL, regret_max, REGRET_TOL),
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
