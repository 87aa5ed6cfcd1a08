"""Strategy search engine.

Reference: atorch AccelerationEngine (auto/engine/acceleration_engine.py:13)
with Planner → candidate strategies, Executor → dryrun tasks, and HEBO
Bayesian optimisation over measured throughput.

TPU version: candidates are axis factorizations of the device count plus
remat/precision choices; infeasible ones are rejected analytically
(``analyser``), survivors are ranked either by a locality-aware heuristic
score (free), XLA compiled cost (cheap), or measured dry runs (exact).
"""

import itertools
from typing import List, Optional, Tuple

from dlrover_tpu.common import device
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.models.config import ModelConfig
from dlrover_tpu.accelerate.analyser import analyse
from dlrover_tpu.accelerate.dry_runner import dry_run
from dlrover_tpu.accelerate.strategy import (
    AccelerationPlan,
    Strategy,
    apply_strategy,
)

logger = get_logger(__name__)


# candidate cap for the cheap analytic phase (measured modes are
# separately capped by max_measured); shared with tests
ANALYTIC_CANDIDATE_CAP = 512


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def generate_candidates(
    cfg: ModelConfig,
    n_devices: int,
    seq: int,
    max_candidates: int = 32,
) -> List[Strategy]:
    """Enumerate (tp, sp, pp, fsdp, dp) factorizations + remat choices.

    On fp8-native hardware (device_context.fp8_supported) every dense-
    model candidate carries the fp8 method by default — the reference
    auto-applies TE fp8 the same way when the GPU supports it
    (atorch/auto/opt_lib/amp_optimization.py:197). MoE models stay bf16
    (expert GEMMs have no fp8 wiring)."""
    from dlrover_tpu.accelerate.device_context import fp8_supported

    fp8_default = fp8_supported() and cfg.n_experts == 0
    candidates: List[Strategy] = []
    for tp, sp in itertools.product(_divisors(n_devices), repeat=2):
        if n_devices % (tp * sp):
            continue
        if cfg.n_head % tp or cfg.kv_heads % tp:
            continue
        if seq % max(1, sp):
            continue
        if sp > 1 and cfg.n_head % (sp * tp):
            continue  # ulysses shards the tp-sharded heads across sp too
        rest = n_devices // (tp * sp)
        for pp in _divisors(rest):
            if pp > 1 and (sp > 1 or cfg.n_layer % pp):
                continue  # pipeline can't nest sp shard_maps / split layers
            rest2 = rest // pp
            for fsdp in _divisors(rest2):
                dp = rest2 // fsdp
                base: Strategy = [
                    ("amp_bf16", {}),
                    (
                        "mixed_parallel",
                        {
                            "dp": dp,
                            "fsdp": fsdp,
                            "tp": tp,
                            "sp": sp,
                            "pp": pp,
                        },
                    ),
                ]
                if sp > 1:
                    base.append(("sequence_parallel", {"size": sp}))
                if fp8_default:
                    base.append(("fp8", {}))
                candidates.append(base + [("checkpoint", {"policy": "none"})])
                candidates.append(base + [("checkpoint", {"policy": "full"})])
                # memory-squeeze tier: host-offloaded moments on top of
                # full remat — fits models the resident plans cannot
                candidates.append(
                    base
                    + [("checkpoint", {"policy": "full"}),
                       ("offload_opt", {})]
                )
    # dedupe, keep stable order
    seen = set()
    out = []
    for c in candidates:
        key = str(c)
        if key not in seen:
            seen.add(key)
            out.append(c)
    if len(out) <= max_candidates:
        return out
    # Over the cap: truncate diversity-first, not prefix-first (a prefix
    # cut silently drops whole regions — e.g. every tp>1 plan at 16+
    # devices). Keep the best-scoring plan of every (tp, sp, pp) group,
    # then fill remaining slots by score.
    def model_axes(c):
        for name, cfg_d in c:
            if name == "mixed_parallel":
                return (
                    cfg_d.get("tp", 1),
                    cfg_d.get("sp", 1),
                    cfg_d.get("pp", 1),
                )
        return (1, 1, 1)

    def score(c):
        return _heuristic_score(cfg, apply_strategy(c), n_devices)

    groups = {}
    for c in out:
        groups.setdefault(model_axes(c), []).append(c)
    picked = []
    for group in groups.values():
        group.sort(key=score, reverse=True)
        picked.append(group[0])
    rest = [c for g in groups.values() for c in g[1:]]
    rest.sort(key=score, reverse=True)
    picked.extend(rest)
    picked = picked[:max_candidates]

    def has_offload(c):
        return any(name == "offload_opt" for name, _ in c)

    if not any(has_offload(c) for c in picked):
        # the offload tier scores low (host DMA) so score-based
        # truncation always drops it — but it exists for the case where
        # nothing resident fits, so reserve one slot for the MOST
        # SHARDED offload variant (minimum device memory), not the
        # best-scoring one
        def shards(c):
            for name, d in c:
                if name == "mixed_parallel":
                    return (
                        d.get("fsdp", 1) * d.get("tp", 1) * d.get("pp", 1)
                    )
            return 1

        offloads = sorted(
            (c for c in out if has_offload(c)),
            key=lambda c: (shards(c), score(c)),
            reverse=True,
        )
        if offloads:
            picked[-1] = offloads[0]
    return picked


def _heuristic_score(
    cfg: ModelConfig, plan: AccelerationPlan, n_devices: int
) -> float:
    """Cheap locality-aware preference: less model parallelism is better
    unless memory forces it; remat costs ~30% extra FLOPs."""
    sizes = plan.mesh.resolved_sizes(n_devices)
    score = 1.0
    score /= 1.0 + 0.15 * (sizes["tp"] - 1)   # tp all-reduces per layer
    score /= 1.0 + 0.10 * (sizes["sp"] - 1)   # sp all-to-alls
    score /= 1.0 + 0.02 * (sizes["fsdp"] - 1)  # fsdp all-gathers overlap well
    pp = sizes["pp"]
    if pp > 1:
        from dlrover_tpu.parallel.pipeline import pipeline_bubble_fraction

        n_micro = cfg.pp_microbatches or pp
        score *= 1.0 - pipeline_bubble_fraction(pp, n_micro)  # fill/drain
    if plan.remat == "full":
        score *= 0.75
    if plan.offload_opt_state:
        # host DMA around the optimizer update (measured ~2x step cost
        # at 124M single-chip; relatively cheaper as models grow) —
        # chosen only when resident plans don't fit
        score *= 0.55
    return score


def _bo_search(
    cfg: ModelConfig,
    feasible: List[Tuple[float, Strategy, AccelerationPlan]],
    n_devices: int,
    global_batch: int,
    seq: int,
    budget: int,
    devices,
) -> Optional[Tuple[float, Strategy, AccelerationPlan]]:
    """Bayesian-opt over the feasible set, measured by dry runs.

    Reference: ATorch's HEBO BO over dryrun throughput
    (auto/engine/sg_algo/bayes_opt_sg.py). The BO space is the strategy's
    knobs (log2 of each mesh axis + remat); each suggestion is projected
    onto the nearest feasible candidate, so the surrogate learns over a
    smooth space while only real plans get measured.
    """
    import math

    import numpy as np

    from dlrover_tpu.accelerate.hpsearch import (
        BayesianOptimizer,
        Choice,
        Int,
        SearchSpace,
    )

    def knobs(plan: AccelerationPlan) -> dict:
        sizes = plan.mesh.resolved_sizes(n_devices)
        return {
            "log2_tp": int(math.log2(sizes["tp"])),
            "log2_sp": int(math.log2(sizes["sp"])),
            "log2_pp": int(math.log2(sizes["pp"])),
            "log2_fsdp": int(math.log2(sizes["fsdp"])),
            "remat": plan.remat,
        }

    max_log2 = max(1, int(math.log2(n_devices)))
    space = SearchSpace(
        {
            "log2_tp": Int(0, max_log2),
            "log2_sp": Int(0, max_log2),
            "log2_pp": Int(0, max_log2),
            "log2_fsdp": Int(0, max_log2),
            "remat": Choice(["none", "full"]),
        }
    )
    encoded = [space.encode(knobs(plan)) for _, _, plan in feasible]
    opt = BayesianOptimizer(space, n_init=max(2, budget // 3))
    measured: dict = {}
    best = None
    for _ in range(budget):
        want = space.encode(opt.suggest())
        # project onto the nearest not-yet-measured feasible candidate
        order = np.argsort(
            [float(np.sum((e - want) ** 2)) for e in encoded]
        )
        idx = next((int(i) for i in order if int(i) not in measured), None)
        if idx is None:
            break  # feasible set exhausted
        _, strat, plan = feasible[idx]
        res = dry_run(cfg, plan, global_batch, seq, devices=devices)
        metric = res.tokens_per_sec if res.ok else 0.0
        measured[idx] = metric
        opt.observe(knobs(plan), metric)
        logger.info("BO measured %s → %.3g tokens/s", strat, metric)
        if res.ok and (best is None or metric > best[0]):
            best = (metric, strat, plan)
    return best


def search_strategy(
    cfg: ModelConfig,
    n_devices: int,
    global_batch: int,
    seq: int,
    mode: str = "heuristic",  # heuristic | cost | measure | bo
    max_measured: int = 6,
    devices=None,
) -> Tuple[Strategy, AccelerationPlan]:
    if mode == "measured":  # common alias
        mode = "measure"
    if mode not in ("heuristic", "cost", "measure", "bo"):
        # an unknown mode used to silently fall through to the measure
        # loop — fail loudly instead
        raise ValueError(
            f"unknown search mode {mode!r}: expected "
            "heuristic | cost | measure | bo"
        )
    hbm = device.device_memory_bytes()
    batch_per_chip = max(1, global_batch // n_devices)
    feasible: List[Tuple[float, Strategy, AccelerationPlan]] = []
    # the analytic feasibility filter is cheap — consider the (near-)
    # full candidate set here; only the measured modes below are capped
    # (max_measured), so the default truncation would just hide plans
    # (e.g. the offload tier) that memory pressure makes load-bearing
    for strat in generate_candidates(cfg, n_devices, seq,
                                     max_candidates=ANALYTIC_CANDIDATE_CAP):
        plan = apply_strategy(strat)
        try:
            a = analyse(cfg, plan, n_devices, batch_per_chip, seq, hbm)
        except ValueError:
            continue
        if not a.fits:
            continue
        feasible.append((_heuristic_score(cfg, plan, n_devices), strat, plan))
    if not feasible:
        # nothing fits: force max sharding + full remat + bf16 params
        # + host-offloaded moments (the one offload strategy method;
        # full remat is the lower device-memory bound)
        strat = [
            ("half", {}),
            ("mixed_parallel", {"dp": 1, "fsdp": n_devices, "tp": 1, "sp": 1}),
            ("checkpoint", {"policy": "full"}),
            ("bf16_optim", {}),
            ("offload_opt", {}),
        ]
        logger.warning("no analytically-feasible strategy; forcing %s", strat)
        return strat, apply_strategy(strat)

    feasible.sort(key=lambda t: -t[0])

    def _warn_if_unvalidated_offload(plan):
        # analyse() budgets the offloaded moments' device working set at
        # the largest-leaf bound the streamed update enforces
        # (streamed_offload_adamw's barrier-serialized transfers). The
        # bound is structural for the streamed adamw path; a measured
        # step (mode='measure'/'bo') remains the ground truth for
        # optimizers that still take the legacy whole-tree path.
        if plan.offload_opt_state and (
            plan.optimizer != "adamw"
            or plan.optimizer_state_dtype is not None
        ):
            logger.warning(
                "selected offload_opt with a non-streaming optimizer "
                "(%s/%s): the whole-tree legacy path has no working-set "
                "bound — run mode='measure' or 'bo' to validate before "
                "training",
                plan.optimizer,
                plan.optimizer_state_dtype,
            )

    if mode == "heuristic":
        score, strat, plan = feasible[0]
        logger.info("heuristic strategy (score %.3f): %s", score, strat)
        _warn_if_unvalidated_offload(plan)
        return strat, plan

    if mode == "bo":
        best = _bo_search(
            cfg, feasible, n_devices, global_batch, seq, max_measured, devices
        )
        if best is None:
            _, strat, plan = feasible[0]
            _warn_if_unvalidated_offload(plan)
            return strat, plan
        return best[1], best[2]

    best = None
    for score, strat, plan in feasible[:max_measured]:
        res = dry_run(
            cfg,
            plan,
            global_batch,
            seq,
            cost_only=(mode == "cost"),
            devices=devices,
        )
        if not res.ok:
            continue
        metric = (
            -res.cost_flops - res.cost_bytes
            if mode == "cost"
            else res.tokens_per_sec
        )
        logger.info(
            "measured %s → %.3g (%s)",
            strat,
            metric,
            "cost" if mode == "cost" else "tokens/s",
        )
        if best is None or metric > best[0]:
            best = (metric, strat, plan)
    if best is None:
        # every dry run failed: the fallback pick is exactly as
        # unvalidated as the heuristic one
        _, strat, plan = feasible[0]
        _warn_if_unvalidated_offload(plan)
        return strat, plan
    if mode == "cost":
        # cost mode compiles but never executes a step, so an offload
        # pick is still runtime-unvalidated
        _warn_if_unvalidated_offload(best[2])
    return best[1], best[2]
