"""Routed experts: how unevenly the step's rows fall on the experts —
the rows of the fullest expert over the mean rows an expert gets, mean
over layers (1 = balanced, n_experts / expert_top_k = every token picks
the same experts). The program's own step metric ``moe_max_load``, from
the group sizes its grouped matmuls ran with; the median over the
warm-up and traced steps. A dropless step's grouped matmuls do the same
work however the rows fall; what imbalance costs is the tail of the
fullest expert's tiles, and a capacity or an expert-parallel layout
would pay for it in drops or in waiting."""

import statistics


def read(run):
    loads = run.get("step_metrics", {}).get("moe_max_load")
    return statistics.median(loads) if loads else None
