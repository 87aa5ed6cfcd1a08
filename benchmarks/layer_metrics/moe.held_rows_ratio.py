"""Routed experts, a chip that holds a part of them: the (token,
expert) rows the experts held here received in a step, over the rows
they would receive under balanced routing, ``tokens x expert_top_k x
n_experts_held / n_experts``. The program's own step metric
``moe_held_rows`` (the sum of the group sizes its grouped matmuls ran
with, mean over the routed blocks); the median over the warm-up and
traced steps. 1 is balanced. ``lib/flops.py`` prices the held experts
at the balanced share, so ``train_step.mfu`` is off by (this ratio - 1)
times the held experts' part of the required FLOPs."""

import statistics


def balanced_rows(tokens, sizes):
    return (
        tokens * sizes["expert_top_k"] * sizes["n_experts_held"]
        / sizes["n_experts"]
    )


def read(run):
    rows = run.get("step_metrics", {}).get("moe_held_rows")
    if not rows:
        return None
    return statistics.median(rows) / balanced_rows(
        run["window"]["tokens"], run["sizes"]
    )
