"""Softmax top-8 of 64 experts, rank 0 of four: the (token, expert) rows
the 16 experts held here received in a step, over the rows they would
receive under balanced routing, ``tokens x 8 x 16 / 64`` = 65,536: the
program's own step metric ``moe_held_rows`` (mean over the layers), the
median over the warm-up and traced steps, as ``kda.held_rows_ratio``
reads it. 1 is balanced. The routed blocks' time goes by these rows: the
one thing in the timed step whose amount of work changes with
``--seed``."""

import statistics


def read(run):
    rows = run.get("step_metrics", {}).get("moe_held_rows")
    if not rows:
        return None
    sizes = run["sizes"]
    return statistics.median(rows) / (
        run["window"]["tokens"] * sizes["expert_top_k"]
        * sizes["n_experts_held"] / sizes["n_experts"]
    )
