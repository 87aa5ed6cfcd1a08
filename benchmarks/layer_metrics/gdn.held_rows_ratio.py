"""Routed experts, rank 0 of sixteen: the (token, expert) rows the 32
experts held here received in a step, over the rows they would receive
under balanced routing, ``tokens x 10 x 32 / 512``: the program's own
step metric ``moe_held_rows`` (mean over the routed blocks), the median
over the warm-up and traced steps, as ``moe.held_rows_ratio`` reads it
for the other cells that hold a part of their experts. 1 is balanced.
The routed blocks' time goes by these rows, so the cell's step time
follows this ratio from seed to seed."""

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
