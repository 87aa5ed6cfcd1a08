"""Sigmoid top-4 of 32 experts, rank 0 of four: the (token, expert) rows
the 8 experts held here received in a step, over the rows they would
receive under balanced routing, ``tokens x 4 x 8 / 32`` = 32,768: the
program's own step metric ``moe_held_rows`` (mean over the layers), the
median over the warm-up and traced steps, as ``mellum.held_rows_ratio``
reads it. 1 is balanced. The routed blocks' time goes by these rows: the
one thing in the timed step whose amount of work changes with
``--seed``. The steps' own numbers, and the fullest expert's load beside
them (``moe_max_load``, over the mean), go on a ``BENCH`` line
(``event: held_rows``)."""

import statistics


def read(run):
    rows = run.get("step_metrics", {}).get("moe_held_rows")
    if not rows:
        return None
    sizes = run["sizes"]
    balanced = (
        run["window"]["tokens"] * sizes["expert_top_k"]
        * sizes["n_experts_held"] / sizes["n_experts"]
    )
    run["say"](
        event="held_rows", metric="lfm2.held_rows_ratio", balanced=balanced,
        rows=rows, max_load=run["step_metrics"].get("moe_max_load"),
    )
    return statistics.median(rows) / balanced
