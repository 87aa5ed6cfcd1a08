"""Softmax top-8 of 64 experts, rank 0 of four: percent of the device's
busy time spent in the routed blocks, from the device trace: self time
of the first device's operations under the program's scopes
``moe.route`` (the 64-wide router in float32, softmax, top-8,
renormalised), ``moe.sort``, ``moe.experts`` (the experts' passes
between the grouped matmuls) and ``moe.combine``, forward, recomputed
and backward alike, and of the grouped matmuls over the held rows
themselves, over its busy time. ``lax.ragged_dot`` lowers to the
compiler's own ``tpu_custom_call`` whose ``op_name`` is
``ragged-dot-none``, under no scope of the program: they are taken by
their label, and a row found both ways counts once (``kda.moe_share``'s
way). The rows found go on ``BENCH`` lines (``event: scope_rows``,
``event: ragged_dot_rows``); a scope without a row is an error."""

from benchmarks.lib.gdn import first_device
from benchmarks.lib.mellum import scope_rows
from benchmarks.lib.trace import scope_seconds

SCOPES = ("moe.route", "moe.sort", "moe.experts", "moe.combine")


def read(run):
    got = scope_rows(run, "mellum.moe_share", SCOPES)
    if got is None:
        return None
    first = first_device(run)
    scoped = scope_seconds(first, SCOPES)
    matmuls = {
        label: row[0] for label, row in first["by_name"].items()
        if label.startswith("ragged-dot") and label not in scoped
    }
    run["say"](
        event="ragged_dot_rows", metric="mellum.moe_share",
        rows=[len(matmuls), sum(matmuls.values())],
    )
    return 100.0 * (got[0] + sum(matmuls.values())) / got[1]
