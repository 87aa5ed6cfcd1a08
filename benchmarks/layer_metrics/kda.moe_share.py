"""Routed experts beside a shared one: percent of the device's busy time
spent in the routed blocks, from the device trace: self time of the
first device's operations under the program's scopes ``moe.route`` (the
256-wide router, sigmoid, top-8), ``moe.sort``, ``moe.experts`` (the
experts' passes between the grouped matmuls), ``moe.combine`` and
``moe.shared`` (the shared expert), forward, recomputed and backward
alike, and of the grouped matmuls over the held rows themselves, over
its busy time. ``lax.ragged_dot`` lowers to the compiler's own
``tpu_custom_call`` whose ``op_name`` is ``ragged-dot-none``, under no
scope of the program: they are taken by their label, as
``moe.grouped_matmul_share`` takes them, and a row found both ways
counts once. The rows found under each scope go on a ``BENCH`` line
(``event: scope_rows``), the grouped matmuls on one of their own
(``event: ragged_dot_rows``); a program without one of the scopes reads
nothing."""

from benchmarks.lib.gdn import first_device, scope_rows
from benchmarks.lib.trace import scope_seconds

SCOPES = (
    "moe.route", "moe.sort", "moe.experts", "moe.combine", "moe.shared",
)


def read(run):
    got = scope_rows(run, "kda.moe_share", SCOPES)
    if got is None:
        return None
    first = first_device(run)
    scoped = scope_seconds(first, SCOPES)
    matmuls = {
        label: row[0] for label, row in first["by_name"].items()
        if label.startswith("ragged-dot") and label not in scoped
    }
    run["say"](
        event="ragged_dot_rows", metric="kda.moe_share",
        rows=[len(matmuls), sum(matmuls.values())],
    )
    return 100.0 * (got[0] + sum(matmuls.values())) / got[1]
