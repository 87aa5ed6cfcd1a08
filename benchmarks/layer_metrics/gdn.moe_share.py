"""Routed experts beside a gated shared one: percent of the device's
busy time spent in the routed blocks, from the device trace: self time
of the first device's operations under the program's scopes
``moe.route`` (the 512-wide router, softmax, top-10), ``moe.sort``,
``moe.experts`` (the grouped matmuls over the held rows),
``moe.combine`` and ``moe.shared`` (the shared expert and its sigmoid
gate), forward, recomputed and backward alike, over its busy time; a
row under two of them counts once. The rows found under each go on a
``BENCH`` line (``event: scope_rows``); a program without one of the
scopes reads nothing."""

from benchmarks.lib.gdn import share

SCOPES = (
    "moe.route", "moe.sort", "moe.experts", "moe.combine", "moe.shared",
)


def read(run):
    return share(run, "gdn.moe_share", SCOPES)
