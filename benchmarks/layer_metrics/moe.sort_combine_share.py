"""Routed experts: percent of the device's busy time spent moving rows
around the grouped matmuls, from the device trace: self time of the
first device's operations traced under the program's scopes
``moe.sort`` (expert order, its inverse, the dispatch gather) and
``moe.combine`` (the gather back and the contraction with the k
weights), forward, recomputed and backward alike, over its busy time.
The scopes come from the compiled step's ``op_name``s
(``lib/trace.op_names``). The ``ragged-dot*`` calls carry no scope and
stay with ``moe.grouped_matmul_share``; the router is ``moe.route``.
The rows found under each scope go on a ``BENCH`` line
(``event: scope_rows``); none under either is an error, not a metric
left out."""

from benchmarks.lib.trace import scope_seconds

SCOPES = ("moe.sort", "moe.combine")


def read(run):
    trace = run["trace"]
    if not trace or not trace["per_device"]:
        return None
    first = trace["per_device"][0]
    rows = {scope: scope_seconds(first, (scope,)) for scope in SCOPES}
    run["say"](
        event="scope_rows", metric="moe.sort_combine_share",
        busy_s=first["busy_s"], modules=first.get("modules"),
        rows={k: [len(v), sum(v.values())] for k, v in rows.items()},
    )
    # listed for routed cells alone: a traced step of one without a row
    # under either scope has lost the scopes or the way they get here,
    # and a metric that silently drops out of the line hides that
    if not all(rows.values()):
        raise LookupError(
            f"no operation of the traced step under {SCOPES}: "
            f"{ {k: len(v) for k, v in rows.items()} } rows"
        )
    if not first["busy_s"]:
        return None
    # a row whose merged path names both scopes counts once
    seconds = sum(scope_seconds(first, SCOPES).values())
    return 100.0 * seconds / first["busy_s"]
