"""Shared by the readers of one program scope's share of the device's
busy time (``mla.latent_share``, ``mtp.share``); not a metric itself."""

from benchmarks.lib.trace import scope_seconds


def share(run, metric, scope):
    """Percent of the first device's busy time in operations traced
    under ``scope`` (a path component of their ``op_name``: forward,
    recomputed and backward alike), by ``lib/trace.scope_seconds``. The
    rows found go on a ``BENCH`` line (``event: scope_rows``). None
    where there is no device trace; a traced step with no row under the
    scope is an error, since the metric is listed only for cells whose
    program has the scope, and one that vanished must not read as a
    metric left out."""
    trace = run["trace"]
    if not trace or not trace["per_device"]:
        return None
    first = trace["per_device"][0]
    rows = scope_seconds(first, (scope,))
    run["say"](
        event="scope_rows", metric=metric, busy_s=first["busy_s"],
        modules=first.get("modules"),
        rows={scope: [len(rows), sum(rows.values())]},
    )
    if not rows:
        raise LookupError(
            f"no operation of the traced step under the scope {scope!r}"
        )
    if not first["busy_s"]:
        return None
    return 100.0 * sum(rows.values()) / first["busy_s"]
