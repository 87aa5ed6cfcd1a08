"""The program's own counter table, read from outside.

``dlrover_tpu.observability.tracing.counters()`` is a flat table the
program keeps at boundaries that happen at most once a step or once a
trace. A program that has no such table (a parent commit from before it
existed) gives an empty one here: a reader then finds its counter
missing and leaves its metric out, and nothing raises.
"""


def program_counters():
    try:
        from dlrover_tpu.observability.tracing import counters
    except ImportError:
        return {}
    return counters()


def zero_step_bytes(table):
    """Bytes one rank moves for ZeRO in a step — the gradient stream it
    hands to the exchange plus the parameter stream it gathers back —
    or None when either counter is missing."""
    sent = table.get("zero.exchange_bytes")
    gathered = table.get("zero.gather_bytes")
    if sent is None or gathered is None:
        return None
    return sent + gathered
