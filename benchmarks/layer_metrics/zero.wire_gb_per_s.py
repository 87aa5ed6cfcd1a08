"""ZeRO exchange: the payload (algorithmic) bandwidth of the exchange
while a collective runs, in GB/s per chip: the bytes one rank hands to
ZeRO's collectives in a step (the program's counters, as
``zero.exchange_mb_per_step``) over the collective seconds of one traced
step (device trace: the union of the collectives' intervals, averaged
over the chips, divided by the dispatches inside the traced window).

Payload, not bytes on a link: a reduce-scatter or an all-gather over n
ranks puts about (n - 1) / n of its payload on each rank's links, so
hold this times (n - 1) / n (3/4 on four chips) against the chip's
interconnect peak in ``lib/peaks.py`` (``ici_bits_s`` / 8), not the
figure itself."""

from benchmarks.lib.counters import program_counters, zero_step_bytes


def traced_steps(spans):
    """Dispatches inside the last traced window: other ``dispatch``
    spans of the run, should a runner ever emit them, do not count."""
    windows = [(s, e) for n, s, e in spans.spans if n == "traced_window"]
    if not windows:
        return 0
    lo, hi = windows[-1]
    return sum(
        1 for n, s, e in spans.spans if n == "dispatch" and lo <= s and e <= hi
    )


def read(run):
    trace = run["trace"]
    nbytes = zero_step_bytes(program_counters())
    steps = traced_steps(run["spans"])
    if nbytes is None or not trace or not trace["collective_s"] or not steps:
        return None
    return nbytes / (trace["collective_s"] / steps) / 1e9
