"""Train step: milliseconds from the call of the compiled step to the
host readback of its loss (host clock), median over the measured
window's steps."""

import statistics


def read(run):
    steps = run["spans"].durations("step", since=run["window_start"])
    return 1e3 * statistics.median(steps) if steps else None
