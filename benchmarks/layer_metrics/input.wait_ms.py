"""Data input: milliseconds a step waits for its batch to be built on
the host and placed on the device (the benchmark's own ``input`` span
around ``synthetic_batch`` + ``form_global_batch``), median over the
measured window's steps."""

import statistics


def read(run):
    waits = run["spans"].durations("input", since=run["window_start"])
    return 1e3 * statistics.median(waits) if waits else None
