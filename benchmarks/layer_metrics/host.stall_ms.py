"""Host loop: milliseconds of the measured window beyond the step
period in stalled intervals — those the program's step clock
(``dlrover_tpu/observability/profiler.py``) read at over 3 × its
running median and over the median + 0.25 s, each of which left a
``host.stall`` record that names its cause — and the window's last
step, which no tick closes, where it alone is already that long. 0 in a
clean run. A program
without the clock leaves the metric out."""


def read(run):
    try:
        from dlrover_tpu.observability.profiler import step_clock
    except ImportError:
        return None
    start = run["window_start"]
    seen = step_clock().window(start, start + run["window"]["seconds"])
    return None if seen is None else 1e3 * seen["stall_s"]
