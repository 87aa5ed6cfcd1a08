"""Host loop: the longest, in milliseconds, that the process or the
interpreter lock kept a ready thread from running inside the measured
window — the largest lateness of the step clock's 20 ms beat
(``dlrover_tpu/observability/profiler.py``) over the window's ticks. A
program without the clock leaves the metric out."""


def read(run):
    try:
        from dlrover_tpu.observability.profiler import step_clock
    except ImportError:
        return None
    start = run["window_start"]
    seen = step_clock().window(start, start + run["window"]["seconds"])
    return None if seen is None else 1e3 * seen["beat_late_max_s"]
