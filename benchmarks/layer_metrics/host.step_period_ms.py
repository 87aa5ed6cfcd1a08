"""Host loop: the step period as the program sees it — milliseconds
between one batch's entry into the device and the next's, the median of
the periods that the step clock's ticks
(``dlrover_tpu/observability/profiler.py``) closed inside the measured
window. The inside twin of ``train_step.step_ms``, which
times the step's call from outside. A program without the clock leaves
the metric out."""


def read(run):
    try:
        from dlrover_tpu.observability.profiler import step_clock
    except ImportError:
        return None
    start = run["window_start"]
    seen = step_clock().window(start, start + run["window"]["seconds"])
    return None if seen is None else 1e3 * seen["period_s"]
