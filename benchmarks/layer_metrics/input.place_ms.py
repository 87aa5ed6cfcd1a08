"""Data input: milliseconds inside the program's placement call
(``form_global_batch``), the median of the step clock's ticks
(``dlrover_tpu/observability/profiler.py``) over the measured window.
The inside twin of ``input.wait_ms``, which also holds the benchmark's
``synthetic_batch`` and its wait for readiness. A program without the
clock leaves the metric out."""


def read(run):
    try:
        from dlrover_tpu.observability.profiler import step_clock
    except ImportError:
        return None
    start = run["window_start"]
    seen = step_clock().window(start, start + run["window"]["seconds"])
    return None if seen is None else 1e3 * seen["place_s"]
