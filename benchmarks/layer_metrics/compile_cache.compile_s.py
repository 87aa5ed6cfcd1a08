"""Compile cache: seconds jax spent tracing, lowering and compiling —
or fetching from the persistent cache — during set-up, summed from
jax's own compile-duration events. Hits and misses are on the
``setup_done`` line."""


def read(run):
    return run["setup_compile_s"]
