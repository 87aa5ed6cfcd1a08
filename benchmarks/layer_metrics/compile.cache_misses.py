"""Compile cache: executables of the process that the backend compiled
and did not fetch from the persistent cache. 0 on a warm run: it tells a
warm ``setup_s`` from a cold one, which the seconds alone cannot.
The program's own counter ``compile.cache_misses``
(``dlrover_tpu/common/compile_cache.py``); a program without it leaves
the metric out."""

from benchmarks.lib.counters import program_counters


def read(run):
    return program_counters().get("compile.cache_misses")
