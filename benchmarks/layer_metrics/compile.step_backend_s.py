"""Compile cache: seconds the train step spent in the backend: compiling
on a miss; reading, deserializing and loading the executable on a hit.
The program's own counter ``compile.step.backend_s``
(``dlrover_tpu/common/compile_cache.py``); a program without it leaves
the metric out."""

from benchmarks.lib.counters import program_counters


def read(run):
    return program_counters().get("compile.step.backend_s")
