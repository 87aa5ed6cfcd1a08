"""Compile cache: seconds the program spent tracing the train step's
Python to a jaxpr, the jitted functions it calls counted inside it and
not again: what a serialized executable would skip, whatever the cache
holds.
The program's own counter ``compile.step.trace_s``
(``dlrover_tpu/common/compile_cache.py``); a program without it leaves
the metric out."""

from benchmarks.lib.counters import program_counters


def read(run):
    return program_counters().get("compile.step.trace_s")
