"""Compile cache: seconds tracing, lowering and compiling (or fetching)
every program of the process that is neither the train step nor the
state's initialisation. In a benchmark run that is the correctness
check's programs and the plain reference: the benchmark's own share of
``setup_s``.
The program's own counter ``compile.other.s``
(``dlrover_tpu/common/compile_cache.py``); a program without it leaves
the metric out."""

from benchmarks.lib.counters import program_counters


def read(run):
    return program_counters().get("compile.other.s")
