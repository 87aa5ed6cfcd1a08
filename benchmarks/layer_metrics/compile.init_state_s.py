"""Compile cache: seconds tracing, lowering and compiling (or fetching)
the programs ``init_train_state`` made: the seeded initialisation of the
parameters and the optimizer's state.
The program's own counter ``compile.init_state.s``
(``dlrover_tpu/common/compile_cache.py``); a program without it leaves
the metric out."""

from benchmarks.lib.counters import program_counters


def read(run):
    return program_counters().get("compile.init_state.s")
