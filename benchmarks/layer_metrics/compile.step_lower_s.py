"""Compile cache: seconds the program spent lowering the train step's
jaxpr to MLIR, its Mosaic kernels included: paid on a warm start as on
a cold one, since the cache's key is the lowered text.
The program's own counter ``compile.step.lower_s``
(``dlrover_tpu/common/compile_cache.py``); a program without it leaves
the metric out."""

from benchmarks.lib.counters import program_counters


def read(run):
    return program_counters().get("compile.step.lower_s")
