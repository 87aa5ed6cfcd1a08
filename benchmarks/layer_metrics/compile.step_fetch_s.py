"""Compile cache: of the train step's backend seconds, the part jax
reports as retrieval from the persistent cache (0 on a miss): what a hit
costs, the floor a warm restart cannot go under without keeping the
executable loaded.
The program's own counter ``compile.step.fetch_s``
(``dlrover_tpu/common/compile_cache.py``); a program without it leaves
the metric out."""

from benchmarks.lib.counters import program_counters


def read(run):
    return program_counters().get("compile.step.fetch_s")
