"""Process start: seconds from the start of the process, as the operating
system has it, to the first ``TrainStepBuilder`` constructed: the
interpreter, the imports, the TPU runtime's start, the mesh. The host's
part of ``setup_s``, before the program compiles anything.
The program's own counter ``setup.before_build_s``
(``dlrover_tpu/common/compile_cache.py``); a program without it leaves
the metric out."""

from benchmarks.lib.counters import program_counters


def read(run):
    return program_counters().get("setup.before_build_s")
