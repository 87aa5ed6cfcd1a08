"""ZeRO exchange: megabytes one rank moves for ZeRO in one step, from the
program's counters (set when the step is traced, from the pack plan):
the gradient stream handed to the bucketed exchange
(``zero.exchange_bytes``: buckets x bucket elements x wire bytes, the
tied head's buckets included) plus the parameter stream gathered back
after the sharded update (``zero.gather_bytes``)."""

from benchmarks.lib.counters import program_counters, zero_step_bytes


def read(run):
    nbytes = zero_step_bytes(program_counters())
    return None if nbytes is None else nbytes / 1e6
