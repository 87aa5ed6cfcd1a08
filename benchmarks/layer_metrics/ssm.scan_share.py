"""State-space layer: percent of the device's busy time spent in the
chunked scan alone, from the device trace: self time of the first
device's operations under the program's scope ``ssm.scan``
(``ops/ssd.py::ssd_scan``: the cumulative log-decays, the score and
decay blocks of every chunk, the chunk states and the decays between
chunks, the read-out; in the XLA body forward, recomputed twice (the
layer's rematerialisation and the head block's own) and backward; in the
Pallas kernels, which the Nemotron cell runs since PR 50, ``ssd_fwd``
forward and remade once, ``ssd_states`` and ``ssd_bwd``) over its busy
time. The reader sums the scope whatever runs under it. It sized what a
scan kernel could win, and says what is left. The rows summed go on a
``BENCH`` line (``event: scope_rows``); a traced step with none is an
error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "ssm.scan_share", "ssm.scan")
