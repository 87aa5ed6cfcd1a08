"""State-space layer: percent of the device's busy time spent in the
chunked scan alone, from the device trace: self time of the first
device's operations under the program's scope ``ssm.scan``
(``ops/ssd.py::ssd_scan``: the cumulative log-decays, the score and
decay blocks of every chunk, the chunk states and the decays between
chunks, the read-out; forward, recomputed twice (the layer's
rematerialisation and the head block's own) and backward) over its busy
time. It sizes what a scan kernel could win. The rows summed go on a
``BENCH`` line (``event: scope_rows``); a traced step with none is an
error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "ssm.scan_share", "ssm.scan")
