"""Linear attention: percent of the device's busy time spent in the
lightning recurrence alone, from the device trace: self time of the
first device's operations under the program's scope ``ssm.scan``
(``ops/ssd.py::ssd_scan`` at one head of 128 channels a group over a
state of 128: the cumulative log-decays and, in the Pallas kernels,
``ssd_fwd`` forward and remade once, ``ssd_states`` and ``ssd_bwd``; the
model has no other scan) over its busy time. The reader sums the scope
whatever runs under it. The rows summed go on a ``BENCH`` line
(``event: scope_rows``); a traced step with none is an error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "sala.scan_share", "ssm.scan")
