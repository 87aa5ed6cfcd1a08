"""Selective-scan layer: percent of the device's busy time spent in the
selective scan alone, from the device trace: self time of the first
device's operations under the program's scope ``ssm1.scan``
(``ops/selective_scan.py::selective_scan``: the recurrence over the
tokens of every chunk on the carried state, the read-out, the layout
copies around them; forward, the layer's remade forward, and in the
backward the remade chunk states and the walk back) over its busy time.
It sizes what a scan kernel could win. The rows summed go on a
``BENCH`` line (``event: scope_rows``); a traced step with none is an
error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "ssm1.scan_share", "ssm1.scan")
