"""Selective-scan layer: percent of the device's busy time spent making
the scan's operands, from the device trace: self time of the first
device's operations under the program's scope ``ssm1.dbc``
(``models/decoder.py::_mamba1_block``: the projection to [Δ's rank | B
| C], the three RMSNorms, the projection of the rank to every channel,
the softplus; forward, recomputed and backward alike) over its busy
time: 192 and 160 columns against 5,120 channels are thin matmuls. The
rows summed go on a ``BENCH`` line (``event: scope_rows``); a traced
step with none is an error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "ssm1.dbc_share", "ssm1.dbc")
