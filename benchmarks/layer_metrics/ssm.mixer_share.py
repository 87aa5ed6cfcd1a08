"""State-space layer: percent of the device's busy time spent in the
Mamba-2 mixers, from the device trace: self time of the first device's
operations under the program's scope ``ssm``
(``models/decoder.py::_part_body`` and ``_mamba_block``: the layer's
norm, the in-projection, the conv, the time step, the chunked scan, the
skip, the gated group norm and the out-projection; forward, recomputed
and backward alike) over its busy time. The rows summed go on a
``BENCH`` line (``event: scope_rows``); a traced step with none is an
error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "ssm.mixer_share", "ssm")
