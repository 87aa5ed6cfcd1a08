"""Selective-scan layer: percent of the device's busy time spent in the
Mamba-1 mixers, from the device trace: self time of the first device's
operations under the program's scope ``ssm1``
(``models/decoder.py::_part_body`` and ``_mamba1_block``: the part's
norm, the in-projection, the conv, the low-rank projection with its
three norms and the time step, the selective scan, the skip, the gate
and the out-projection; forward, recomputed and backward alike) over
its busy time. The layer's MLP is another part and is not in it. The
rows summed go on a ``BENCH`` line (``event: scope_rows``); a traced
step with none is an error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "ssm1.mixer_share", "ssm1")
