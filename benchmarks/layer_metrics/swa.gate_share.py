"""Attention by layer kind: percent of the device's busy time spent in
the gate on the attention's output, from the device trace: self time of
the first device's operations under the program's scope ``attn.gate``
(``models/decoder.py::_gate_output``: the product of the layer's normed
input with ``W_g``, the sigmoid and the multiply, in layers of either
kind; forward, recomputed and backward alike) over its busy time. It
lies inside ``attn.window`` and ``attn.full`` and is counted in those
two as well. The rows summed go on a ``BENCH`` line (``event:
scope_rows``); a traced step with none is an error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "swa.gate_share", "attn.gate")
