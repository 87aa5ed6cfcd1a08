"""Attention by layer kind: percent of the device's busy time spent in
the sliding-window layers' whole attention part, from the device trace:
self time of the first device's operations under the program's scope
``attn.window`` (``models/decoder.py::_layer_body`` of an ``S`` layer of
``layer_types``: the input norm, the q, k, v and gate projections, the
per-head norms, rope, the flash kernels with the window as a static
operand, the gate, the output projection and the norm on it; forward,
recomputed and backward alike) over its busy time. The rows summed go
on a ``BENCH`` line (``event: scope_rows``); a traced step with none is
an error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "swa.window_share", "attn.window")
