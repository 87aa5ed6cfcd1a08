"""Attention by layer kind: percent of the device's busy time spent in
the full layers' whole attention part, from the device trace: self time
of the first device's operations under the program's scope
``attn.full`` (``models/decoder.py::_layer_body`` of an ``F`` layer of
``layer_types``: what ``swa.window_share`` lists, without rope and with
the flash kernels over the whole causal span; forward, recomputed and
backward alike) over its busy time. The rows summed go on a ``BENCH``
line (``event: scope_rows``); a traced step with none is an error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "swa.full_share", "attn.full")
