"""A rope per layer kind: percent of the device's busy time spent in the
full layer's whole attention part, from the device trace: self time of
the first device's operations under the program's scope ``attn.full``
(``models/decoder.py::_layer_body`` of a ``Y`` layer of
``layer_types``: the projections, the per-head norms, the turning by
YaRN's table under ``attn.rope`` and the flash kernels over the whole
causal span; forward, recomputed and backward alike) over its busy time.
The rows summed go on a ``BENCH`` line (``event: scope_rows``); a traced
step with none is an error."""

from benchmarks.lib.mellum import share


def read(run):
    return share(run, "mellum.full_attn_share", ("attn.full",))
