"""Latent attention: percent of the device's busy time spent in what
latent attention (MLA) adds around the flash kernels, from the device
trace: self time of the first device's operations under the program's
scope ``attn.latent`` (``models/decoder.py::_latent_qkv``: both
down-projections, the two rank norms, both up-projections, rope on the
rope channels, the broadcast of the shared rope key and the two
concatenations; forward, recomputed and backward alike) over its busy
time. The flash calls and the output projection are not in it. The
rows summed go on a ``BENCH`` line (``event: scope_rows``); a traced
step with none is an error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "mla.latent_share", "attn.latent")
