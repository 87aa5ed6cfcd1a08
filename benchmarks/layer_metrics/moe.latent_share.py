"""Routed experts: percent of the device's busy time spent in the two
projections around the experts' latent, from the device trace: self
time of the first device's operations under the program's scope
``moe.latent`` (``parallel/moe.py::_ragged_tokens``: d_model -> latent
before the dispatch, latent -> d_model once after the combine; forward,
recomputed and backward alike) over its busy time. The rows summed go on
a ``BENCH`` line (``event: scope_rows``); a traced step with none is an
error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "moe.latent_share", "moe.latent")
