"""Block-sparse attention: percent of the device's busy time spent in
the sparse (``minicpm4``) part whole, from the device trace: self time
of the first device's operations under the program's scope ``attn``
(``decoder._part_body``'s ``S`` part: its norm, the projections, the
per-head norms, the selection, the units expanded to an int8 key mask,
the ``_sel`` flash kernels, the gate and ``W_o``; forward, recomputed
and backward) over its busy time. The model has no other attention. The
rows summed go on a ``BENCH`` line (``event: scope_rows``); a traced
step with none is an error."""

from benchmarks.layer_metrics.scope_share import share


def read(run):
    return share(run, "sala.sparse_attn_share", "attn")
