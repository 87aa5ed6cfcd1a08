"""Delta rule by key channel (KDA): percent of the device's busy time
spent in the rule alone, from the device trace: self time of the first
device's operations under the program's scope ``kda.rule``
(``ops/gated_delta.py::gated_delta_rule`` with a decay a key channel and
what feeds it in ``_kda_block``: β, g and its running sums, the L2 norms
of q and k, the sub-blocks' differences and matmuls, the triangular
inverse, W and U, the scan over the chunks; forward, remade by the layer
and by each stretch, and backward) over its busy time. It sizes what
kernels for the vector rule could win. The rows summed go on a ``BENCH``
line (``event: scope_rows``); a program without the scope reads
nothing."""

from benchmarks.lib.gdn import share


def read(run):
    return share(run, "kda.rule_share", ("kda.rule",))
