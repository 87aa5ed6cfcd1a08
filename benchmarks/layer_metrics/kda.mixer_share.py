"""Delta rule by key channel (KDA): percent of the device's busy time
spent in the KDA mixers, from the device trace: self time of the first
device's operations under the program's scope ``kda``
(``models/decoder.py::_part_body`` and ``_kda_block``: the layer's norm,
the projections and the two low-rank pairs, the conv, the decays and
write strengths, the L2 norms, the rule, the output norm and sigmoid
gate, the out-projection; forward, recomputed and backward alike) over
its busy time. The rows summed go on a ``BENCH`` line (``event:
scope_rows``); a program without the scope reads nothing."""

from benchmarks.lib.gdn import share


def read(run):
    return share(run, "kda.mixer_share", ("kda",))
