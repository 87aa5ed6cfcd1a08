"""Gated delta rule: percent of the device's busy time spent in the
gated-delta-rule mixers, from the device trace: self time of the first
device's operations under the program's scope ``gdn``
(``models/decoder.py::_part_body`` and ``_gdn_block``: the layer's norm,
the two in-projections, the conv, the decays and write strengths, the
L2 norms, the rule, the output norm and gate, the out-projection;
forward, recomputed and backward alike) over its busy time. The rows
summed go on a ``BENCH`` line (``event: scope_rows``); a program without
the scope reads nothing."""

from benchmarks.lib.gdn import share


def read(run):
    return share(run, "gdn.mixer_share", ("gdn",))
