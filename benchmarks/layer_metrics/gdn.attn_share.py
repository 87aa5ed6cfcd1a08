"""Gated attention: percent of the device's busy time spent in the full
layers' attention parts, from the device trace: self time of the first
device's operations under the program's scope ``attn``
(``models/decoder.py::_part_body`` on a ``*`` part: the norm, q, k, v
and their per-head norms, the partial rope, the flash kernels, the
sigmoid gate ``attn.gate`` and ``W_o``; forward, recomputed and backward
alike) over its busy time. The rows summed go on a ``BENCH`` line
(``event: scope_rows``); a program without the scope reads nothing."""

from benchmarks.lib.gdn import share


def read(run):
    return share(run, "gdn.attn_share", ("attn",))
