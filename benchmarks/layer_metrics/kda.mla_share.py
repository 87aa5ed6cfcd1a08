"""Latent attention without positions: percent of the device's busy time
spent in the full layer's attention part, from the device trace: self
time of the first device's operations under the program's scope ``attn``
(``models/decoder.py::_part_body`` on a ``*`` part: the norm, q at full
rank, the latent's down- and up-projection ``attn.latent``, v's padding
to the score width, the flash kernels, the cut and ``W_o``; forward,
recomputed and backward alike) over its busy time. The rows summed go
on a ``BENCH`` line (``event: scope_rows``); a program without the scope
reads nothing."""

from benchmarks.lib.gdn import share


def read(run):
    return share(run, "kda.mla_share", ("attn",))
