"""Delta rule by key channel (KDA): what the rule reaches of its
roofline, in percent, from the device trace: the time the chip would
need at the least for the RECURRENCE's own operations and bytes in the
traced steps (``lib/kda.py``: 21 x 128 x 128 operations a token, head
and layer, forward and backward; q, k, v, g — a float32 a head and KEY
CHANNEL — and β read and o written once a pass, the cotangents
likewise — whichever of operations over ``peaks.bf16_flops`` and bytes
over ``peaks.hbm_bytes_s`` is the longer) over the self seconds of the
first device's operations under the scope ``kda.rule``. The count is of
the recurrence and not of any chunking, sub-block or recomputation:
whatever implements the rule is held to the same work, and none can
pass 100%."""

from benchmarks.lib.kda import rule_roofline


def read(run):
    return rule_roofline(run)
