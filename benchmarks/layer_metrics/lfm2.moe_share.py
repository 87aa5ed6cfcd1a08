"""Sigmoid top-4 of 32 experts, rank 0 of four: percent of the device's
busy time spent in the four routed blocks, from the device trace: self
time under the program's scopes ``moe.route`` (the 32-wide router in
float32, sigmoid, top-4, renormalised), ``moe.sort``, ``moe.experts``
and ``moe.combine``, forward, recomputed and backward alike, and of the
grouped matmuls over the held rows, which carry no scope and are taken
by their label (``lib/lfm2.py``), over the busy time."""

from benchmarks.lib.lfm2 import moe_share


def read(run):
    return moe_share(run)
