"""Sigmoid top-4 of 32 experts, rank 0 of four: what the grouped matmuls
at 2048 x 1792 reach of their roofline, in percent, from the device
trace: the operations over the rows the 8 held experts RECEIVED in the
traced steps (the program's ``moe_held_rows``; some 4,096 an expert),
or the bytes where those take longer, over the self seconds of the
``ragged-dot`` rows (``lib/lfm2.py``, by ``lib/mellum.py``'s account;
compute bound, so the share is of the bf16 peak)."""

from benchmarks.lib.lfm2 import grouped_matmul_roofline


def read(run):
    return grouped_matmul_roofline(run)
