"""Softmax top-8 of 64 experts, rank 0 of four: what the grouped matmuls
at 2304 x 896 reach of their roofline, in percent, from the device
trace: the operations over the rows the 16 held experts RECEIVED in the
traced steps (the program's ``moe_held_rows``; some 4,096 an expert
where the other routed cells give 1,024 or fewer), or the bytes where
those take longer, over the self seconds of the ``ragged-dot`` rows
(``lib/mellum.py``; compute bound, so the share is of the bf16
peak)."""

from benchmarks.lib.mellum import grouped_matmul_roofline


def read(run):
    return grouped_matmul_roofline(run)
