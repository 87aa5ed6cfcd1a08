"""A rope per layer kind: percent of the device's busy time spent in the
three window layers' whole attention parts, from the device trace: self
time of the first device's operations under the program's scope
``attn.window`` (an ``S`` layer of ``layer_types``: what
``mellum.full_attn_share`` lists, under the plain table and with the
flash kernels on the banded grid of a 1,024-key window) over its busy
time. A traced step with no such row is an error."""

from benchmarks.lib.mellum import share


def read(run):
    return share(run, "mellum.window_attn_share", ("attn.window",))
