"""A rope per layer kind: percent of the device's busy time spent
building the two tables and turning q and k by them, both kinds, from
the device trace: self time of the first device's operations whose
``op_name`` has the program's scope ``attn.rope``
(``models/decoder.py::_rope_tables`` and the turning in
``_project_qkv``; forward, recomputed and backward alike) over its busy
time. A traced step with no such row is an error.

WHAT KIND OF READING IT IS: a fusion goes by ONE ``op_name``, its
root's (``lib/trace.op_names``), and the compiler fuses the turning
with its neighbours, so this is the share of the fusions whose ROOT is
rope's — neither an upper nor a lower reading of the rope's own work.
In the cell's step compiled for a described v5e (PR 70, second round:
75 fusions hold an instruction of the scope) it errs both ways: 49
counted fusions are rope's alone (2 more, of the tables' build, nearly);
16 are counted WHOLE though one instruction of six is rope's (the
per-head norm's multiply, whose root is the convert to float32 the
turning opens with; q's eight move 1.07 GB each: 1.3 ms at the
memory's peak, 2.6 by the compiler's estimate, k's an eighth of that —
too HIGH by 12-24 ms a step); and 8 are LEFT OUT though four of six
are rope's (the derivative of the split into halves: two pads and
their sum under a root convert of the layer's; the same sizes, half as
many — too LOW by 6-12 ms). Of the 55 ms read, then, some 6-12 are
not the rope's, and the compiler's choice of root can move the number
with no change to the rope. A reading that splits a fusion
needs per-instruction times the trace does not have."""

from benchmarks.lib.mellum import share


def read(run):
    return share(run, "mellum.rope_share", ("attn.rope",))
