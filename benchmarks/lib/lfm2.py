"""What the gated short convolution HAS to move, what the flash kernels
have to do at heads of 64 on 32 / 8 heads and the grouped matmuls at
2048 x 1792, and the readers' shared parts for the cell that runs them
(``lfm2.*``; PR 73). The scope sums are ``lib/gdn.py``'s: a scope
without a row reads NOTHING and raises nothing, so a program without
the ``C`` part (a parent of PR 73) leaves the metric out of the line.

The gated conv (``ops/ssd.py::gated_conv``, scope ``conv.gate``):
``y = C * conv(B * x)`` over d channels of the in-projection's
``[B | C | x]``. One pass HAS to read the three windows and write y:
4 array-passes of ``batch x seq x d x 2`` bytes (bf16) going forward;
going back it has to read y's cotangent and the three windows again and
write the three cotangents: 7. The taps and their gradient are 12 KB.
The count is of the OPERATION and of no implementation — a body that
writes B * x out and reads it back, pads, casts the whole array or
copies a window moves more and reads a lower share, a fused one cannot
read above 100% — so a later change of kernel leaves the yardstick as it
is. Passes a traced step: a forward and, under ``remat: full``, a
recomputed forward, and a backward, each conv layer
(``sizes["layer_pattern"].count("C")``). At 8 x 4,096 x 2,048 an
array-pass is 134 MB: 537 MB = 0.66 ms forward, 940 MB = 1.15 ms
backward at 819 GB/s; the arithmetic (some 40 float32 operations an
element both ways) is under that on the vector unit's paper, so the
memory bounds it.

The flash kernels (``ops/pallas_attention.py``) run in the one
attention layer of the six, causal, no window: a (query, key) pair of a
head costs 2 x ``head_dim`` operations in each product a kernel makes
and a call moves q, k, v, out (and their cotangents) once at the least —
``lib/mellum.py``'s two functions, taken as they are, at a window of 0.
At 8 x 4,096 x 32 / 8 x 64 a forward call is 2 x 2 x 64 x 32 x 8 x
4,096 x 2,048.5 = 0.55 TFLOP, 2.8 ms at 197 TFLOP/s, against 84 MB,
0.10 ms: compute bound. Masked pairs the kernels execute are not
counted.

The grouped matmuls (``lax.ragged_dot``): ``lib/mellum.py``'s account
(2 x rows x ``d_model`` x ``d_expert`` a call over the rows the held
experts RECEIVED; bytes: the rows in and out once, every held expert's
matrix once) at 2048 x 1792 and 8 held experts. At 32,768 rows: 240
GFLOP, 1.22 ms, against 310 MB, 0.38 ms: compute bound.
"""

from benchmarks.lib import mellum
from benchmarks.lib.gdn import first_device, scope_rows, traced_steps
from benchmarks.lib.trace import has_scope, scope_seconds

# array-passes the gated conv has to make, forward and backward
FORWARD_PASSES, BACKWARD_PASSES = 4, 7
MOE_SCOPES = ("moe.route", "moe.sort", "moe.experts", "moe.combine")


def conv_layers(sizes):
    return sizes["layer_pattern"].count("C")


def gated_conv_bytes(sizes, batch, seq, steps, itemsize=2):
    """Bytes ``steps`` training steps have to move through the gated
    conv, every conv layer: a forward (twice under ``remat: full``) and
    a backward."""
    forwards = 2 if sizes["remat"] == "full" else 1
    passes = forwards * FORWARD_PASSES + BACKWARD_PASSES
    one = float(batch * seq * sizes["d_model"] * itemsize)
    return passes * one * conv_layers(sizes) * steps


def gated_conv_roofline(run, metric="lfm2.gated_conv_roofline"):
    """Percent of the memory's peak the gated conv reaches in the traced
    steps: the bytes it has to move over the self seconds under
    ``conv.gate``. The kernels' rows, where the program ran kernels, go
    on a ``BENCH`` line beside it (``event: gated_conv_rows``)."""
    got = scope_rows(run, metric, ("conv.gate",))
    steps = traced_steps(run["spans"])
    if got is None or not steps:
        return None
    first = first_device(run)
    run["say"](
        event="gated_conv_rows", metric=metric, steps=steps,
        rows={
            label: list(row) for label, row in first["by_name"].items()
            if label.startswith("gated_conv_")
        },
    )
    seq = run["seq"]
    batch = run["window"]["tokens"] // seq
    floor = gated_conv_bytes(run["sizes"], batch, seq, steps) / (
        run["peaks"].hbm_bytes_s
    )
    return 100.0 * floor / got[0]


def dense_mlp_share(run, metric="lfm2.dense_mlp_share"):
    """Percent of the first device's busy time under the scope ``mlp``
    and under NONE of the routed block's own (``moe.*``): the dense
    MLPs' three matmuls, their activation and their norms. The routed
    parts run under ``mlp`` too, so their four input norms are read with
    it; the grouped matmuls carry no scope and are not."""
    first = first_device(run)
    if first is None or not first["busy_s"]:
        return None
    seconds = sum(
        s
        for paths in (first.get("op_names") or {}).values()
        for path, s in paths.items()
        if has_scope(path, "mlp")
        and not any(has_scope(path, scope) for scope in MOE_SCOPES)
    )
    run["say"](event="scope_rows", metric=metric, rows={"mlp": seconds})
    if not seconds:
        return None
    return 100.0 * seconds / first["busy_s"]


def _ragged_dot_rows(first, but=()):
    return {
        label: row for label, row in first["by_name"].items()
        if label.startswith("ragged-dot") and label not in but
    }


def moe_share(run, metric="lfm2.moe_share"):
    """Percent of the busy time in the routed blocks: the ``moe.*``
    scopes and the grouped matmuls, which carry no scope and are taken
    by their label, a row found both ways counted once
    (``mellum.moe_share``'s way)."""
    got = scope_rows(run, metric, MOE_SCOPES)
    if got is None:
        return None
    first = first_device(run)
    matmuls = _ragged_dot_rows(first, scope_seconds(first, MOE_SCOPES))
    seconds = sum(s for s, _calls in matmuls.values())
    run["say"](
        event="ragged_dot_rows", metric=metric, rows=[len(matmuls), seconds],
    )
    return 100.0 * (got[0] + seconds) / got[1]


def grouped_matmul_roofline(run, metric="lfm2.grouped_matmul_roofline"):
    """``mellum.grouped_matmul_roofline``'s account at this cell's
    widths; None where the traced step has no ``ragged-dot`` row."""
    first, rows = first_device(run), mellum.received_rows(run)
    if first is None or not rows:
        return None
    timed = _ragged_dot_rows(first)
    seconds = sum(s for s, _calls in timed.values())
    if not seconds:
        return None
    calls = sum(
        calls for label, (_s, calls) in timed.items()
        if not label.startswith("ragged-dot-metadata")
    )
    sizes = run["sizes"]
    floor = calls * max(
        mellum.grouped_matmul_flops(rows, sizes) / run["peaks"].bf16_flops,
        mellum.grouped_matmul_bytes(rows, sizes) / run["peaks"].hbm_bytes_s,
    )
    run["say"](
        event="ragged_dot_rows", metric=metric, rows=[len(timed), seconds],
        calls=calls, received=rows,
    )
    return 100.0 * floor / seconds


def flash_roofline(run, metric="lfm2.flash_roofline"):
    """Percent of their roofline the flash kernels reach at heads of 64:
    each call's REQUIRED pair operations under the causal mask, or its
    bytes where those take longer, over the kernels' self seconds. None
    where the traced step has no flash kernel."""
    first = first_device(run)
    if first is None:
        return None
    sizes, seq = run["sizes"], run["seq"]
    batch = run["window"]["tokens"] // seq
    seconds = floor = 0.0
    found = {}
    for label, (self_s, calls) in first["by_name"].items():
        kernel = next(
            (k for k, _ in mellum.PRODUCTS if label.startswith(k)), None
        )
        if kernel is None:
            continue
        seconds += self_s
        floor += calls * max(
            mellum.flash_call_flops(kernel, sizes, batch, seq, 0)
            / run["peaks"].bf16_flops,
            mellum.flash_call_bytes(kernel, sizes, batch, seq)
            / run["peaks"].hbm_bytes_s,
        )
        found[label] = [self_s, calls]
    run["say"](event="flash_rows", metric=metric, rows=found)
    if not seconds:
        return None
    return 100.0 * floor / seconds
