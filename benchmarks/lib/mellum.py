"""What the flash kernels and the grouped matmuls of the cell that runs
a rope per layer kind HAVE to do, and its readers' shared parts
(``mellum.*``; PR 70). The scope sums' form is ``lib/gdn.py``'s, but a
scope without a row is an ERROR here: the metrics are listed for the
one cell whose program has the scopes, and one that vanished must not
read as a metric left out.

The flash kernels (``ops/pallas_attention.py``: ``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv``) run in both kinds of layer, and a
call's kind is its ``op_name``'s scope: under ``attn.window`` query i
attends to min(i + 1, ``attn_window``) keys,
``lib/flops.mean_span(seq, attn_window)`` a query (1,008.02 at 32,768
tokens and a window of 1,024); under ``attn.full`` to i + 1 (16,384.5).
A (query, key) pair of a head costs 2 x ``head_dim`` operations in each
product a kernel makes (``PRODUCTS``: forward scores and p v; dq the
scores again, dp and dq; dkv the scores again, dp, dv and dk). The
masked part of every block the kernels touch — the upper half of a
diagonal block, the keys of a band's blocks outside the window — is
executed and NOT counted, so a share cannot read high. The rope's
amplitude and blend change no count: q and k arrive turned.

Bytes of a call at the least (``ARRAYS``), bf16: forward q and out at
``n_head`` heads, k and v at ``n_kv_head``; dq: q, dO, dq | k, v; dkv:
q, dO | k, v, dk, dv. At 1 x 32,768 x 32 / 4 x 128 a forward call moves
604 MB, 0.74 ms at 819 GB/s, against 2 x 2 x 128 x 32 x 32,768 x
1,008 = 0.54 TFLOP, 2.7 ms at 197 TFLOP/s, in a window layer and 8.8
TFLOP, 44.6 ms, in the full one: compute bound in both kinds.

The grouped matmuls (``lax.ragged_dot``, the compiler's ``ragged-dot``
rows) multiply the rows the held experts RECEIVED, which the program's
step metric ``moe_held_rows`` counts (mean over the layers), by one
2304 x 896 matrix an expert: 2 x rows x ``d_model`` x ``d_expert`` a
call, forward, recomputed, input-gradient and weight-gradient alike
(``moe.grouped_matmul_roofline``'s account); bytes: the rows in and
out once and every held expert's matrix once. At 65,536 rows: 271
GFLOP, 1.37 ms, against 485 MB, 0.59 ms: compute bound.
"""

from benchmarks.lib.flops import mean_span
from benchmarks.lib.gdn import first_device, traced_steps
from benchmarks.lib.trace import has_scope, scope_seconds

# products per (head, query, key) pair, by kernel; the longer name first
PRODUCTS = (("flash_bwd_dkv", 4), ("flash_bwd_dq", 3), ("flash_fwd", 2))
# arrays [batch, seq, heads, head_dim] a call moves at the least, as
# (at n_head heads, at n_kv_head heads)
ARRAYS = {"flash_fwd": (2, 2), "flash_bwd_dq": (3, 2), "flash_bwd_dkv": (2, 4)}
# the program's scope of a layer kind -> whether its window is live
KINDS = (("attn.window", True), ("attn.full", False))


def scope_rows(run, metric, scopes):
    """(self seconds under any of ``scopes``, a row counted once; the
    first device's busy seconds) of a traced run; the rows found under
    each scope go on a ``BENCH`` line (``event: scope_rows``). None
    without a device trace; a traced step with no row under one of the
    scopes is an error."""
    first = first_device(run)
    if first is None:
        return None
    rows = {scope: scope_seconds(first, (scope,)) for scope in scopes}
    run["say"](
        event="scope_rows", metric=metric, busy_s=first["busy_s"],
        modules=first.get("modules"),
        rows={k: [len(v), sum(v.values())] for k, v in rows.items()},
    )
    missing = [scope for scope, found in rows.items() if not found]
    if missing:
        raise LookupError(
            f"no operation of the traced step under {', '.join(missing)}"
        )
    if not first["busy_s"]:
        return None
    return sum(scope_seconds(first, scopes).values()), first["busy_s"]


def share(run, metric, scopes):
    """Percent of the first device's busy time under ``scopes``."""
    got = scope_rows(run, metric, scopes)
    return None if got is None else 100.0 * got[0] / got[1]


def flash_call_flops(kernel, sizes, batch, seq, window):
    """Operations one call of ``kernel`` has to execute: its products
    of 2 x head_dim operations over the useful pairs of every head of
    every sequence, under a window of ``window`` keys (0 = none)."""
    pairs = batch * sizes["n_head"] * seq * mean_span(seq, window)
    return dict(PRODUCTS)[kernel] * 2.0 * pairs * sizes["head_dim"]


def flash_call_bytes(kernel, sizes, batch, seq, itemsize=2):
    """Bytes one call has to move at the least, bf16."""
    wide, narrow = ARRAYS[kernel]
    heads = wide * sizes["n_head"] + narrow * sizes["n_kv_head"]
    return float(batch * seq * sizes["head_dim"] * itemsize * heads)


def _kind_window(paths, window):
    """The window of the layer a kernel's row ran in, from the
    ``op_name`` paths of its instruction; None where they name no
    kind."""
    for scope, windowed in KINDS:
        if any(has_scope(path, scope) for path in paths):
            return window if windowed else 0
    return None


def flash_roofline(run):
    """Percent of their roofline the flash kernels of both kinds reach:
    for every call the trace counts, the longer of its required
    operations over the bf16 peak and its bytes over the memory's peak,
    over the kernels' self seconds. None without a device trace; a
    traced step without a flash kernel, or with one under neither kind's
    scope, is an error."""
    first = first_device(run)
    if first is None:
        return None
    sizes, seq = run["sizes"], run["seq"]
    batch = run["window"]["tokens"] // seq
    seconds = floor = 0.0
    found = {}
    for label, (self_s, calls) in first["by_name"].items():
        kernel = next((k for k, _ in PRODUCTS if label.startswith(k)), None)
        if kernel is None:
            continue
        paths = (first.get("op_names") or {}).get(label, {})
        window = _kind_window(paths, sizes["attn_window"])
        if window is None:
            raise LookupError(
                f"{label!r} ran under neither "
                f"{' nor '.join(scope for scope, _ in KINDS)}"
            )
        seconds += self_s
        floor += calls * max(
            flash_call_flops(kernel, sizes, batch, seq, window)
            / run["peaks"].bf16_flops,
            flash_call_bytes(kernel, sizes, batch, seq)
            / run["peaks"].hbm_bytes_s,
        )
        found[label] = [self_s, calls, window]
    run["say"](event="flash_rows", metric="mellum.flash_roofline", rows=found)
    if not seconds:
        raise LookupError("no flash_* row in the traced step")
    return 100.0 * floor / seconds


def grouped_matmul_flops(rows, sizes):
    return 2.0 * rows * sizes["d_model"] * sizes["d_expert"]


def grouped_matmul_bytes(rows, sizes, itemsize=2):
    d, f = sizes["d_model"], sizes["d_expert"]
    return float(itemsize) * (
        rows * d + sizes["n_experts_held"] * d * f + rows * f
    )


def received_rows(run):
    """Mean over the traced steps of the rows the held experts received
    in a layer (the program's ``moe_held_rows``); None where the program
    reports none or no step was traced."""
    rows = run.get("step_metrics", {}).get("moe_held_rows")
    steps = traced_steps(run["spans"])
    if not rows or not steps:
        return None
    traced = rows[-steps:]
    return sum(traced) / len(traced)


def grouped_matmul_roofline(run):
    """Percent of their roofline the ``ragged-dot`` calls reach over the
    rows the held experts RECEIVED: the longer of a call's operations
    over the bf16 peak and its bytes over the memory's peak, every
    matmul call the trace counts, over the self seconds of the
    ``ragged-dot`` rows (the small ``ragged-dot-metadata`` calls are
    timed with them and multiply nothing)."""
    first, rows = first_device(run), received_rows(run)
    if first is None or not rows:
        return None
    timed = {
        label: row for label, row in first["by_name"].items()
        if label.startswith("ragged-dot")
    }
    seconds = sum(s for s, _calls in timed.values())
    if not seconds:
        raise LookupError("no ragged-dot row in the traced step")
    calls = sum(
        calls for label, (_s, calls) in timed.items()
        if not label.startswith("ragged-dot-metadata")
    )
    sizes = run["sizes"]
    floor = calls * max(
        grouped_matmul_flops(rows, sizes) / run["peaks"].bf16_flops,
        grouped_matmul_bytes(rows, sizes) / run["peaks"].hbm_bytes_s,
    )
    run["say"](
        event="ragged_dot_rows", metric="mellum.grouped_matmul_roofline",
        rows=[len(timed), seconds], calls=calls, received=rows,
    )
    return 100.0 * floor / seconds
