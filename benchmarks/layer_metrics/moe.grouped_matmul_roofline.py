"""Routed experts: what the grouped matmuls reach of the chip's peak
bf16 rate, in percent, from the device trace: the operations they
require over their self seconds, over ``peaks.bf16_flops``.

Operations of one call (``grouped_matmul_flops``). A step sends
``rows = tokens x expert_top_k`` (token, expert) rows through each of
an expert's three projections (gate, up: d_model -> d_ff; down: d_ff
-> d_model). Every ``ragged-dot`` call of the step multiplies those
rows by one such matrix per expert, 2 x rows x d_model x d_ff: the
forward call, the same call recomputed under ``full`` remat, the
input-gradient call (rows x d_ff by d_ff x d_model or the transpose:
the same product of sizes) and the weight-gradient call (for each
expert its rows' inputs transposed times their output gradients:
summed over experts again rows x d_model x d_ff multiply-adds). Dropless
routing sends every row to exactly one expert, so the count does not
depend on how the rows fall. The compiler names the matmul calls
``ragged-dot-none*``; the small ``ragged-dot-metadata*`` calls beside
them are timed with them and counted as no operations.

Bytes of one call (``grouped_matmul_bytes``), bf16: the rows read
(rows x d_model or rows x d_ff), every expert's matrix read once
(n_experts x d_model x d_ff) and the rows written. At OLMoE's widths
and 65,536 rows: 268 MB + 268 MB + 134 MB = 671 MB, 0.82 ms at 819
GB/s, against 275 GFLOP, 1.40 ms at 197 TFLOP/s: the call is compute
bound, so its roofline share is its share of the bf16 peak.
"""

def grouped_matmul_flops(rows, d_model, d_ff):
    return 2.0 * rows * d_model * d_ff


def grouped_matmul_bytes(rows, d_model, d_ff, n_experts, itemsize=2):
    return itemsize * (rows * d_model + n_experts * d_model * d_ff + rows * d_ff)


def read(run):
    trace = run["trace"]
    if not trace or not trace["per_device"]:
        return None
    # the rows moe.grouped_matmul_share sums: the first device's
    # operations named ragged-dot, label -> [self seconds, calls]
    rows = {
        label: row
        for label, row in trace["per_device"][0]["by_name"].items()
        if label.startswith("ragged-dot")
    }
    seconds = sum(s for s, _calls in rows.values())
    if not seconds:
        return None
    sizes = run["sizes"]
    per_call = grouped_matmul_flops(
        run["window"]["tokens"] * sizes["expert_top_k"],
        sizes["d_model"], sizes["d_ff"],
    )
    # ``ragged-dot-metadata`` turns the group sizes into the kernels'
    # tile tables, three times a layer: its time belongs to the grouped
    # matmuls, and it multiplies nothing
    calls = sum(
        calls for label, (_s, calls) in rows.items()
        if not label.startswith("ragged-dot-metadata")
    )
    return 100.0 * calls * per_call / seconds / run["peaks"].bf16_flops
