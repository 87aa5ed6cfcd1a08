"""What the flash kernels that take a selection (``flash_*_sel``) reach
of the chip's peak bf16 rate on the pairs a model HAD to compute, from
the device trace: the reading ``layer_metrics/dsa.flash_roofline.py``
makes for a selection by keys, for a reader that names its own pairs a
query (``sala.flash_roofline``: by blocks).

A (query, key) pair costs 2 x channels operations in each product a
kernel makes, for each head. The products, per head and pair
(``PRODUCTS``): ``flash_fwd_sel`` scores and p v: 2;
``flash_bwd_dq_sel`` the scores again, dp and dq: 3;
``flash_bwd_dkv_sel`` the scores again, dp, dv and dk: 4. One call runs
the whole batch and every head; the calls are counted from the trace
(under full rematerialisation ``flash_fwd_sel`` may run twice a layer).
"""

# products per (head, query, key) pair, by kernel; the longer name first
PRODUCTS = (
    ("flash_bwd_dkv_sel", 4), ("flash_bwd_dq_sel", 3), ("flash_fwd_sel", 2),
)


def peak_share(run, useful):
    """Percent of ``peaks.bf16_flops`` the ``flash_*_sel`` rows of a
    traced run reach on the pairs a model had to compute:
    ``useful(sizes, seq)`` -> (heads, channels a head, pairs a query and
    head). None without a device trace; raises where the traced step has
    no such row."""
    trace = run["trace"]
    if not trace or not trace["per_device"]:
        return None
    heads, channels, pairs = useful(run["sizes"], run["seq"])
    pairs *= run["window"]["tokens"] * heads
    seconds = flops = 0.0
    for label, (self_s, calls) in trace["per_device"][0]["by_name"].items():
        for kernel, products in PRODUCTS:
            if label.startswith(kernel):
                seconds += self_s
                flops += calls * products * 2.0 * pairs * channels
                break
    if not seconds:
        raise LookupError("no flash_*_sel row in the traced step")
    return 100.0 * flops / seconds / run["peaks"].bf16_flops
