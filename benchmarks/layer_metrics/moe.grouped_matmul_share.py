"""Routed experts: percent of the device's busy time spent inside the
grouped matmuls of the expert layers, from the device trace: self time
of the first device's operations whose label starts ``ragged-dot``
(``lax.ragged_dot`` lowers to the compiler's own ``tpu_custom_call`` of
that name: forward, recomputed, input-gradient and weight-gradient
calls alike) over its busy time. Sort, gather and combine are not in
it: they are XLA fusions the benchmark cannot tell by name yet."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["per_device"]:
        return None
    first = trace["per_device"][0]
    seconds = sum(
        row[0] for label, row in first["by_name"].items()
        if label.startswith("ragged-dot")
    )
    if not seconds or not first["busy_s"]:
        return None
    return 100.0 * seconds / first["busy_s"]
