"""Train step: model FLOP/s utilization, in percent — tokens per second
of the measured window x required FLOPs per token (the convention of
``benchmarks/lib/flops.py`` on the terms the configuration's reference
module states, resolved once by the runner; recomputation does not
count) over chips x the chip's peak bf16 rate
(``benchmarks/lib/peaks.py``)."""


def read(run):
    w = run["window"]
    if not w["steps"]:
        return None
    tokens_per_s = w["steps"] * w["tokens"] / w["seconds"]
    return 100.0 * tokens_per_s * run["required_flops_per_token"] / (
        run["chips"] * run["peaks"].bf16_flops
    )
