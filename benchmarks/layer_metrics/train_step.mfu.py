"""Train step: model FLOP/s utilization, in percent — tokens per second
of the measured window x required FLOPs per token
(``benchmarks/lib/flops.py``; recomputation does not count) over
chips x the chip's peak bf16 rate (``benchmarks/lib/peaks.py``)."""

from benchmarks.lib.flops import required_flops_per_token


def read(run):
    w = run["window"]
    if not w["steps"]:
        return None
    tokens_per_s = w["steps"] * w["tokens"] / w["seconds"]
    need = required_flops_per_token(run["sizes"], run["seq"])
    return 100.0 * tokens_per_s * need / (
        run["chips"] * run["peaks"].bf16_flops
    )
