"""Pre-flight node health check: matmul + collective micro-benchmark.

Reference: NodeCheckElasticAgent (training.py:864) running
trainer/torch/node_check/utils.py:58,88,149 (matmul + 16M-element
allreduce) on each rank, with the master pairing nodes per round to
isolate faulty hosts. TPU version: a bf16 MXU matmul loop on every local
chip plus a psum across all local chips (and across hosts when
jax.distributed is up) — exercising HBM, MXU, and ICI.
"""

import argparse
import json
import sys
import time
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)


def matmul_bench(
    size: int = 4096, iters: int = 8, device=None
) -> float:
    """Time a chain of bf16 matmuls on one chip; returns seconds."""
    device = device or jax.devices()[0]
    x = jax.device_put(
        jnp.ones((size, size), jnp.bfloat16), device
    )

    @jax.jit
    def chain(x):
        def body(_, a):
            return (a @ a) * (1.0 / size)

        return jax.lax.fori_loop(0, iters, body, x)

    chain(x).block_until_ready()  # compile
    t0 = time.perf_counter()
    chain(x).block_until_ready()
    return time.perf_counter() - t0


def collective_bench(n_elems: int = 1 << 24, iters: int = 4) -> float:
    """Time psum over every visible device (ICI within a host/slice)."""
    devices = jax.devices()
    n = len(devices)
    if n == 1:
        return 0.0
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("x",))
    x = jax.device_put(
        jnp.ones((n, n_elems // n), jnp.bfloat16),
        NamedSharding(mesh, P("x", None)),
    )

    @jax.jit
    def allreduce(x):
        def body(_, a):
            s = jnp.sum(a, axis=0, keepdims=True)  # cross-device reduce
            return jnp.broadcast_to(s / n, a.shape)

        return jax.lax.fori_loop(0, iters, body, x)

    allreduce(x).block_until_ready()
    t0 = time.perf_counter()
    allreduce(x).block_until_ready()
    return time.perf_counter() - t0


def run_comm_perf_test(sizes=(1 << 20, 1 << 24, 1 << 27)) -> dict:
    """Sweep allreduce sizes and report algorithmic bus bandwidth
    (reference: dlrover-run --comm-perf-test). Returns {n_elems: GB/s}
    keyed by the REQUESTED global element count — per-device derived
    sizes can collide (two requested sizes within a factor of
    device-count of each other) and would silently overwrite; logs a
    warning when the largest size runs below half the best observed
    bandwidth (a congested/degraded link)."""
    n = len(jax.devices())
    if n < 2:
        logger.info("comm perf: skipped — fewer than 2 devices")
        return {}
    iters = 4
    results = {}
    per_device_bytes = {}
    for n_elems in sizes:
        secs = collective_bench(n_elems=n_elems, iters=iters)
        # collective_bench shards [n, n_elems/n]: each device allreduces
        # an n_elems/n-element bf16 buffer; a ring moves 2(n-1)/n of
        # that buffer per device
        nbytes = (n_elems // n) * 2
        per_device_bytes[n_elems] = nbytes
        algo_bytes = 2 * (n - 1) / n * nbytes * iters
        results[n_elems] = (algo_bytes / secs / 1e9) if secs > 0 else 0.0
    vals = [v for v in results.values() if v > 0]
    if vals and results[max(results)] < 0.5 * max(vals):
        logger.warning(
            "comm perf: largest allreduce at %.2f GB/s, well below the "
            "best observed %.2f GB/s — link may be degraded",
            results[max(results)],
            max(vals),
        )
    for n_elems, gbps in results.items():
        logger.info(
            "comm perf: allreduce %6.1f MB/device → %7.2f GB/s",
            per_device_bytes[n_elems] / 1e6,
            gbps,
        )
    return results


def run_node_check(mock_error: bool = False) -> Tuple[bool, float]:
    """Returns (succeeded, elapsed_seconds)."""
    try:
        if mock_error:
            raise RuntimeError("mock node-check error")
        t0 = time.perf_counter()
        mm = matmul_bench()
        coll = collective_bench()
        elapsed = time.perf_counter() - t0
        logger.info(
            "node check ok: matmul=%.3fs collective=%.3fs total=%.3fs",
            mm,
            coll,
            elapsed,
        )
        return True, elapsed
    except Exception:  # noqa: BLE001
        logger.exception("node check failed")
        return False, 0.0


def main(argv=None) -> int:
    """``python -m dlrover_tpu.agent.node_check check|comm-perf``: the
    device half of the launcher's pre-flight, run as a child so the
    agent never holds the chip. Prints one JSON object as its last
    stdout line."""
    p = argparse.ArgumentParser(prog="dlrover-tpu-node-check")
    p.add_argument("mode", choices=("check", "comm-perf"))
    args = p.parse_args(argv)
    if args.mode == "check":
        ok, elapsed = run_node_check()
        print(json.dumps({"ok": ok, "elapsed_s": elapsed}), flush=True)
    else:
        bandwidth = run_comm_perf_test()
        print(
            json.dumps({"gbps": {str(k): v for k, v in bandwidth.items()}}),
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
