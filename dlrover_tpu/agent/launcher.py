"""``dlrover-tpu-run``: torchrun-style elastic launcher for TPU hosts.

Reference: dlrover/trainer/torch/elastic_run.py (parse_args:125, run:342,
_launch_dlrover_local_master:237). Single-host runs spawn an in-process
LocalJobMaster automatically; multi-host runs point every agent at the job
master's address.

Usage:
    python -m dlrover_tpu.agent.launcher --nnodes 1:2 --node-id 0 \
        [--network-check] [--max-restarts 3] -- python train.py ...
"""

import argparse
import json
import os
import subprocess
import sys
import threading
import time
from typing import List, Optional

from dlrover_tpu.common.constants import GraftEnv, NodeStatus
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.agent.agent import ElasticLaunchConfig, ElasticTrainingAgent
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.monitor import ResourceMonitor

logger = get_logger(__name__)


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(prog="dlrover-tpu-run")
    p.add_argument(
        "--nnodes",
        default="1",
        help="N or MIN:MAX node count (elastic range)",
    )
    p.add_argument("--node-id", type=int, default=None)
    p.add_argument(
        "--nproc",
        type=int,
        default=0,
        help="local chip count (0 = the DLROVER_TPU_LOCAL_CHIPS "
        "environment, else 1; the agent never opens the device to count)",
    )
    p.add_argument("--master-addr", default="", help="job master host:port")
    p.add_argument("--max-restarts", type=int, default=3)
    p.add_argument(
        "--network-check",
        action="store_true",
        help="run the matmul+collective health check before training",
    )
    p.add_argument(
        "--comm-perf-test",
        action="store_true",
        help="sweep allreduce sizes across local chips and log bus "
        "bandwidth before training (reference: dlrover-run "
        "--comm-perf-test)",
    )
    p.add_argument(
        "--exclude-straggler",
        action="store_true",
        help="with --network-check: a node the check flags as a "
        "straggler exits instead of joining (and slowing) the world "
        "(reference: dlrover-run --exclude-straggler)",
    )
    p.add_argument("--node-unit", type=int, default=1)
    p.add_argument(
        "--compile-cache-dir",
        default="",
        help="persistent XLA compile-cache dir for workers (e.g. a "
        "job-shared NFS path), used when JAX_COMPILATION_CACHE_DIR is "
        "not set; default: a fixed directory inside the checkout — "
        "restarts with an already-seen mesh shape skip the recompile",
    )
    p.add_argument("--monitor-interval", type=float, default=2.0)
    p.add_argument("entrypoint", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if args.entrypoint and args.entrypoint[0] == "--":
        args.entrypoint = args.entrypoint[1:]
    return args


def _parse_nnodes(spec: str):
    if ":" in spec:
        lo, hi = spec.split(":")
        return int(lo), int(hi)
    return int(spec), int(spec)


def _local_chips(args: argparse.Namespace) -> int:
    """Chips on this host, as told: ``--nproc``, else the environment,
    else one. Counting them through jax would take them from the worker;
    the worker reports what it finds (agent/monitor.py)."""
    return args.nproc or int(os.environ.get(GraftEnv.LOCAL_CHIPS, "1"))


def _device_child(mode: str, timeout_s: float = 600.0) -> dict:
    """Run one ``agent.node_check`` mode in a child process and return
    the JSON object it prints. A chip belongs to one process at a time:
    the agent never initialises a jax backend itself, and the child has
    exited — and released the chip — before the worker starts."""
    import dlrover_tpu

    pkg_parent = os.path.dirname(os.path.dirname(dlrover_tpu.__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (pkg_parent, env.get("PYTHONPATH", "")) if p
    )
    proc = subprocess.run(
        [sys.executable, "-m", "dlrover_tpu.agent.node_check", mode],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout_s,
    )
    sys.stderr.write(proc.stderr)
    if proc.returncode != 0:
        raise RuntimeError(
            f"node_check {mode} child exited rc={proc.returncode}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _launch_local_master(num_workers: int, max_workers: int, node_unit: int):
    """Spin an in-process LocalJobMaster (reference: :237)."""
    from dlrover_tpu.master.master import LocalJobMaster

    master = LocalJobMaster(
        port=0,
        num_workers=num_workers,
        max_workers=max_workers,
        node_unit=node_unit,
    )
    master.prepare()
    threading.Thread(
        target=master.run, name="local-master", daemon=True
    ).start()
    logger.info("local master started at %s", master.addr)
    return master


def _run_network_check(client: MasterClient, config: ElasticLaunchConfig):
    """Two paired check rounds; abort if this node is declared faulty."""
    from dlrover_tpu.common.constants import RendezvousName
    from dlrover_tpu.agent.rendezvous import MasterRendezvousHandler

    for _ in range(2):
        handler = MasterRendezvousHandler(
            client,
            client.node_rank,
            config.local_chips,
            rdzv_name=RendezvousName.NETWORK_CHECK,
            timeout_s=config.rdzv_timeout_s,
        )
        handler.next_rendezvous()
        result = _device_child("check")
        client.report_network_check_result(
            result["elapsed_s"], result["ok"]
        )
        time.sleep(1.0)
    status = client.get_network_check_status()
    if not status.normal:
        logger.error(
            "this node failed the network check (faults=%s); exiting",
            status.fault_nodes,
        )
        client.report_node_status(NodeStatus.CHECK_FAILED)
        sys.exit(3)
    if status.stragglers:
        logger.warning("stragglers detected: %s", status.stragglers)
        if (
            config.exclude_straggler
            and client.node_rank in status.stragglers
        ):
            logger.error(
                "this node is a straggler and --exclude-straggler is "
                "set; exiting"
            )
            client.report_node_status(NodeStatus.CHECK_FAILED)
            sys.exit(3)


def _run_comm_perf_test():
    """Allreduce bandwidth sweep before the worker starts. A diagnostic:
    its figures are logged, and a child that fails or runs out of time
    is logged too — it never stops the launch."""
    try:
        gbps = _device_child("comm-perf")["gbps"]
    except (OSError, RuntimeError, subprocess.SubprocessError, ValueError,
            LookupError):
        logger.warning("comm perf test failed", exc_info=True)
        return
    logger.info(
        "comm perf, GB/s by allreduce size in bf16 elements: %s",
        gbps or "skipped — fewer than 2 devices",
    )


def run(args: argparse.Namespace) -> int:
    min_nodes, max_nodes = _parse_nnodes(args.nnodes)
    node_id = (
        args.node_id
        if args.node_id is not None
        else int(os.environ.get(GraftEnv.NODE_ID, "0"))
    )
    if os.environ.get(GraftEnv.TRACE_DIR):
        # flight recorder on: this process's failover spans stream as
        # role=agent (workers it spawns stream as role=worker)
        from dlrover_tpu.observability.tracing import configure_tracer

        configure_tracer("agent")
    local_chips = _local_chips(args)

    master = None
    master_addr = args.master_addr or os.environ.get(GraftEnv.MASTER_ADDR, "")
    if not master_addr:
        if min_nodes > 1:
            logger.error("multi-node runs need --master-addr")
            return 2
        master = _launch_local_master(min_nodes, max_nodes, args.node_unit)
        master_addr = master.addr

    config = ElasticLaunchConfig(
        min_nodes=min_nodes,
        max_nodes=max_nodes,
        node_id=node_id,
        local_chips=local_chips,
        max_restarts=args.max_restarts,
        monitor_interval_s=args.monitor_interval,
        network_check=args.network_check,
        comm_perf_test=args.comm_perf_test,
        exclude_straggler=args.exclude_straggler,
        node_unit=args.node_unit,
        compile_cache_dir=args.compile_cache_dir,
        entrypoint=args.entrypoint,
    )
    config.auto_configure()
    if not config.entrypoint:
        logger.error("no training entrypoint given")
        return 2

    client = MasterClient(master_addr, node_id=node_id)
    client.register_node(local_chips=local_chips)

    monitor = ResourceMonitor(client)
    monitor.start()
    from dlrover_tpu.agent.config_tuner import ParalConfigTuner

    tuner = ParalConfigTuner(client)
    tuner.start()
    try:
        if config.network_check:
            _run_network_check(client, config)
        if config.comm_perf_test:
            _run_comm_perf_test()
        agent = ElasticTrainingAgent(config, client)
        try:
            from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver

            saver = AsyncCheckpointSaver.start_async_saving_ckpt()
            agent.attach_ckpt_saver(saver)
        except Exception:  # noqa: BLE001 — ckpt daemon is best-effort
            logger.warning("checkpoint saver daemon unavailable", exc_info=True)
        return agent.run()
    finally:
        monitor.stop()
        tuner.stop()
        if master is not None:
            master.request_stop()


def main(argv: Optional[List[str]] = None) -> int:
    return run(parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
