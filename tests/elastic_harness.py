"""Shared process harness for the multi-node end-to-end tests.

Spawns the real distributed stack on one machine: a standalone master
process, launcher/agent process groups that rendezvous through it, and
worker processes forming a real jax.distributed cluster over CPU
(SURVEY.md §4's multi-node-without-a-cluster tier). Used by
test_multinode.py and test_slice_elasticity.py.
"""

import os
import queue as queue_mod
import re
import signal
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_env(run_id, extra=None):
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "",  # workers: 1 local CPU device each
            "DLROVER_TPU_RUN_ID": run_id,
            "DLROVER_TPU_HOST_ADDR": "localhost",
        }
    )
    if extra:
        env.update(extra)
    return env


def drain(proc):
    """Pump a process's merged stdout into a queue from a daemon thread:
    keeps the ~64KB pipe from backpressure-blocking the producer while
    the test waits on OTHER processes, and lets readers enforce real
    deadlines (a blocking readline would only re-check its deadline
    between lines)."""
    q = queue_mod.Queue()

    def run():
        for line in proc.stdout:
            q.put(line)
        q.put(None)

    threading.Thread(target=run, daemon=True).start()
    return q


def kill_tree(proc):
    """SIGKILL a launched agent AND its worker children (they share the
    process group because we launch with start_new_session=True).

    Safe to call even after the leader was reaped: Linux keeps the pid
    number reserved while it is still the pgid of any live member, so
    killpg either hits OUR group (reaping a crashed leader's orphaned
    workers — the case this exists for) or raises ProcessLookupError
    once the whole group is gone."""
    if proc is None:
        return
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        if proc.poll() is None:
            proc.kill()


def drain_now(q, lines):
    """Pull whatever is already queued, non-blocking (for diagnostics)."""
    while True:
        try:
            line = q.get_nowait()
        except queue_mod.Empty:
            return
        if line is None:
            return
        lines.append(line)


def collect(q, lines, until, deadline, on_line=None):
    """Consume queued lines until ``until(line)`` or EOF/deadline.
    Returns the matching line or None."""
    while time.time() < deadline:
        try:
            line = q.get(timeout=0.2)
        except queue_mod.Empty:
            continue
        if line is None:
            return None
        lines.append(line)
        if on_line:
            on_line(line)
        if until(line):
            return line
    return None


def start_master(run_id, argv_extra=(), env_extra=None):
    """Spawn dlrover_tpu.master.main, return (proc, queue, lines, addr)."""
    master = subprocess.Popen(
        [
            sys.executable,
            "-m",
            "dlrover_tpu.master.main",
            "--port",
            "0",
            *argv_extra,
        ],
        cwd=REPO,
        env=make_env(run_id, env_extra),
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )
    q = drain(master)
    lines = []
    addr_line = collect(
        q,
        lines,
        until=lambda l: l.startswith("DLROVER_TPU_MASTER_ADDR="),
        deadline=time.time() + 60,
    )
    assert addr_line, "master did not print its address"
    addr = re.match(
        r"DLROVER_TPU_MASTER_ADDR=(.+)", addr_line.strip()
    ).group(1)
    return master, q, lines, addr


def launch_agent(run_id, node_id, addr, train_args, agent_args=(),
                 nnodes="1:2", script="examples/train_gpt_elastic.py",
                 env_extra=None):
    """Spawn a launcher+worker process group for one node."""
    env = {"DLROVER_TPU_COORDINATOR_PORT": "0"}
    if env_extra:
        env.update(env_extra)
    return subprocess.Popen(
        [
            sys.executable,
            "-m",
            "dlrover_tpu.agent.launcher",
            "--nnodes",
            nnodes,
            "--node-id",
            str(node_id),
            "--nproc",
            "1",
            *agent_args,
            "--master-addr",
            addr,
            "--",
            sys.executable,
            script,
            *train_args,
        ],
        cwd=REPO,
        env=make_env(f"{run_id}_n{node_id}", env),
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        start_new_session=True,
    )
