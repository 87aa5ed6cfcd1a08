"""Full-stack elasticity drill (VERDICT r3 #7).

The production composition in ONE job: a real master process, two
launcher/agent process groups training DeepFM-with-dense-tower, a
two-process KvServer ring carrying the sparse tier, and a remote
coworker feed (this test IS the producer pool, pushing packed CTR
batches over TCP into each worker's shm ring). Mid-run an agent AND a
sparse server are killed; recovery must complete inside 60 s each and
convergence continue to the end.

Reference story: docs/tech_report/fault_tolerance_exps.md:1-60 — the
pieces are individually proven (test_multinode, test_sparse_serving,
test_coworker); this is their composition.
"""

import json
import multiprocessing as mp
import os
import re
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from elastic_harness import (
    REPO,
    collect as _collect,
    drain as _drain,
    drain_now as _drain_now,
    kill_tree as _kill_tree,
    launch_agent as _launch_agent,
    make_env as _env,
    start_master as _start_master,
)
from test_sparse_serving import _spawn_server

from dlrover_tpu.observability.tracing import merge_trace_dir

RECOVERY_BUDGET_S = 60.0


def _launch_drill_agent(
    run_id, node_id, addr, kv_json, steps, wire_token, trace_dir
):
    return subprocess.Popen(
        [
            sys.executable,
            "-m",
            "dlrover_tpu.agent.launcher",
            "--nnodes",
            "1:2",
            "--node-id",
            str(node_id),
            "--nproc",
            "1",
            "--master-addr",
            addr,
            "--",
            sys.executable,
            "examples/train_deepfm_fullstack.py",
            "--steps",
            str(steps),
            "--kv-addrs",
            kv_json,
        ],
        cwd=REPO,
        env=_env(
            f"{run_id}_n{node_id}",
            {
                "DLROVER_TPU_COORDINATOR_PORT": "0",
                # the job-wide wire credential: run ids are node-scoped
                # here (shm isolation on one box), so the cross-host
                # planes authenticate with this instead
                "DLROVER_TPU_WIRE_TOKEN": wire_token,
                # the flight recorder: one JOB-wide trace dir (run ids
                # are node-scoped, so this is the cross-process merge
                # key); the agent streams role=agent spans, its workers
                # inherit the dir and stream role=worker
                "DLROVER_TPU_TRACE_DIR": trace_dir,
            },
        ),
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        start_new_session=True,
    )


def _find_worker_pid(agent_pid, script="train_deepfm_fullstack.py",
                     deadline_s=30.0):
    """The agent's worker child: ppid == agent AND running the drill
    script (the launcher itself also matches the script name in argv)."""
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        for pid_dir in os.listdir("/proc"):
            if not pid_dir.isdigit():
                continue
            try:
                with open(f"/proc/{pid_dir}/stat") as f:
                    ppid = int(f.read().rsplit(")", 1)[1].split()[1])
                if ppid != agent_pid:
                    continue
                with open(f"/proc/{pid_dir}/cmdline", "rb") as f:
                    cmd = f.read().replace(b"\0", b" ").decode(
                        errors="replace"
                    )
                if script in cmd:
                    return int(pid_dir)
            except (OSError, ValueError, IndexError):
                continue
        time.sleep(0.5)
    return None


def _failover_phases(events, t0, t1):
    """Attribute the recovery inside wall window [t0, t1] to phases from
    the merged ``failover.*`` events (``ts`` is wall-anchored epoch µs).

    Returns ({phase: seconds}, window_events). Spans/instants that carry
    a ``node`` arg are pinned to node 0 — the node whose worker was
    killed; master-side events (rdzv seal) carry no node and pass."""
    lo, hi = (t0 - 2.0) * 1e6, (t1 + 5.0) * 1e6
    win = [
        e
        for e in events
        if e.get("name", "").startswith("failover.")
        and lo <= e.get("ts", 0.0) <= hi
    ]

    def first(name, ph):
        for e in win:
            if e.get("name") != name or e.get("ph") != ph:
                continue
            if (e.get("args") or {}).get("node", 0) != 0:
                continue
            return e
        return None

    phases = {}
    exit_ev = first("failover.worker_exit", "i")
    if exit_ev:
        phases["detect_s"] = round(exit_ev["ts"] / 1e6 - t0, 3)
    for span_name, key in (
        ("failover.ckpt_persist", "ckpt_persist_s"),
        ("failover.rendezvous", "rendezvous_s"),
        ("failover.restore", "restore_s"),
    ):
        ev = first(span_name, "X")
        if ev:
            phases[key] = round(ev.get("dur", 0.0) / 1e6, 3)
    fs = first("failover.first_step", "i")
    if fs:
        phases["first_step_s"] = round(fs["ts"] / 1e6 - t0, 3)
    return phases, win


def _synthetic_ctr(rng, n, fields, n_dense):
    cat = rng.integers(0, 50, size=(n, fields)).astype(np.int64)
    dense = rng.normal(size=(n, n_dense)).astype(np.float32)
    hot = (cat % 7 == 0).sum(axis=1) + dense[:, 0]
    p = 1.0 / (1.0 + np.exp(-(hot - 2.0)))
    labels = (rng.random(n) < p).astype(np.float32)
    return cat, dense, labels


class _Producer(threading.Thread):
    """One remote coworker: pushes the fixed dataset over TCP forever
    (until stopped or the worker's ingress goes away)."""

    def __init__(self, port, batch):
        super().__init__(daemon=True)
        self.port = port
        self.batch = batch
        self.stop_ev = threading.Event()

    def run(self):
        from dlrover_tpu.data.coworker import RemoteBatchWriter

        try:
            w = RemoteBatchWriter(("127.0.0.1", self.port), timeout=30.0)
            while not self.stop_ev.is_set():
                w.put(self.batch)
                time.sleep(0.02)
        except Exception:  # noqa: BLE001 — worker gone/done
            return


_STEP_RE = re.compile(r"\[fullstack\] step (\d+) loss ([0-9.]+)")
_METRICS_RE = re.compile(r"metrics endpoint on port (\d+)")


def _master_metrics(port: int) -> dict:
    import urllib.request

    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/json", timeout=10
    ) as resp:
        return json.loads(resp.read())


@pytest.mark.slow
def test_fullstack_elasticity_drill(monkeypatch, tmp_path):
    run_id = f"drill{os.getpid()}"
    wire_token = f"{run_id}-wire"
    # job-wide flight-recorder dir: every process (master, agents,
    # workers) streams its spans here; the merge is the drill artifact
    trace_dir = str(tmp_path / "trace")
    # the KvServer children (mp spawn) inherit this env
    monkeypatch.setenv("DLROVER_TPU_WIRE_TOKEN", wire_token)
    ctx = mp.get_context("spawn")
    kv_procs, kv_addrs = [], {}
    for name in ("s0", "s1"):
        p, addr = _spawn_server(ctx)
        kv_procs.append(p)
        kv_addrs[name] = addr
    kv_json = json.dumps({k: list(v) for k, v in kv_addrs.items()})

    master = agents = None
    producers = []
    try:
        master, mq, mlines, maddr = _start_master(
            run_id,
            argv_extra=("--num-workers", "2"),
            env_extra={
                "DLROVER_TPU_WIRE_TOKEN": wire_token,
                # detect the killed agent INSIDE the drill window (the
                # 300 s default would outlive the whole test), so the
                # goodput tracker sees the failure
                "DLROVER_TPU_CTX_HEARTBEAT_TIMEOUT_S": "35",
                "DLROVER_TPU_TRACE_DIR": trace_dir,
            },
        )
        # the metrics endpoint is logged during prepare(), before the
        # address line _start_master scraped — so it is already in mlines
        metrics_port = None
        for line in mlines:
            m = _METRICS_RE.search(line)
            if m:
                metrics_port = int(m.group(1))
        assert metrics_port, "".join(mlines)[-2000:]
        agents = [
            _launch_drill_agent(
                run_id, i, maddr, kv_json, steps=60,
                wire_token=wire_token, trace_dir=trace_dir,
            )
            for i in (0, 1)
        ]
        queues = [_drain(a) for a in agents]
        logs = [[], []]

        # discover each worker's TCP ingress and become its producers
        rng = np.random.default_rng(7)
        batch_data = _synthetic_ctr(rng, 256, fields=6, n_dense=4)
        batch = {
            "cat": batch_data[0],
            "dense": batch_data[1],
            "labels": batch_data[2],
        }
        # the port line can interleave with worker logger output on the
        # merged pipe: match the digits explicitly (the script prints
        # the line twice so one clean copy always exists)
        port_re = re.compile(r"\[fullstack\] feed port (\d+)\b")
        for i in (0, 1):
            line = _collect(
                queues[i],
                logs[i],
                until=lambda l: bool(port_re.search(l)),
                deadline=time.time() + 120,
            )
            assert line, (
                f"worker {i} never served its feed port:\n"
                + "".join(logs[i][-40:])
            )
            port = int(port_re.search(line).group(1))
            prod = _Producer(port, batch)
            prod.start()
            producers.append(prod)

        def steps_seen(log):
            out = {}
            for line in log:
                m = _STEP_RE.search(line)
                if m:
                    out[int(m.group(1))] = float(m.group(2))
            return out

        # both workers make progress against the shared sparse tier
        for i in (0, 1):
            assert _collect(
                queues[i],
                logs[i],
                until=lambda l: bool(
                    (m := _STEP_RE.search(l)) and int(m.group(1)) >= 8
                ),
                deadline=time.time() + 180,
            ), f"worker {i} stalled:\n" + "".join(logs[i][-40:])
        first_losses = steps_seen(logs[0])
        first = first_losses[min(first_losses)]

        # ---- failure 1: kill worker 0's PROCESS (agent survives) ------
        # the one failure that exercises the full per-phase recovery
        # chain the flight recorder attributes: the agent's poll detects
        # the exit, persists the staged ckpt, re-rendezvouses (agent 1
        # sees the waiting node and rejoins too), respawns with
        # restart=1, and the new worker's first step closes the timeline
        worker_pid = _find_worker_pid(agents[0].pid)
        assert worker_pid, "could not locate worker 0's process"
        # keep BOTH producers feeding through the kill: starving worker 1
        # here would let it drain its ring and exit CLEANLY — its agent
        # then reports SUCCEEDED and leaves, and the re-rendezvous can
        # never seal. The producer threads exit on their own when the
        # kill/respawn tears down the old ingress sockets.
        old_producers = producers
        producers = []
        t_kill_worker = time.time()
        os.kill(worker_pid, signal.SIGKILL)
        # BOTH workers respawn (coordinated re-rendezvous): re-discover
        # the new ingress ports and become their producers again
        for i in (0, 1):
            line = _collect(
                queues[i],
                logs[i],
                until=lambda l: bool(port_re.search(l)),
                deadline=t_kill_worker + RECOVERY_BUDGET_S,
            )
            assert line, (
                f"worker {i} never re-served its feed port after the "
                "worker kill:\n" + "".join(logs[i][-40:])
            )
            port = int(port_re.search(line).group(1))
            prod = _Producer(port, batch)
            prod.start()
            producers.append(prod)
        for prod in old_producers:
            prod.stop_ev.set()  # hygiene — their sockets are gone
        line = _collect(
            queues[0],
            logs[0],
            until=lambda l: bool(_STEP_RE.search(l)),
            deadline=t_kill_worker + RECOVERY_BUDGET_S,
        )
        assert line, (
            "worker 0 made no step within 60s of the worker kill:\n"
            + "".join(logs[0][-40:])
        )
        recovery_worker_s = time.time() - t_kill_worker
        assert recovery_worker_s < RECOVERY_BUDGET_S

        # goodput window opens here: startup (rendezvous + first jit
        # compile) AND the worker-kill recovery above are excluded — the
        # reference's 95% headline is a steady-state number too, not a
        # cold-start one. The stall the kill opened closes only once a
        # respawned worker's report ADVANCES past the pre-kill watermark
        # (restarted workers count from step 0 again), so wait for
        # lost-seconds to stop growing before sampling the baseline.
        deadline = time.time() + 60
        prev_lost = -1.0
        while time.time() < deadline:
            lost_now = _master_metrics(metrics_port)[
                "goodput_lost_seconds"
            ]
            if lost_now == prev_lost:
                break
            prev_lost = lost_now
            time.sleep(1.0)
        else:
            raise AssertionError(
                "worker-kill goodput stall never closed"
            )
        gp0 = _master_metrics(metrics_port)
        t_window_open = time.time()

        # ---- failure 2: kill agent 1 (whole process group) ------------
        t_kill_agent = time.time()
        producers[1].stop_ev.set()
        _kill_tree(agents[1])
        # recovery: the surviving worker keeps stepping (PS-style
        # training has no collective coupling to the dead peer) and the
        # master stays up — within the budget
        base = max(steps_seen(logs[0]))
        line = _collect(
            queues[0],
            logs[0],
            until=lambda l: bool(
                (m := _STEP_RE.search(l)) and int(m.group(1)) > base
            ),
            deadline=t_kill_agent + RECOVERY_BUDGET_S,
        )
        assert line, (
            "worker 0 made no progress within 60s of the agent kill:\n"
            + "".join(logs[0][-40:])
        )
        recovery_agent_s = time.time() - t_kill_agent
        assert recovery_agent_s < RECOVERY_BUDGET_S
        assert master.poll() is None, "master died with the agent"

        # ---- failure 3: kill sparse server s0 -------------------------
        t_kill_kv = time.time()
        kv_procs[0].kill()
        kv_procs[0].join(timeout=10)
        line = _collect(
            queues[0],
            logs[0],
            until=lambda l: "[fullstack] sparse failover" in l,
            deadline=t_kill_kv + RECOVERY_BUDGET_S,
        )
        assert line and "'s1'" in line, (
            "worker 0 never failed over the sparse ring:\n"
            + "".join(logs[0][-40:])
        )
        base = max(steps_seen(logs[0]))
        line = _collect(
            queues[0],
            logs[0],
            until=lambda l: bool(
                (m := _STEP_RE.search(l)) and int(m.group(1)) > base
            ),
            deadline=t_kill_kv + RECOVERY_BUDGET_S,
        )
        assert line, (
            "worker 0 made no step within 60s of the KvServer kill:\n"
            + "".join(logs[0][-40:])
        )
        recovery_kv_s = time.time() - t_kill_kv
        assert recovery_kv_s < RECOVERY_BUDGET_S

        # the master must have SEEN the agent kill (heartbeat timeout) before
        # the goodput window closes — otherwise the goodput number would
        # be vacuous (no stall ever marked)
        deadline = time.time() + 60
        while time.time() < deadline:
            if _master_metrics(metrics_port)["counters"][
                "node_failures_total"
            ] >= 1:
                break
            time.sleep(2)
        else:
            raise AssertionError(
                "master never detected the killed agent"
            )

        # ---- convergence continues to the end -------------------------
        assert _collect(
            queues[0],
            logs[0],
            until=lambda l: "[fullstack] done" in l,
            deadline=time.time() + 240,
        ), "worker 0 never finished:\n" + "".join(logs[0][-40:])
        losses = steps_seen(logs[0])
        final = losses[max(losses)]
        assert np.isfinite(final)
        # through both failures (incl. re-initialized embedding rows)
        # the loss ends below where it started
        assert final < first, (first, final)

        # ---- goodput across the two failures (VERDICT r4 ask #5) ------
        # windowed: (lost-time delta) / (wall delta) between the sample
        # taken before failure 1 and now, from the LIVE master's
        # GoodputTracker — the measured analog of the reference's
        # 69%→95% headline (reference README.md:57-58)
        gp1 = _master_metrics(metrics_port)
        window_wall = time.time() - t_window_open
        lost = (
            gp1["goodput_lost_seconds"] - gp0["goodput_lost_seconds"]
        )
        goodput = max(0.0, 1.0 - lost / max(window_wall, 1e-9))
        assert goodput >= 0.90, (
            f"goodput {goodput:.3f} across the two failures "
            f"(lost {lost:.1f}s of {window_wall:.1f}s)"
        )

        # ---- flight recorder: merged timeline + phase attribution -----
        # one time-sorted JSONL of every process's spans; the worker-kill
        # failover must decompose into detect → (persist) → rendezvous →
        # restore → first-step, with all three roles on the timeline
        trace_out = str(tmp_path / "drill_trace.jsonl")
        events = merge_trace_dir(trace_dir, out_path=trace_out)
        phases, win = _failover_phases(
            events, t_kill_worker, t_kill_worker + recovery_worker_s
        )
        roles = {(e.get("args") or {}).get("role", "") for e in win}
        assert {"worker", "agent", "master"} <= roles, (
            f"failover window roles {roles} "
            f"({len(events)} events total, {len(win)} in window)"
        )
        for key in (
            "detect_s", "rendezvous_s", "restore_s", "first_step_s"
        ):
            assert key in phases, (
                phases,
                sorted({e.get("name") for e in win}),
            )

        artifact = {
            "drill": "test_fullstack_elasticity_drill",
            "failures": [
                {
                    "kind": "worker_killed",
                    "recovery_s": round(recovery_worker_s, 2),
                    "phases": phases,
                },
                {"kind": "agent_killed", "recovery_s": round(recovery_agent_s, 2)},
                {"kind": "sparse_server_killed", "recovery_s": round(recovery_kv_s, 2)},
            ],
            "recovery_budget_s": RECOVERY_BUDGET_S,
            "goodput_across_failures": round(goodput, 4),
            "goodput_lost_s": round(lost, 2),
            "goodput_window_s": round(window_wall, 2),
            "goodput_since_master_start": gp1["goodput"],
            "node_failures_seen_by_master": gp1["counters"][
                "node_failures_total"
            ],
            "trace_events": len(events),
            "trace_path": os.path.basename(trace_out),
        }
        with open(tmp_path / "drill.json", "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"\n[drill] {json.dumps(artifact)}")
    finally:
        dump_dir = os.environ.get("DLROVER_TPU_DRILL_DEBUG_DIR")
        if dump_dir:
            # post-mortem: the failing assert only shows ONE process's
            # tail — dump every captured stream for cross-correlation
            os.makedirs(dump_dir, exist_ok=True)
            try:
                for i, (q, log) in enumerate(zip(queues, logs)):
                    _drain_now(q, log)
                    with open(
                        os.path.join(dump_dir, f"worker{i}.log"), "w"
                    ) as f:
                        f.writelines(log)
                _drain_now(mq, mlines)
                with open(
                    os.path.join(dump_dir, "master.log"), "w"
                ) as f:
                    f.writelines(mlines)
            except Exception:  # noqa: BLE001 — best-effort diagnostics
                pass
        for prod in producers:
            prod.stop_ev.set()
        for a in agents or []:
            _kill_tree(a)
        if master is not None:
            master.kill()
        for p in kv_procs:
            if p.is_alive():
                p.terminate()
            p.join(timeout=10)


@pytest.mark.slow
def test_live_reshard_eviction_drill(tmp_path):
    """Host-eviction stage: a mid-training ``EvictionNotice`` turns into
    a master reshard directive; the worker live-reshards dp 8→4 from
    in-HBM state (survivors donate ZeRO-1 shards over the PackPlan
    wire), the step rebuilds, and training finishes at the new size.
    The happy path must land inside the recovery budget WITHOUT a
    storage-tier restore, and the artifact records per-phase seconds."""
    run_id = f"reshard{os.getpid()}"
    tel_dir = str(tmp_path / "telemetry")
    os.makedirs(tel_dir, exist_ok=True)
    master = agent = None
    lines = []
    try:
        master, mq, mlines, maddr = _start_master(
            run_id,
            env_extra={"DLROVER_TPU_TELEMETRY_DIR": tel_dir},
        )
        agent = _launch_agent(
            run_id,
            0,
            maddr,
            train_args=[
                "--steps", "12", "--batch", "8", "--seq", "16",
                "--zero1", "--evict-at", "6",
                "--ckpt-dir", str(tmp_path / "ckpt"),
            ],
            nnodes="1:1",
            env_extra={
                # the eviction is emulated INSIDE one worker: 8 virtual
                # CPU devices so the mesh can shrink 8 -> 4 in-process
                # (the harness default of one device per worker would
                # leave nothing to reshard)
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
                "DLROVER_TPU_TELEMETRY_DIR": tel_dir,
                # hermetic compile cache: jaxlib's CPU backend segfaults
                # re-executing a persistent-cache-deserialized executable
                # compiled for a device SUBSET (the dp=4 survivor mesh),
                # so never share the cache across drill runs
                "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jit_cache"),
            },
        )
        q = _drain(agent)
        done = _collect(
            q,
            lines,
            until=lambda l: "[reshard] done" in l,
            deadline=time.time() + 420,
        )
        assert done, "worker never reported reshard:\n" + "".join(
            lines[-40:]
        )
        summary = json.loads(done.split("[reshard] done", 1)[1])
        assert summary["path"] == "live", summary
        assert summary["dp"] == "8->4", summary
        assert summary["recovery_s"] < RECOVERY_BUDGET_S, summary
        for phase in (
            "detect", "replan", "migrate", "rebuild", "first_step"
        ):
            assert phase in summary["phases"], summary

        # training must CONTINUE at dp=4 to the end — the reshard is a
        # recovery, not a shutdown
        assert _collect(
            q,
            lines,
            until=lambda l: "[worker] done" in l,
            deadline=time.time() + 240,
        ), "worker never finished after reshard:\n" + "".join(lines[-40:])

        # flight recorder: rehydrate the telemetry stream and check the
        # phase events landed and the disk was never read
        from dlrover_tpu.observability import telemetry as tel

        records = []
        for fname in sorted(os.listdir(tel_dir)):
            with open(os.path.join(tel_dir, fname)) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        records.append(tel.from_json(line))
                    except Exception:  # noqa: BLE001 — torn tail line
                        continue
        elastic = [r for r in records if isinstance(r, tel.ElasticEvent)]
        kinds = [r.kind for r in elastic]
        assert "eviction_notice" in kinds, kinds
        phase_s = {}
        for phase in (
            "detect", "replan", "migrate", "rebuild", "first_step"
        ):
            ev = [r for r in elastic if r.kind == f"reshard_{phase}"]
            assert ev, (phase, kinds)
            assert "ok=True" in ev[-1].detail, ev[-1]
            phase_s[phase] = round(ev[-1].seconds, 3)
        recovery = [r for r in elastic if r.kind == "reshard_recovery"]
        assert recovery, kinds
        assert "path=live" in recovery[-1].detail, recovery[-1]
        assert recovery[-1].seconds < RECOVERY_BUDGET_S, recovery[-1]
        # the defining property of tier 0: NO successful storage-tier
        # restore anywhere in the run (engine only stamps tier="storage"
        # when the disk actually answered)
        disk_restores = [
            r
            for r in records
            if isinstance(r, tel.CheckpointRecord)
            and r.kind == "restore"
            and r.tier == "storage"
        ]
        assert not disk_restores, disk_restores

        # ---- artifact: the eviction stage -----------------------------
        artifact = {
            "drill": "test_live_reshard_eviction_drill",
            "failures": [
                {
                    "kind": "host_eviction_live_reshard",
                    "recovery_s": round(float(summary["recovery_s"]), 2),
                    "phases": phase_s,
                    "restore_tier": "live",
                }
            ],
            "recovery_budget_s": RECOVERY_BUDGET_S,
        }
        with open(tmp_path / "drill.json", "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"\n[drill] {json.dumps(artifact['failures'][-1])}")
    finally:
        _kill_tree(agent)
        if master is not None:
            master.kill()


@pytest.mark.slow
def test_nan_fault_health_drill(monkeypatch, tmp_path):
    """Health-sentinel stage of the drill: a worker whose batch poisons
    the gradients at step 4 must produce — across the REAL wire — an
    AnomalyRecord on the master's flight recorder, a triggered runtime
    capture on the worker, a HealthSummary verdict from the master's
    aggregator, and a healthcheck CLI report (run as the operator
    would, `python -m ...healthcheck`) naming the failing rank and the
    first bad step."""
    import glob as _glob

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.models import decoder, get_config
    from dlrover_tpu.observability import telemetry
    from dlrover_tpu.parallel import MeshConfig, build_mesh
    from dlrover_tpu.train import Trainer, TrainerArgs, make_optimizer

    run_id = f"nandrill{os.getpid()}"
    tel_dir = str(tmp_path / "telemetry")
    monkeypatch.setenv("DLROVER_TPU_RUN_ID", run_id)
    monkeypatch.setenv("DLROVER_TPU_NODE_ID", "1")
    master = None
    telemetry.reset_hub()
    try:
        master, mq, mlines, maddr = _start_master(
            run_id,
            argv_extra=("--num-workers", "2"),
            env_extra={"DLROVER_TPU_TELEMETRY_DIR": tel_dir},
        )
        client = MasterClient(maddr, node_id=1)
        telemetry.configure_hub(sinks=[telemetry.MasterSink(client)])

        cfg = get_config(
            "tiny", n_layer=2, d_model=64, d_ff=128, n_head=4,
            vocab_size=128, max_seq=32,
        )
        mesh = build_mesh(MeshConfig(dp=8))

        def poison_loss(params, batch, **kw):
            clean = {k: v for k, v in batch.items() if k != "poison"}
            loss, metrics = decoder.loss_fn(
                params, clean, cfg=cfg, mesh=mesh
            )
            bad = jnp.max(batch["poison"]) > 0
            return loss * jnp.where(bad, jnp.float32(jnp.nan), 1.0), metrics

        def data():
            rng = np.random.RandomState(0)
            step = 0
            while True:
                step += 1
                base = rng.randint(0, 8, size=(8, 33))
                yield {
                    "tokens": np.asarray(base[:, :-1], np.int32),
                    "targets": np.asarray(base[:, 1:], np.int32),
                    "poison": np.full(
                        (8, 32), 1 if step == 4 else 0, np.int32
                    ),
                }

        args = TrainerArgs(
            output_dir=str(tmp_path / "out"), max_steps=6,
            save_interval=0, log_interval=0, report_to_master=False,
            detect_loss_spikes=False, resume=False,
            health_sentinels=True, sanitize_grads="skip",
        )
        t = Trainer(
            cfg, args, data(), make_optimizer(learning_rate=1e-3),
            mesh=mesh, loss_fn=poison_loss,
        )
        t.train()

        # worker side: classified anomaly with a triggered capture
        (rec,) = [r for r in t.watchdog.anomalies if r.kind == "nan_grads"]
        assert rec.step == 4 and rec.node_id == 1
        assert rec.capture and os.path.exists(rec.capture)
        assert json.load(open(rec.capture))["ops"]

        # master side: the wire-forwarded record and the aggregator's
        # verdict both land on the master's flight recorder
        deadline = time.time() + 30
        jsonl = None
        while time.time() < deadline:
            for path in _glob.glob(
                os.path.join(tel_dir, "telemetry-master-*.jsonl")
            ):
                body = open(path).read()
                if '"AnomalyRecord"' in body and '"HealthSummary"' in body:
                    jsonl = path
                    break
            if jsonl:
                break
            time.sleep(0.5)
        assert jsonl, "master flight recorder never saw the anomaly"

        # operator side: the offline CLI replays the jsonl to the same
        # diagnosis, exit code 1 because anomalies are present
        proc = subprocess.run(
            [
                sys.executable,
                "-m",
                "dlrover_tpu.observability.healthcheck",
                jsonl,
                "--world",
                "2",
            ],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "nan_grads" in proc.stdout
        assert "failing rank(s) 1" in proc.stdout
        assert "first bad step 4" in proc.stdout
        assert "suspect_data_or_hardware" in proc.stdout
    finally:
        telemetry.reset_hub()
        if master is not None:
            master.kill()
