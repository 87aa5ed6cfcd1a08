"""Master control-plane tests with an in-process master.

Replicates the reference's keystone fixture (SURVEY.md §4): a real gRPC
master in-process, real clients, no cluster.
"""

import time

import pytest

from dlrover_tpu.common.constants import (
    NodeStatus,
    NodeType,
    RendezvousName,
)
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.master.master import LocalJobMaster
from dlrover_tpu.master.rdzv_manager import NetworkCheckRendezvousManager
from dlrover_tpu.master.status_flow import transition
from dlrover_tpu.master.task_manager import TaskManager


@pytest.fixture()
def master():
    m = LocalJobMaster(port=0, num_workers=2)
    m.prepare()
    yield m
    m.stop()


def _client(master, node_id):
    c = MasterClient(master.addr, node_id=node_id)
    c.register_node(local_chips=4, tpu_type="v5e")
    return c


def test_event_callback_registry_fires_hooks(master):
    """Pluggable NodeEventCallback observers (event_callback.py:42
    analog) see started/failed/succeeded with the cluster context, and
    an observer exception never breaks lifecycle handling."""
    from dlrover_tpu.master.event_callback import NodeEventCallback

    seen = []

    class Recorder(NodeEventCallback):
        def on_node_started(self, node, ctx):
            seen.append(("started", node.id, ctx is not None))

        def on_node_failed(self, node, ctx):
            seen.append(("failed", node.id, ctx.task_manager is not None))

    class Broken(NodeEventCallback):
        def on_node_started(self, node, ctx):
            raise RuntimeError("observer bug")

    master.job_manager.event_callbacks.extend([Recorder(), Broken()])
    c0 = _client(master, 0)
    c0.report_node_status(NodeStatus.FAILED, exit_reason="fatal_error")
    assert ("started", 0, True) in seen
    assert ("failed", 0, True) in seen
    # Broken raised on started, yet the node still registered + failed
    assert master.job_manager.get_node(0).status == NodeStatus.FAILED


def test_step_heartbeat_carries_the_workers_device_report(master):
    """The worker holds the chips, so it — through the step heartbeat
    every worker already sends — is the master's only source of device
    kind, chip count and HBM use; the agent's host-only reports in
    between must not read as an empty HBM."""
    import jax

    from dlrover_tpu.agent.monitor import ResourceMonitor, holds_devices

    devices = jax.local_devices()  # this process is a worker: backend up
    assert holds_devices()
    records = []
    master.telemetry_hub.subscribe(records.append, ("ResourceRecord",))
    client = MasterClient(master.addr, node_id=0)
    client.register_node(local_chips=1)  # what the agent was told
    assert client.report_global_step(1)
    assert client.report_global_step(2)  # inside the interval: no second
    assert ResourceMonitor(client).report_once()  # the agent's, host-only
    deadline = time.time() + 5
    while len(records) < 2 and time.time() < deadline:
        time.sleep(0.05)
    worker, agent = records
    assert worker.tpu_type == devices[0].device_kind
    assert worker.local_chips == len(devices)
    assert (agent.tpu_type, agent.local_chips, agent.hbm_mb) == ("", 0, 0.0)
    res = master.job_manager.get_node(0).config_resource
    assert (res.tpu_type, res.tpu_chips) == (
        devices[0].device_kind, len(devices),
    )
    # only the worker's reading reached the HBM gauges
    assert master.metric_collector.gauges["hbm_used_mb"] == worker.hbm_mb


def test_task_reschedule_callback_requeues_shards(master):
    """A dead node's in-flight shard goes back to the queue through the
    registry's TaskRescheduleCallback (no inline master plumbing)."""
    master.task_manager.new_dataset(
        "ds", dataset_size=8, shard_size=4
    )
    c0, c1 = _client(master, 0), _client(master, 1)
    t0 = c0.get_task("ds")
    assert t0.task_id >= 0
    c0.report_node_status(NodeStatus.FAILED, exit_reason="killed")
    # the shard node 0 held is available again (for node 1)
    t1 = c1.get_task("ds")
    t2 = c1.get_task("ds")
    got = {t1.shard_start, t2.shard_start}
    assert t0.shard_start in got


def test_chief_and_evaluator_roles(master):
    """Role-aware accounting: workers succeeding does not complete the
    job while an evaluator still runs; chief visibility is queryable."""
    from dlrover_tpu.common.constants import NodeType

    c0, c1 = _client(master, 0), _client(master, 1)
    ev = MasterClient(master.addr, node_id=7)
    ev.register_node(node_type=NodeType.EVALUATOR)
    chief = MasterClient(master.addr, node_id=8)
    chief.register_node(node_type=NodeType.CHIEF)

    jm = master.job_manager
    assert jm.is_chief_running()
    assert len(jm.nodes_of_type(NodeType.EVALUATOR)) == 1
    c0.report_node_status(NodeStatus.SUCCEEDED)
    c1.report_node_status(NodeStatus.SUCCEEDED)
    chief.report_node_status(NodeStatus.SUCCEEDED)
    assert jm.all_workers_succeeded()
    assert not jm.all_evaluators_exited()  # evaluator still running
    ev.report_node_status(NodeStatus.SUCCEEDED)
    assert jm.all_evaluators_exited()


def test_chief_exhaustion_fails_job_and_evaluator_gates_exit(master):
    from dlrover_tpu.common.constants import NodeType
    from dlrover_tpu.master.event_callback import ChiefFailureCallback

    failures = []
    master.job_manager.event_callbacks.append(
        ChiefFailureCallback(failures.append)
    )
    chief = MasterClient(master.addr, node_id=9)
    chief.register_node(node_type=NodeType.CHIEF)
    # non-relaunchable exit → the job-failed hook fires (DELETED path
    # covered by the alias)
    chief.report_node_status(NodeStatus.FAILED, exit_reason="fatal_error")
    assert failures and "chief" in failures[0]

    # evaluator gating: workers done but evaluator alive → master's exit
    # condition must hold off
    ev = MasterClient(master.addr, node_id=7)
    ev.register_node(node_type=NodeType.EVALUATOR)
    c0, c1 = _client(master, 0), _client(master, 1)
    c0.report_node_status(NodeStatus.SUCCEEDED)
    c1.report_node_status(NodeStatus.SUCCEEDED)
    jm = master.job_manager
    assert jm.all_workers_succeeded() is False  # chief FAILED counts
    assert not jm.all_evaluators_exited()
    ev.report_node_status(NodeStatus.SUCCEEDED)
    assert jm.all_evaluators_exited()


def test_brain_ps_weights_flow_to_sparse_tier(master):
    """Brain hot-shard plan → auto-scaler → ElasticPsService weights +
    version bump (the rebalance consumer path)."""
    from dlrover_tpu.master.auto_scaler import JobAutoScaler
    from dlrover_tpu.master.node_manager import NoopScaler
    from dlrover_tpu.master.resource_optimizer import ResourcePlan

    scaler = JobAutoScaler(
        master.job_manager,
        master.speed_monitor,
        NoopScaler(),
        ps_service=master.ps_service,
    )
    v0 = master.ps_service.get_global_version()
    plan = ResourcePlan()
    plan.node_resources["ps"] = {"weights": {"ps0": 0.5, "ps1": 1.0}}
    scaler.execute_plan(plan)
    assert master.ps_service.get_weights() == {"ps0": 0.5, "ps1": 1.0}
    assert master.ps_service.get_global_version() == v0 + 1
    # idempotent: same weights do not churn the version
    scaler.execute_plan(plan)
    assert master.ps_service.get_global_version() == v0 + 1

    # ps-oom count hints reach the platform hook
    targets = []
    scaler.ps_scale_fn = targets.append
    plan2 = ResourcePlan()
    plan2.node_resources["ps"] = {"num": 3}
    scaler.execute_plan(plan2)
    assert targets == [3]


def test_node_unit_rendezvous_seals_whole_slices():
    """node_unit=2 (hosts per slice): 3 waiting nodes seal a 2-node
    world — a partial slice has no ICI and must never join; the odd
    node stays waiting for the next round."""
    from dlrover_tpu.master.rdzv_manager import RendezvousManager

    mgr = RendezvousManager()
    mgr.update_rdzv_params(
        min_nodes=2, max_nodes=4, node_unit=2, waiting_timeout=0.0
    )
    for rank in (0, 1, 2):
        mgr.join_rendezvous(
            node_id=rank, node_rank=rank, local_world_size=4
        )
    _, _, world, _ = mgr.get_comm_world(0)
    # floor(3, unit=2) = 2, deterministically the lowest ranks
    assert set(world) == {0, 1}, world
    # the left-out node is still waiting for the next seal
    assert mgr.num_nodes_waiting() == 1


def test_node_unit_rejects_below_minimum():
    """2 waiting with unit 4 (min 4): nothing usable, no seal."""
    from dlrover_tpu.master.rdzv_manager import RendezvousManager

    mgr = RendezvousManager()
    mgr.update_rdzv_params(
        min_nodes=4, max_nodes=8, node_unit=4, waiting_timeout=0.0
    )
    mgr.join_rendezvous(node_id=0, node_rank=0, local_world_size=4)
    mgr.join_rendezvous(node_id=1, node_rank=1, local_world_size=4)
    _, _, world, _ = mgr.get_comm_world(0)
    assert world == {}


def test_pending_node_timeout_fails_job():
    """A node stuck INITIAL/PENDING past the deadline trips
    pending_timeout() — the master exits PENDING_TIMEOUT on it."""
    from dlrover_tpu.master.node_manager import JobManager

    jm = JobManager(num_workers=2, pending_timeout_s=0.2)
    assert not jm.pending_timeout()  # fresh nodes, inside the window
    time.sleep(0.3)
    assert jm.pending_timeout()  # neither ever registered
    # one registers: the OTHER still pending → still timed out
    from dlrover_tpu.common.messages import NodeMeta

    jm.register_node(NodeMeta(node_id=0))
    assert jm.pending_timeout()


def test_register_and_heartbeat(master):
    c = _client(master, 0)
    assert c.node_rank == 0
    assert c.report_heartbeat()
    node = master.job_manager.get_node(0)
    assert node.status == NodeStatus.RUNNING
    assert node.config_resource.tpu_chips == 4


def test_rendezvous_two_nodes(master):
    c0, c1 = _client(master, 0), _client(master, 1)
    assert c0.join_rendezvous(local_world_size=4) >= 1
    # world not sealed until min nodes joined
    _, _, world, _ = c0.get_comm_world()
    assert world == {}
    c1.join_rendezvous(local_world_size=4)
    _, _, world, coord = c0.get_comm_world()
    assert world == {0: 4, 1: 4}
    assert coord
    # both nodes see the same sealed world
    _, _, world1, coord1 = c1.get_comm_world()
    assert world1 == world and coord1 == coord


def test_rendezvous_restart_bumps_round(master):
    c0, c1 = _client(master, 0), _client(master, 1)
    r1 = c0.join_rendezvous(4)
    c1.join_rendezvous(4)
    _, _, world, _ = c0.get_comm_world()
    assert len(world) == 2
    # node 1 dies: master event callback removes it from the world
    c1.report_node_status(NodeStatus.FAILED, exit_reason="killed")
    time.sleep(0.1)
    # both nodes re-join (the agent restarts its worker) → new round seals
    r2 = c0.join_rendezvous(4)
    c1.join_rendezvous(4)
    assert r2 > r1
    _, _, world, _ = c0.get_comm_world()
    assert world == {0: 4, 1: 4}


def test_model_info_and_running_nodes(master):
    """report_model_info lands in the metrics collector's JobMeta (the
    Brain optimizer's input); get_running_nodes lists the live world
    (reference: master_client.py report_model_info/get_running_nodes)."""
    c0 = _client(master, 0)
    c1 = _client(master, 1)
    assert c0.report_model_info(
        model_name="llama-1.4b",
        num_params=1_360_000_000,
        flops_per_token=8.2e9,
        global_batch_size=8,
        seq_len=1024,
    )
    meta = master.metric_collector.meta
    assert meta.model_name == "llama-1.4b"
    assert meta.num_params == 1_360_000_000
    assert meta.seq_len == 1024

    nodes = c1.get_running_nodes()
    assert {n.id for n in nodes} == {0, 1}
    assert all(n.status == "running" for n in nodes)
    assert {n.rank_index for n in nodes} == {0, 1}


def test_rendezvous_concurrent_join_storm():
    """Stress: many threads join/poll/crash/rejoin concurrently. The
    sealed world must always be internally consistent — contiguous rank
    set from the waiting pool, node_unit multiple, one coordinator —
    and a post-storm rendezvous must still seal (no wedged state)."""
    import threading

    import numpy as np

    from dlrover_tpu.master.rdzv_manager import (
        ElasticTrainingRendezvousManager,
    )

    mgr = ElasticTrainingRendezvousManager()
    mgr.update_rdzv_params(
        min_nodes=4, max_nodes=8, waiting_timeout=0.05, node_unit=2
    )
    stop = time.time() + 2.0
    errors = []

    def node(rank):
        rng = np.random.RandomState(rank)
        try:
            while time.time() < stop:
                mgr.join_rendezvous(rank, rank, 4, f"h{rank}")
                for _ in range(rng.randint(1, 20)):
                    rnd, _, world, coord = mgr.get_comm_world(rank)
                    if world:
                        # invariants on any observed sealed world
                        if len(world) % 2:
                            errors.append(f"odd world {world}")
                        if not (4 <= len(world) <= 8):
                            errors.append(f"size {len(world)}")
                        if rank in world and not coord:
                            errors.append("sealed without coordinator")
                        break
                    time.sleep(0.001)
                if rng.rand() < 0.3:
                    mgr.remove_alive_node(rank)  # simulated crash
                time.sleep(rng.rand() * 0.01)
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [
        threading.Thread(target=node, args=(r,)) for r in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:5]

    # post-storm: clear every storm leftover (waiting stragglers AND a
    # possibly still-sealed world), then a clean rendezvous must seal —
    # proving the storm cannot wedge the manager's internal state.
    for r in range(8):
        mgr.remove_alive_node(r)
    for r in range(4):
        mgr.join_rendezvous(r, r, 4, f"h{r}")
    deadline = time.time() + 2
    world = {}
    while time.time() < deadline and not world:
        _, _, world, coord = mgr.get_comm_world(0)
        time.sleep(0.01)
    assert sorted(world) == [0, 1, 2, 3], world
    assert coord


def test_data_sharding_dispatch_and_requeue(master):
    c0, c1 = _client(master, 0), _client(master, 1)
    c0.report_dataset_shard_params(
        "train", dataset_size=100, shard_size=10, num_epochs=1
    )
    t0 = c0.get_task("train")
    t1 = c1.get_task("train")
    assert t0.shard_end - t0.shard_start == 10
    assert (t0.shard_start, t0.shard_end) != (t1.shard_start, t1.shard_end)
    assert c0.report_task_result("train", t0.task_id, success=True)

    # worker 1 dies with a task in flight → its shard is re-dispatched
    c1.report_node_status(NodeStatus.FAILED, exit_reason="killed")
    time.sleep(0.1)
    seen = set()
    while True:
        t = c0.get_task("train")
        if t.task_id < 0:
            break
        seen.add((t.shard_start, t.shard_end))
        c0.report_task_result("train", t.task_id, success=True)
    assert (t1.shard_start, t1.shard_end) in seen


def test_shard_checkpoint_roundtrip(master):
    c0 = _client(master, 0)
    c0.report_dataset_shard_params(
        "ds", dataset_size=40, shard_size=10, num_epochs=1
    )
    got = c0.get_task("ds")
    assert got.task_id >= 0
    ckpt = c0.get_shard_checkpoint("ds")
    assert ckpt
    # restore re-queues the in-flight shard
    assert c0.report_shard_checkpoint("ds", ckpt)
    ranges = []
    while True:
        t = c0.get_task("ds")
        if t.task_id < 0:
            break
        ranges.append((t.shard_start, t.shard_end))
        c0.report_task_result("ds", t.task_id)
    assert (got.shard_start, got.shard_end) in ranges
    assert len(ranges) == 4


def test_kv_and_sync(master):
    c0, c1 = _client(master, 0), _client(master, 1)
    assert c0.kv_store_set("coord", "h0:1234")
    assert c1.kv_store_get("coord") == "h0:1234"
    assert not c0.sync_finished("step-sync")
    c0.join_sync("step-sync")
    c1.join_sync("step-sync")
    assert c0.sync_finished("step-sync")


def test_speed_monitor_and_ckpt_sync(master):
    c0 = _client(master, 0)
    # rate math uses the master-side monotonic arrival clock (injected
    # here); the wall timestamp is watermark metadata only
    master.speed_monitor.collect_global_step(0, now=90.0)
    master.speed_monitor.collect_global_step(100, now=100.0)
    assert master.speed_monitor.running_speed == pytest.approx(10.0, rel=0.1)
    c0.report_ckpt_step(120)
    assert c0.get_min_ckpt_step() == 120


def test_status_flow():
    assert transition(NodeStatus.PENDING, NodeStatus.RUNNING).allowed
    assert not transition(NodeStatus.FAILED, NodeStatus.RUNNING).allowed
    assert not transition(NodeStatus.RUNNING, NodeStatus.RUNNING).allowed


def test_network_check_grouping_and_fault():
    mgr = NetworkCheckRendezvousManager()
    groups = mgr._group_nodes([0, 1, 2, 3])
    assert groups == [[0, 1], [2, 3]]
    mgr._check_round = 1
    groups2 = mgr._group_nodes([0, 1, 2, 3])
    assert groups2 != groups

    # node 2 fails both rounds → fault; node 3 only once → not fault
    mgr._check_round = 0
    for rank in (0, 1, 3):
        mgr.report_network_check_result(rank, True, 1.0)
    mgr.report_network_check_result(2, False, 0.0)
    mgr.next_check_round()
    for rank in (0, 1):
        mgr.report_network_check_result(rank, True, 1.0)
    mgr.report_network_check_result(2, False, 0.0)
    mgr.report_network_check_result(3, False, 0.0)
    fault, _ = mgr.check_fault_node()
    assert fault == [2]


def test_straggler_detection():
    mgr = NetworkCheckRendezvousManager()
    for rank in range(3):
        mgr.report_network_check_result(rank, True, 1.0)
    mgr.report_network_check_result(3, True, 5.0)
    stragglers, _ = mgr.get_stragglers(ratio=1.6)
    assert stragglers == [3]


def test_task_manager_timeout_requeue():
    tm = TaskManager(shard_timeout_s=0.05)
    tm.new_dataset("d", 20, 10)
    t = tm.get_task("d", worker_id=0)
    assert t.task_id >= 0
    time.sleep(0.1)
    n = tm._datasets["d"].recover_timeout_tasks(0.05)
    assert n == 1


def test_abort_fans_out_to_all_nodes(master):
    """An OOM (abort-classified) failure on one node must stop every node."""
    c0 = _client(master, 0)
    c1 = _client(master, 1)
    c0.report_heartbeat()
    c1.report_heartbeat()
    c0.report_failure(
        "Traceback ...\nRESOURCE_EXHAUSTED: out of memory allocating ...",
        level="process_error",
    )
    assert "abort_job" in c0.heartbeat_with_actions()
    assert "abort_job" in c1.heartbeat_with_actions()
    # actions drain: second heartbeat is clean
    assert c1.heartbeat_with_actions() == []


def test_unknown_failure_does_not_restart_dead_worker(master):
    """Plain exit-code reports must not queue a duplicate restart (the
    agent already restarts a dead worker itself)."""
    c0 = _client(master, 0)
    c0.report_failure("worker exit code 1", level="process_error")
    assert c0.heartbeat_with_actions() == []


def test_reregistration_clears_stale_prescriptions():
    """A replacement agent must never be handed a prescription queued
    against its dead predecessor: the slice drill's joiner was told
    relaunch_node (diagnosed from the ORIGINAL node's crash) and obeyed
    by exiting — looping the recovery it was the recovery for. A fresh
    registration drains the node's pending action queue."""
    from dlrover_tpu.common import messages as msgs
    from dlrover_tpu.diagnosis.manager import DiagnosisManager
    from dlrover_tpu.master.node_manager import JobManager
    from dlrover_tpu.master.servicer import MasterServicer

    jm = JobManager(num_workers=2)
    dm = DiagnosisManager()
    servicer = MasterServicer(job_manager=jm, diagnosis_manager=dm)

    # node 1 dies; the failure is diagnosed as needing a node relaunch
    dm.collect_failure(
        msgs.NodeFailureReport(
            node_id=1, error_data="killed: preempted", level="node_error"
        )
    )
    assert dm._pending_actions.get(1), "precondition: action queued"

    # the replacement registers (fresh incarnation)
    resp = servicer.get(
        msgs.NodeRegisterRequest(
            meta=msgs.NodeMeta(node_id=1, node_rank=1, host_addr="h1"),
            restart_count=0,
        )
    )
    assert resp.success
    # ...and the stale prescription is gone: its next heartbeat carries
    # no relaunch order
    hb = servicer.get(msgs.HeartbeatReport(node_id=1))
    assert not hb.actions, hb.actions


def test_worker_restart_requeues_inflight_shards():
    """A PLANNED worker restart (membership change / restart
    prescription) must re-queue the node's leased shard immediately:
    only node FAILURES re-queued before, so a voluntary restart leaked
    the lease and the dataset tail deadlocked until the 1800 s shard
    timeout (found by the slice-elasticity drill's grow phase)."""
    from dlrover_tpu.common import messages as msgs
    from dlrover_tpu.master.servicer import MasterServicer
    from dlrover_tpu.master.task_manager import TaskManager

    tm = TaskManager()
    tm.new_dataset("train", dataset_size=16, shard_size=8)
    servicer = MasterServicer(task_manager=tm)

    t1 = tm.get_task("train", worker_id=0)
    assert t1.task_id >= 0
    # the second shard goes out too — nothing left in todo
    t2 = tm.get_task("train", worker_id=0)
    assert t2.task_id >= 0
    assert tm.get_task("train", worker_id=0).task_type == "wait"

    # agent kills + respawns its worker: both leases come back
    servicer.report(msgs.WorkerRestartReport(node_id=0, reason="test"))
    t3 = tm.get_task("train", worker_id=0)
    assert t3.task_id >= 0, "lease was not re-queued"


def test_agent_registration_carries_slice_placement(monkeypatch):
    """The operator injects DLROVER_TPU_SLICE_INDEX per pod and GKE
    multislice exposes MEGASCALE_SLICE_ID; the agent must forward the
    real placement so the master's SliceTopology (whole-slice scaling,
    rdzv node_unit) isn't a cosmetic all-zeros map."""
    from dlrover_tpu.agent.agent import (
        ElasticLaunchConfig,
        ElasticTrainingAgent,
    )

    seen = {}

    class _T:
        addr = "localhost:1"

    class _Client:
        _t = _T()
        node_rank = 0

        def register_node(self, **kw):
            seen.update(kw)
            raise RuntimeError("stop after register")  # end run() early

    monkeypatch.setenv("DLROVER_TPU_SLICE_INDEX", "3")
    monkeypatch.setenv("DLROVER_TPU_SLICE_ID", "slice-3")
    agent = ElasticTrainingAgent(ElasticLaunchConfig(), _Client())
    with pytest.raises(RuntimeError):
        agent.run()
    assert seen["slice_index"] == 3
    assert seen["slice_id"] == "slice-3"

    # GKE multislice fallback
    seen.clear()
    monkeypatch.delenv("DLROVER_TPU_SLICE_INDEX")
    monkeypatch.delenv("DLROVER_TPU_SLICE_ID")
    monkeypatch.setenv("MEGASCALE_SLICE_ID", "1")
    agent2 = ElasticTrainingAgent(ElasticLaunchConfig(), _Client())
    with pytest.raises(RuntimeError):
        agent2.run()
    assert seen["slice_index"] == 1


# ---------------------------------------------------------------------------
# Live-reshard directive (eviction → survivors migrate instead of restart)
# ---------------------------------------------------------------------------


def test_reshard_plan_versioning_and_world_excision():
    from dlrover_tpu.master.rdzv_manager import RendezvousManager

    mgr = RendezvousManager()
    mgr.update_rdzv_params(min_nodes=1, max_nodes=8, waiting_timeout=0.0)
    for r in range(4):
        mgr.join_rendezvous(node_id=r, node_rank=r, local_world_size=1)
    _, _, world, _ = mgr.get_comm_world(0)
    assert set(world) == {0, 1, 2, 3}
    assert mgr.get_reshard_plan() == {"version": 0}

    v = mgr.plan_reshard([2, 3], dp_size=4, deadline_s=10.0, reason="drill")
    assert v == 1
    plan = mgr.get_reshard_plan()
    assert plan["dp_old"] == 4 and plan["dp_new"] == 2
    assert plan["lost_ranks"] == [2, 3]
    # lost ranks are excised but the round stays sealed for survivors
    _, _, world, _ = mgr.get_comm_world(0)
    assert set(world) == {0, 1}
    # the prune callback firing for a directive-listed rank is a no-op
    mgr.remove_alive_node(3)
    _, _, world, _ = mgr.get_comm_world(0)
    assert set(world) == {0, 1}
    # a SURVIVOR dying is a real failure: the world tears down
    mgr.remove_alive_node(0)
    _, _, world, _ = mgr.get_comm_world(0)
    assert world == {}

    # evicting everyone is rejected; versions stay monotonic
    with pytest.raises(ValueError):
        mgr.plan_reshard([0, 1], dp_size=2)
    assert mgr.plan_reshard([1], dp_size=2) == 2


def test_eviction_notice_issues_reshard_directive(master):
    c0, c1 = _client(master, 0), _client(master, 1)
    c0.join_rendezvous(4)
    c1.join_rendezvous(4)
    _, _, world, _ = c0.get_comm_world()
    assert len(world) == 2
    assert c0.get_reshard_plan().version == 0

    assert c0.report_eviction(
        [1], dp_size=2, deadline_s=5.0, reason="maintenance"
    )
    plan = c1.get_reshard_plan()
    assert plan.version == 1
    assert plan.dp_old == 2 and plan.dp_new == 1
    assert plan.lost_ranks == [1]
    assert plan.deadline_s == 5.0
    # survivor keeps the sealed round with rank 1 excised
    _, _, world, _ = c0.get_comm_world()
    assert world == {0: 4}
    # the evicted node failing afterwards must not tear the round down
    c1.report_node_status(NodeStatus.FAILED, exit_reason="evicted")
    time.sleep(0.1)
    _, _, world, _ = c0.get_comm_world()
    assert world == {0: 4}

    # an eviction that would leave no survivors is refused
    assert not c0.report_eviction([0, 1], dp_size=2)


def test_serving_eviction_issues_page_migration_directive(master):
    """The serving twin of the eviction flow: a replica's departure is
    reported over the wire and the master answers subsequent polls with
    a versioned page-migration directive naming victim + survivors."""
    clients = []
    for nid in (10, 11, 12):
        c = MasterClient(master.addr, node_id=nid)
        c.register_node(node_type=NodeType.SERVING)
        clients.append(c)
    c10, c11, c12 = clients

    assert c10.get_serving_reshard().version == 0  # none pending

    assert c10.report_serving_eviction(
        "serving-11", in_flight=2, deadline_s=3.0, reason="evict"
    )
    d = c12.get_serving_reshard()
    assert d.version == 1
    assert d.victim == "serving-11"
    # survivors default to every OTHER registered serving replica
    assert d.survivors == ["serving-10", "serving-12"]
    assert d.deadline_s == 3.0 and d.reason == "evict"

    # directives version monotonically, latest wins
    assert c10.report_serving_eviction("serving-12", reason="drain")
    d2 = c10.get_serving_reshard()
    assert d2.version == 2 and d2.victim == "serving-12"
    for c in clients:
        c.close()
