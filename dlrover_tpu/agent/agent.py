"""Per-host elastic agent: rendezvous → spawn worker → supervise → recover.

Reference: ElasticTrainingAgent (elastic_agent/torch/training.py:362-729).
TPU differences: one worker *process per host* drives all local chips (jax
owns them), so there is no per-GPU fork; membership changes and failures are
handled by re-rendezvous + process restart, with flash-checkpoint persist
hooks before restarts.
"""

import os
import signal
import subprocess
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from dlrover_tpu.common import compile_cache
from dlrover_tpu.common.constants import (
    DefaultValues,
    GraftEnv,
    NodeStatus,
    RendezvousName,
    TrainingExceptionLevel,
)
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.rendezvous import (
    MasterRendezvousHandler,
    RendezvousOutcome,
)
from dlrover_tpu.observability import telemetry
from dlrover_tpu.observability.tracing import get_tracer

logger = get_logger(__name__)


@dataclass
class ElasticLaunchConfig:
    """Reference: ElasticLaunchConfig (training.py:117)."""

    min_nodes: int = 1
    max_nodes: int = 1
    node_id: int = 0
    local_chips: int = 1
    max_restarts: int = DefaultValues.RELAUNCH_BUDGET
    monitor_interval_s: float = 2.0
    heartbeat_interval_s: float = DefaultValues.HEARTBEAT_INTERVAL_S
    rdzv_timeout_s: float = DefaultValues.RDZV_TIMEOUT_S
    network_check: bool = False
    comm_perf_test: bool = False
    exclude_straggler: bool = False
    node_unit: int = 1
    coordinator_port: int = 7010
    # persistent XLA compile-cache dir for workers, used when
    # JAX_COMPILATION_CACHE_DIR is not set ("" = the fixed in-checkout
    # default, common/compile_cache.py); same-shape restarts deserialize
    # the cached executable instead of recompiling
    compile_cache_dir: str = ""
    entrypoint: List[str] = field(default_factory=list)
    env: Dict[str, str] = field(default_factory=dict)

    def auto_configure(self):
        """Fill node/chip counts from the environment when unset."""
        if GraftEnv.NODE_NUM in os.environ:
            n = int(os.environ[GraftEnv.NODE_NUM])
            self.min_nodes = self.max_nodes = n
        if GraftEnv.NODE_ID in os.environ:
            self.node_id = int(os.environ[GraftEnv.NODE_ID])
        if GraftEnv.LOCAL_CHIPS in os.environ:
            self.local_chips = int(os.environ[GraftEnv.LOCAL_CHIPS])


class WorkerProcess:
    """The single training process on this host.

    stderr is teed: echoed through to the agent's stderr AND kept as a tail
    ring so failure reports carry the actual traceback — the master's
    diagnosis rules classify on it (OOM/ICI/hang/user-error)."""

    def __init__(self, cmd: List[str], env: Dict[str, str]):
        self._cmd = cmd
        full_env = dict(os.environ)
        full_env.update(env)
        # SIGUSR2 py-stack dumper for hang diagnosis (collectors.py)
        full_env.setdefault("DLROVER_TPU_STACK_DUMP", "1")
        self._tail: "deque[str]" = deque(maxlen=200)
        self._proc = subprocess.Popen(
            cmd, env=full_env, stderr=subprocess.PIPE, text=True
        )
        self._pump = threading.Thread(
            target=self._pump_stderr, name="worker-stderr", daemon=True
        )
        self._pump.start()

    def _pump_stderr(self):
        try:
            for line in self._proc.stderr:
                self._tail.append(line)
                try:
                    sys.stderr.write(line)
                except OSError:
                    # agent stderr gone (EPIPE): keep draining the pipe so
                    # the worker never blocks on a full buffer
                    pass
        except ValueError:  # stream closed during shutdown
            pass

    def stderr_tail(self, max_chars: int = 4000) -> str:
        # the pump races the exit we just observed — wait for it to drain
        # the pipe so the final traceback makes it into the report
        self._pump.join(timeout=5.0)
        return "".join(self._tail)[-max_chars:]

    @property
    def pid(self) -> int:
        return self._proc.pid

    def poll(self) -> Optional[int]:
        return self._proc.poll()

    def terminate(self, grace_s: float = 10.0):
        if self._proc.poll() is not None:
            return
        self._proc.send_signal(signal.SIGTERM)
        try:
            self._proc.wait(grace_s)
        except subprocess.TimeoutExpired:
            self._proc.kill()
            self._proc.wait()


class ElasticTrainingAgent:
    def __init__(self, config: ElasticLaunchConfig, client: MasterClient):
        self.config = config
        self.client = client
        self._worker: Optional[WorkerProcess] = None
        self._outcome: Optional[RendezvousOutcome] = None
        self._remaining_restarts = config.max_restarts
        self._pending_restart = threading.Event()
        self._pending_abort = threading.Event()
        self._pending_relaunch = threading.Event()
        self._stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        self._ckpt_saver = None  # AsyncCheckpointSaver, attached by launcher

    def attach_ckpt_saver(self, saver):
        self._ckpt_saver = saver

    # ---- setup -----------------------------------------------------------

    def _start_heartbeats(self):
        def loop():
            while not self._stop.wait(self.config.heartbeat_interval_s):
                try:
                    actions = self.client.heartbeat_with_actions()
                    if "restart_worker" in actions:
                        logger.info("master prescribed worker restart")
                        self._pending_restart.set()
                    if "abort_job" in actions:
                        logger.error("master prescribed job abort")
                        self._pending_abort.set()
                    if "relaunch_node" in actions:
                        logger.warning("master prescribed node relaunch")
                        self._pending_relaunch.set()
                except Exception:  # noqa: BLE001 — master may be restarting
                    logger.warning("heartbeat failed", exc_info=True)

        self._hb_thread = threading.Thread(
            target=loop, name="agent-heartbeat", daemon=True
        )
        self._hb_thread.start()

    def _rendezvous(self) -> RendezvousOutcome:
        handler = MasterRendezvousHandler(
            self.client,
            self.client.node_rank,
            self.config.local_chips,
            timeout_s=self.config.rdzv_timeout_s,
        )
        with get_tracer().span(
            "failover.rendezvous", node=self.config.node_id
        ) as sp:
            outcome = handler.next_rendezvous()
            sp.args["rdzv_round"] = outcome.round
            sp.args["world_size"] = outcome.num_processes
        logger.info(
            "rendezvous round %d: %d processes, %d chips, coordinator=%s",
            outcome.round,
            outcome.num_processes,
            outcome.global_chips,
            outcome.coordinator,
        )
        return outcome

    def _worker_env(self, outcome: RendezvousOutcome) -> Dict[str, str]:
        env = {
            GraftEnv.MASTER_ADDR: self.client._t.addr,
            GraftEnv.NODE_ID: str(self.config.node_id),
            GraftEnv.NODE_RANK: str(self.client.node_rank),
            GraftEnv.NODE_NUM: str(outcome.num_processes),
            # jax.distributed bootstrap — consumed by
            # dlrover_tpu.train.distributed.init_distributed()
            "DLROVER_TPU_COORDINATOR": outcome.coordinator,
            "DLROVER_TPU_NUM_PROCESSES": str(outcome.num_processes),
            "DLROVER_TPU_PROCESS_ID": str(outcome.process_id),
            "DLROVER_TPU_RDZV_ROUND": str(outcome.round),
            "DLROVER_TPU_RESTART_COUNT": str(
                self.config.max_restarts - self._remaining_restarts
            ),
            # flight recorder: the worker's spans carry role=worker so the
            # merged timeline separates it from this agent's (the trace/
            # telemetry dirs themselves inherit via the environment copy)
            GraftEnv.TRACE_ROLE: "worker",
            # the entrypoint script must resolve the framework (and the
            # user's project) the same way the agent did
            "PYTHONPATH": os.pathsep.join(
                p
                for p in (
                    os.getcwd(),
                    os.environ.get("PYTHONPATH", ""),
                )
                if p
            ),
        }
        if compile_cache.ENV not in os.environ:
            # persistent XLA compile cache across worker restarts: a
            # restarted worker whose mesh shape was compiled before
            # (same world, or a prior round at the new world size)
            # deserializes the step instead of recompiling it, which
            # dominates the recovery budget. The environment's directory
            # is inherited, never overridden.
            env[compile_cache.ENV] = compile_cache.compile_cache_dir(
                self.config.compile_cache_dir
            )
        env.update(self.config.env)
        return env

    def _initialize_worker(self):
        self._outcome = self._rendezvous()
        env = self._worker_env(self._outcome)
        self._worker = WorkerProcess(self.config.entrypoint, env)
        get_tracer().instant(
            "failover.spawn",
            node=self.config.node_id,
            worker_pid=self._worker.pid,
            rdzv_round=self._outcome.round,
            restart=self.config.max_restarts - self._remaining_restarts,
        )
        logger.info(
            "spawned worker pid=%d round=%d",
            self._worker.pid,
            self._outcome.round,
        )

    # ---- supervision hot loop -------------------------------------------

    def run(self) -> int:
        """Supervise until success, fatal failure, or restart exhaustion."""
        # slice placement: the operator injects DLROVER_TPU_SLICE_INDEX
        # per pod (cluster/crd.py); multislice GKE runtimes expose
        # MEGASCALE_SLICE_ID — either way the master's SliceTopology
        # (whole-slice scaling, rdzv node_unit) needs the real index,
        # not a cosmetic 0
        slice_raw = os.environ.get(
            "DLROVER_TPU_SLICE_INDEX",
            os.environ.get("MEGASCALE_SLICE_ID", ""),
        )
        try:
            slice_index = int(slice_raw)
        except ValueError:
            logger.warning(
                "malformed slice index %r in the environment; "
                "registering as slice 0 — whole-slice scaling will "
                "treat this host as slice 0's",
                slice_raw,
            )
            slice_index = 0
        self.client.register_node(
            local_chips=self.config.local_chips,
            slice_id=os.environ.get("DLROVER_TPU_SLICE_ID", slice_raw),
            slice_index=slice_index,
        )
        self._start_heartbeats()
        self._initialize_worker()
        try:
            return self._invoke_run()
        finally:
            self._stop.set()
            if self._worker:
                self._worker.terminate()

    def _safe_report(self, fn, *args, **kwargs):
        """Status reports must not crash the agent if the master is gone
        (the master legitimately exits first when the dataset finishes).
        Per-call retry cap so shutdown isn't held up by a dead master."""
        try:
            return fn(*args, retries=2, **kwargs)
        except Exception:  # noqa: BLE001
            logger.warning("master unreachable for %s", fn.__name__)
            return None

    def _invoke_run(self) -> int:
        while True:
            time.sleep(self.config.monitor_interval_s)
            rc = self._worker.poll()
            if self._pending_abort.is_set():
                # diagnosis decided the workload is unrecoverable
                # (user error / OOM): stop burning the restart budget
                self._save_ckpt_to_storage()
                self._worker.terminate()
                self._safe_report(
                    self.client.report_node_status,
                    NodeStatus.FAILED,
                    exit_reason="fatal_error",
                )
                return 1
            if self._pending_relaunch.is_set():
                # hardware fault: exit so the platform reschedules this
                # node; "killed" keeps the relaunch budget intact
                self._save_ckpt_to_storage()
                self._worker.terminate()
                self._safe_report(
                    self.client.report_node_status,
                    NodeStatus.FAILED,
                    exit_reason="killed",
                )
                return 2
            if rc is None:
                if self._pending_restart.is_set():
                    self._pending_restart.clear()
                    logger.info("diagnosis action: restarting worker")
                    self._save_ckpt_to_storage()
                    if not self._restart_worker():
                        return 1
                elif self._membership_changed():
                    logger.info(
                        "membership changed; checkpoint + restart workers"
                    )
                    self._save_ckpt_to_storage()
                    if not self._restart_worker():
                        return 1
                continue
            if rc == 0:
                logger.info("worker succeeded")
                self._safe_report(
                    self.client.report_node_status, NodeStatus.SUCCEEDED
                )
                return 0
            # failure path (reference: training.py:687,665,704)
            logger.warning("worker exited rc=%d", rc)
            # detect mark: the agent's poll is the first component to
            # learn the worker died — everything downstream (persist,
            # rendezvous, respawn, first step back) is measured from here
            get_tracer().instant(
                "failover.worker_exit", node=self.config.node_id, rc=rc
            )
            hub = telemetry.get_hub()
            if hub.enabled:
                hub.publish(
                    telemetry.ElasticEvent(
                        kind="worker_exit",
                        node_id=self.config.node_id,
                        restart=self.config.max_restarts
                        - self._remaining_restarts,
                        detail=f"rc={rc}",
                    )
                )
            self._safe_report(
                self.client.report_failure,
                f"worker exit code {rc}\n{self._worker.stderr_tail()}",
                level=TrainingExceptionLevel.PROCESS_ERROR,
                restart_count=self.config.max_restarts
                - self._remaining_restarts,
            )
            self._save_ckpt_to_storage()
            if self._remaining_restarts > 0:
                self._remaining_restarts -= 1
                if not self._restart_worker():
                    return rc
            else:
                self._safe_report(
                    self.client.report_node_status,
                    NodeStatus.FAILED,
                    exit_reason="fatal_error",
                )
                return rc

    def _membership_changed(self) -> bool:
        """A node is waiting to join (scale-up) or the world shrank."""
        try:
            return self.client.num_nodes_waiting() > 0
        except Exception:  # noqa: BLE001
            return False

    def _restart_worker(self) -> bool:
        """Re-rendezvous + respawn. False when the master is gone (job over
        or master crashed) — the caller exits instead of raising."""
        # a restart satisfies any restart prescription that raced with it
        self._pending_restart.clear()
        if self._worker:
            self._worker.terminate()
            # the killed worker can never complete an in-flight shard
            # lease: tell the master to re-queue it NOW (the failure
            # path re-queues via node-down; this voluntary path must
            # do it explicitly or the dataset tail deadlocks)
            self._safe_report(
                self.client.report_worker_restart, "planned restart"
            )
        try:
            self._initialize_worker()
            return True
        except Exception:  # noqa: BLE001
            logger.exception(
                "restart rendezvous failed; master unreachable — exiting"
            )
            return False

    def _save_ckpt_to_storage(self):
        """Persist any staged in-memory checkpoint before losing the world."""
        if self._ckpt_saver is not None:
            with get_tracer().span(
                "failover.ckpt_persist", node=self.config.node_id
            ):
                try:
                    self._ckpt_saver.save_shm_to_storage()
                except Exception:  # noqa: BLE001
                    logger.exception("emergency checkpoint persist failed")
