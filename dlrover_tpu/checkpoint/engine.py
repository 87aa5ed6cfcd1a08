"""Worker-side checkpoint engine: HBM → host shared memory, async persist.

Reference: dlrover/python/elastic_agent/torch/ckpt_saver.py SharedMemoryHandler
(:209) + CheckpointEngine (flash_checkpoint/engine.py:136,297). The worker
blocks only for the device→host copy into shared memory (~HBM bandwidth);
persistence to storage happens in the *agent* process (or a background
thread in standalone mode), so a worker crash after staging never loses the
checkpoint — the agent still holds the bytes.
"""

import contextlib
import os
import threading
import time
from typing import Any, Dict, Optional, Tuple

import jax

from dlrover_tpu.common.constants import GraftEnv
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.common.multi_process import (
    SharedDictClient,
    SharedLockClient,
    SharedQueueClient,
    attach_shared_memory,
    create_shared_memory,
)
from dlrover_tpu.checkpoint import core
from dlrover_tpu.checkpoint.storage import PosixStorage
from dlrover_tpu.observability import telemetry
from dlrover_tpu.observability.tracing import get_tracer

logger = get_logger(__name__)


@contextlib.contextmanager
def _phase(phases: Dict[str, float], name: str, **args):
    """One phase of a save or a restore: a child span ``ckpt.<name>``
    of whatever span is open, and its seconds added to ``phases``
    (kept with tracing off too, for the ``CheckpointRecord``)."""
    t0 = time.perf_counter()
    with get_tracer().span("ckpt." + name, **args):
        try:
            yield
        finally:
            phases[name] = (
                phases.get(name, 0.0) + time.perf_counter() - t0
            )


def shm_name(process_index: Optional[int] = None) -> str:
    run_id = os.environ.get(GraftEnv.RUN_ID, "default")
    pi = jax.process_index() if process_index is None else process_index
    return f"dlrover_tpu_ckpt_{run_id}_{pi}"


class CheckpointEngine:
    """Stages state pytrees into shm; delegates persist to the saver."""

    def __init__(
        self,
        ckpt_dir: str,
        master_client=None,
        use_agent: Optional[bool] = None,
        storage=None,
        replica=None,
    ):
        self.ckpt_dir = ckpt_dir
        self._client = master_client
        self._storage = storage or PosixStorage()
        self._replica = replica  # Optional[replica.ReplicaManager]
        self._shm = None
        self._local_step = -1
        if use_agent is None:
            from dlrover_tpu.common.multi_process import broker_alive

            use_agent = broker_alive("queue_ckpt")
        self._use_agent = use_agent
        if use_agent:
            self._queue = SharedQueueClient("ckpt")
            self._meta = SharedDictClient("ckpt_meta")
            self._lock = SharedLockClient("ckpt")
        else:
            self._queue = None
            self._meta = {}
            self._lock = threading.Lock()
            self._persist_thread: Optional[threading.Thread] = None

    # ---- save ------------------------------------------------------------

    def save_to_memory(self, step: int, state: Any) -> bool:
        """Stage ``state`` into shared memory. Returns False if skipped.

        The stall is one span, ``ckpt.save_memory``, whose children are
        its phases: ``ckpt.plan``, ``ckpt.lock_wait``, ``ckpt.shm_alloc``
        and, from ``core.write_pack``, ``ckpt.d2h_wait`` and
        ``ckpt.shm_copy``. The same seconds go onto the
        ``CheckpointRecord`` and the log line. A save that is skipped or
        raises records no ``ckpt.save_memory``."""
        t0 = time.perf_counter()
        phases: Dict[str, float] = {}
        stage_span = get_tracer().span("ckpt.save_memory", step=step)
        try:
            staged = self._stage(step, state, phases)
        except BaseException:
            stage_span.cancel()
            raise
        if staged is None:
            # saver busy persisting the previous step: skip this save
            # (reference: engine.py:53 check_all_rank_ready skip path)
            stage_span.cancel()
            logger.warning("step %d: saver busy, skipping memory save", step)
            return False
        total, meta = staged
        stage_span.end(nbytes=total)
        stall_s = time.perf_counter() - t0
        hub = telemetry.get_hub()
        if hub.enabled:
            hub.publish(
                telemetry.CheckpointRecord(
                    kind="save_memory",
                    step=step,
                    seconds=stall_s,
                    nbytes=total,
                    tier="memory",
                    phases=telemetry.format_phases(phases),
                )
            )
        if self._replica is not None:
            # stream the fresh pack to ring peers off the critical path
            # (reference: replica.py backup hooked at engine.py:328)
            self._replica.backup_async(meta, shm_lock=self._lock)
        if self._client is not None:
            try:
                self._client.report_ckpt_step(step)
            except Exception:  # noqa: BLE001
                logger.warning("ckpt step report failed", exc_info=True)
        logger.info(
            "staged step %d to shm in %.3fs (%.1f MB): %s",
            step,
            stall_s,
            total / 1e6,
            " ".join(f"{k} {v:.3f}s" for k, v in phases.items()),
        )
        return True

    def _stage(
        self, step: int, state: Any, phases: Dict[str, float]
    ) -> Optional[Tuple[int, Dict]]:
        """The phases of a memory save; the pack's bytes and the meta
        entry it published, or None when the saver holds the lock."""
        with _phase(phases, "plan", step=step):
            entries, payload = core.plan_pack(state)
            header = core.header_bytes(
                step, entries, {"dir": self.ckpt_dir}
            )
            total = core.pack_size(header, payload)
        with _phase(phases, "lock_wait", step=step):
            locked = self._acquire(blocking=False)
        if not locked:
            return None
        try:
            if self._shm is None or self._shm.size < total:
                with _phase(phases, "shm_alloc", step=step, nbytes=total):
                    self._shm = create_shared_memory(
                        shm_name(), _round_up(total)
                    )
            used = core.write_pack(
                memoryview(self._shm.buf),
                step,
                state,
                entries,
                header=header,
                phases=phases,
            )
            meta = {
                "step": step,
                "used": used,
                "dir": self.ckpt_dir,
                "shm": self._shm.name,
                "process_index": jax.process_index(),
                "process_count": jax.process_count(),
                "time": time.time(),
            }
            if self._use_agent:
                self._meta.set("latest", meta)
            else:
                self._meta["latest"] = meta
            self._local_step = step
        finally:
            self._release()
        return total, meta

    def save_to_storage(self, step: int, state: Any) -> bool:
        """Stage + trigger async persist."""
        if not self.save_to_memory(step, state):
            return False
        if self._use_agent:
            return self._queue.put({"type": "persist", "step": step})
        # standalone: persist on a background thread
        if self._persist_thread and self._persist_thread.is_alive():
            self._persist_thread.join()
        meta = dict(self._meta["latest"])
        self._persist_thread = threading.Thread(
            target=self._persist_standalone, args=(meta,), daemon=True
        )
        self._persist_thread.start()
        return True

    def wait_for_persist(self, timeout: float = 300.0) -> bool:
        """Block until the latest staged step is committed to storage.

        Returns False — and publishes a failed ``persist_wait``
        CheckpointRecord — when the commit does not land inside
        ``timeout``; a silent return here previously let callers tear
        down hosts believing the disk tier was durable."""
        ok = True
        if self._use_agent:
            from dlrover_tpu.checkpoint.storage import read_tracker

            deadline = time.time() + timeout
            while True:
                if read_tracker(self.ckpt_dir, self._storage) == (
                    self._local_step
                ):
                    break
                if time.time() >= deadline:
                    ok = False
                    break
                time.sleep(0.1)
        elif self._persist_thread:
            self._persist_thread.join(timeout)
            ok = not self._persist_thread.is_alive()
        if not ok:
            logger.error(
                "persist of step %d did not commit within %.0fs; the "
                "storage tier is STALE for this step",
                self._local_step,
                timeout,
            )
            hub = telemetry.get_hub()
            if hub.enabled:
                hub.publish(
                    telemetry.CheckpointRecord(
                        kind="persist_wait",
                        step=self._local_step,
                        seconds=timeout,
                        ok=False,
                        tier="storage",
                    )
                )
        return ok

    def _persist_standalone(self, meta):
        from dlrover_tpu.checkpoint.saver import persist_pack

        shm = attach_shared_memory(meta["shm"])
        try:
            persist_pack(
                memoryview(shm.buf)[: meta["used"]],
                meta["dir"],
                meta["step"],
                meta["process_index"],
                meta["process_count"],
                self._storage,
            )
        finally:
            shm.close()

    # ---- load ------------------------------------------------------------

    def load(
        self,
        target: Any,
        shardings: Any = None,
        step: Optional[int] = None,
        partial: bool = False,
    ) -> Optional[Any]:
        """Restore: shm if fresh, else committed storage. None if nothing.

        ``partial``: leaves absent from the checkpoint keep the
        target's (concrete) values — the state-tree-upgrade path
        (core.restore_tree). A tree-contract violation
        (core.RestoreMismatchError) in the memory/replica TIERS falls
        through (they are caches; storage is the source of truth), but
        if no tier produces a state the mismatch re-raises rather than
        masquerading as "no checkpoint" — a silent from-scratch restart
        is the worst outcome of a restore bug."""
        mismatch: Optional[core.RestoreMismatchError] = None
        # "failover." prefix: restore is a phase of the recovery timeline,
        # so the drill's phase extraction picks it up with the rest
        span = get_tracer().span("failover.restore")
        # seconds by phase, over the tiers tried: restore_map (attach or
        # mmap the pack, parse its header), then core.restore_tree's
        # read, h2d and device_wait
        phases: Dict[str, float] = {}
        with span:
            tier = "none"
            try:
                state = self._load_from_memory(
                    target, shardings, step, partial, phases
                )
                if state is not None:
                    tier = "memory"
            except core.RestoreMismatchError as e:
                mismatch = e
                state = None
            if state is None:
                try:
                    state = self._load_from_replica(
                        target, shardings, step, partial, phases
                    )
                    if state is not None:
                        tier = "replica"
                except core.RestoreMismatchError as e:
                    mismatch = mismatch or e
                    state = None
            if state is None:
                state = self.load_from_storage(
                    target, shardings, step, partial, phases
                )
                if state is not None:
                    tier = "storage"
            span.args["tier"] = tier
            if state is None and mismatch is not None:
                raise mismatch
        self._publish_restore(tier, span.end(), phases)
        return state

    def _publish_restore(
        self, tier: str, seconds: float, phases: Dict[str, float]
    ):
        hub = telemetry.get_hub()
        if hub.enabled:
            hub.publish(
                telemetry.CheckpointRecord(
                    kind="restore",
                    step=self._local_step,
                    seconds=seconds,
                    ok=tier != "none",
                    tier=tier,
                    phases=telemetry.format_phases(phases),
                )
            )

    def _load_from_memory(
        self, target, shardings, step, partial=False, phases=None
    ):
        phases = {} if phases is None else phases
        try:
            meta = self._meta.get("latest")
            if not meta:
                return None
            if step is not None and meta["step"] != step:
                return None
            if self._client is not None:
                # all ranks must hold the same staged step
                min_step = self._client.get_min_ckpt_step()
                if min_step != meta["step"]:
                    logger.warning(
                        "staged step %s inconsistent with cluster min %s",
                        meta["step"],
                        min_step,
                    )
                    return None
            with _phase(phases, "restore_map", nbytes=meta["used"]):
                shm = attach_shared_memory(meta["shm"])
            idx = core.PackIndex()
            try:
                with _phase(phases, "restore_map", nbytes=meta["used"]):
                    idx.add_pack(memoryview(shm.buf)[: meta["used"]])
                # returns once everything is on the device
                state = core.restore_tree(
                    target, idx, shardings, partial=partial, phases=phases
                )
                step = idx.step
            finally:
                # release the views on every path so the segment can
                # close without 'exported pointers exist' GC noise
                idx.close()
                try:
                    shm.close()
                except BufferError:
                    pass
            logger.info("restored step %d from shared memory", step)
            return state
        except (FileNotFoundError, KeyError):
            return None
        except core.RestoreMismatchError:
            raise  # tree-contract violation: load() decides the fate
        except Exception:  # noqa: BLE001
            logger.warning("memory restore failed", exc_info=True)
            return None

    def _load_from_replica(
        self, target, shardings, step, partial=False, phases=None
    ):
        """Local shm lost (host replaced): pull our pack from a ring peer.

        Reference: engine.py:349 _restore_memory_from_replica.
        """
        if self._replica is None:
            return None
        try:
            if step is None and self._client is not None:
                # pin to the cluster-consistent step: a peer may hold a step
                # the other ranks skipped ("saver busy"), and restoring it
                # would silently diverge this rank from the rest
                min_step = self._client.get_min_ckpt_step()
                if min_step > 0:
                    step = min_step
            # one dead/corrupt donor must not abort the tier: exclude the
            # failing holder and ask the next ring peer for the same pack
            tried: set = set()
            while True:
                hit = self._replica.fetch(
                    step=step, exclude=tuple(tried), with_holder=True
                )
                if hit is None:
                    return None
                got_step, pack, holder = hit
                try:
                    idx = core.PackIndex()
                    idx.add_pack(memoryview(pack))
                    state = core.restore_tree(
                        target, idx, shardings, partial=partial,
                        phases=phases,
                    )
                except core.RestoreMismatchError:
                    raise  # tree-contract violation: load() decides the fate
                except Exception:  # noqa: BLE001
                    logger.warning(
                        "replica restore from holder rank %d failed; "
                        "trying next peer",
                        holder,
                        exc_info=True,
                    )
                    tried.add(holder)
                    continue
                logger.info(
                    "restored step %d from peer replica (holder rank %d)",
                    got_step,
                    holder,
                )
                return state
        except core.RestoreMismatchError:
            raise  # tree-contract violation: load() decides the fate
        except Exception:  # noqa: BLE001
            logger.warning("replica restore failed", exc_info=True)
            return None

    def load_from_storage(
        self, target, shardings=None, step=None, partial=False, phases=None
    ):
        phases = {} if phases is None else phases
        from dlrover_tpu.checkpoint.storage import read_tracker

        step = step if step is not None else read_tracker(
            self.ckpt_dir, self._storage
        )
        if step is None:
            return None
        step_dir = os.path.join(self.ckpt_dir, f"step_{step}")
        idx = core.PackIndex()
        packs = [
            f
            for f in self._storage.listdir(step_dir)
            if f.endswith(".pack")
        ]
        if not packs:
            return None
        with _phase(phases, "restore_map"):
            for name in packs:
                mv = self._storage.mmap(os.path.join(step_dir, name))
                idx.add_pack(mv)
        state = core.restore_tree(
            target, idx, shardings, partial=partial, phases=phases
        )
        logger.info("restored step %d from %s", step, step_dir)
        return state

    # ---- helpers ---------------------------------------------------------

    def _acquire(self, blocking=True) -> bool:
        return self._lock.acquire(blocking=blocking)

    def _release(self):
        self._lock.release()


def _round_up(n: int, unit: int = 1 << 20) -> int:
    return (n + unit - 1) // unit * unit
