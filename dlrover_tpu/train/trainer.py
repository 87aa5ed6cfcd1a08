"""High-level trainer: the AtorchTrainer analog.

Reference: atorch/atorch/trainer/atorch_trainer.py (AtorchTrainer:136 —
HF-Trainer-shaped loop owning train/eval/save/log cadences, flash-ckpt
integration, and master metric reporting). TPU version: one jitted step
from TrainStepBuilder over a mesh, Flash Checkpoint resume + cadenced
saves, loss-spike detection and step timing from the observability tier,
global-step reports to the elastic master when one is present.
"""

import os
import time
from dataclasses import dataclass, field
from typing import (
    Any, Callable, Dict, Iterable, List, Optional, Tuple, Union,
)

import jax
import jax.numpy as jnp
import optax

from dlrover_tpu.common import compile_cache
from dlrover_tpu.common.constants import GraftEnv
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.models.config import ModelConfig
from dlrover_tpu.observability import telemetry
from dlrover_tpu.observability.loss_spike import LossSpikeDetector
from dlrover_tpu.observability.profiler import step_clock
from dlrover_tpu.observability.tracing import get_tracer
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.train.callbacks import (
    Callback,
    CallbackList,
    LossSpikeCallback,
    TrainerControl,
)
from dlrover_tpu.train.train_step import (
    TrainStepBuilder,
    batch_sharding,
    build_eval_step,
    init_train_state,
    restore_or_init_train_state,
)

logger = get_logger(__name__)


@dataclass
class TrainerArgs:
    """Reference: TrainingArguments consumed by AtorchTrainer."""

    output_dir: str = "/tmp/dlrover_tpu_out"
    max_steps: int = 1000
    log_interval: int = 10
    save_interval: int = 100          # async disk persist cadence (steps)
    memory_save_interval: int = 0     # extra shm-only staging cadence; 0=off
    eval_interval: int = 0            # 0 = no eval during training
    eval_steps: int = 8
    seed: int = 0
    resume: bool = True
    # resume from this exact committed step instead of the latest
    # (reference: atorch_trainer's resume_from_checkpoint semantics)
    resume_from_step: Optional[int] = None
    # state-tree-upgrade resume: leaves missing from the checkpoint
    # (new fp8/optimizer slots) keep the fresh init values instead of
    # failing the restore; params still restore exactly or raise
    resume_partial: bool = False
    grad_accum: int = 1
    attn_impl: str = "auto"
    detect_loss_spikes: bool = True
    report_to_master: bool = True
    # run a final evaluation when the loop exits (even without cadence)
    eval_at_end: bool = False
    # sample one step under jax.profiler.trace every N steps and parse
    # the per-op runtime breakdown (observability/runtime_timer.py —
    # the xpu_timer analog); 0 = off
    profile_interval: int = 0
    # keep N batches in flight to the device ahead of the step (async
    # device_put H2D overlap — train.data_utils.prefetch_to_device, the
    # reference GPU preloader analog); 0 = off
    prefetch: int = 0
    # fuse K train steps into ONE jitted device program (a lax.scan over
    # stacked batches) and drain the previous block's per-step metrics
    # while the next block computes; 1 = the classic per-step loop.
    # Save/eval/memory-save cadences and max_steps stay exact for any K:
    # blocks auto-shrink to land on every boundary. Callback control
    # flags (should_save/should_eval/should_stop) and elastic events are
    # honored at the NEXT block boundary — worst-case response is one
    # block.
    block_k: int = 1
    # ZeRO update sharding: reduce-scatter grads, run the optimizer on
    # 1/dp of the flat stream, all-gather params
    # (parallel.sharding.CommConfig / train_step.resolve_update_sharding;
    # silently falls back to the replicated step when the config or
    # optimizer is incompatible — the builder logs why). False = off;
    # "zero1" = one deferred reduce-scatter per step; "zero2" =
    # per-microbatch scattered accumulation (no full-grad buffer across
    # the accum scan); True = legacy alias for "zero2"
    update_sharding: Union[bool, str] = False
    # fixed gradient-collective bucket size (MB of f32 payload)
    comm_bucket_mb: float = 4.0
    # wire dtype for the bucketed exchange: "float32" (bitwise),
    # "bfloat16", or "int8" (blockwise-scaled, EQuARX-style)
    comm_wire_dtype: str = "float32"
    # override wire dtype when the dp axis crosses DCN slices; None =
    # use comm_wire_dtype everywhere
    comm_wire_dtype_dcn: Optional[str] = None
    # in-graph health sentinels (observability/sentinels.py): numeric
    # health scalars computed inside the jitted step, riding the
    # existing metrics drain (zero extra host syncs). Also enables the
    # host-side watchdog — anomaly classification (AnomalyRecords on
    # the hub) plus rate-limited triggered captures when a runtime
    # timer is available.
    health_sentinels: bool = False
    # chain the non-finite gradient guard (observability/numeric.py) in
    # front of the optimizer: None = off, "skip" = drop the whole
    # update when any entry is non-finite, "zero" = zero just the
    # offending entries
    sanitize_grads: Optional[str] = None


class Trainer:
    """Own the whole training loop for one model + mesh + optimizer.

    ``train_iter`` yields batch dicts ({"tokens", "targets", ...}) of
    GLOBAL batch size; the trainer handles device placement/sharding.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        args: TrainerArgs,
        train_iter: Iterable[Dict],
        optimizer: optax.GradientTransformation,
        mesh=None,
        eval_iter_fn: Optional[Callable[[], Iterable[Dict]]] = None,
        master_client=None,
        loss_fn: Optional[Callable] = None,
        rules=None,
        callbacks: Optional[List[Callback]] = None,
        step_builder: Optional[TrainStepBuilder] = None,
        init_state_fn: Optional[Callable] = None,
        eval_step_fn: Optional[Callable] = None,
    ):
        """``step_builder``/``init_state_fn``/``eval_step_fn``: hand in
        the fully-configured lowering (e.g. from ``auto_accelerate`` —
        AccelerateResult.step_builder/.init_state/.eval_step) instead of
        the ones built here from args. This preserves plan details
        TrainerArgs cannot express (sp attention override, offloaded
        optimizer state born on host) across training AND eval."""
        self.cfg = cfg
        self.args = args
        self.mesh = mesh if mesh is not None else build_mesh(
            MeshConfig(dp=-1)
        )
        if args.sanitize_grads:
            if step_builder is None:
                from dlrover_tpu.train.optimizer import with_grad_sanitizer

                optimizer = with_grad_sanitizer(
                    optimizer, args.sanitize_grads
                )
            else:
                # the handed-in builder already baked its optimizer;
                # wrapping ours now would desync init_state from the step
                logger.warning(
                    "sanitize_grads=%r ignored: an external step_builder "
                    "was supplied — wrap its optimizer with "
                    "with_grad_sanitizer instead",
                    args.sanitize_grads,
                )
        self.optimizer = optimizer
        self.train_iter = iter(train_iter)
        self.eval_iter_fn = eval_iter_fn
        self.client = master_client
        self._init_state_fn = init_state_fn
        comm = None
        if args.update_sharding:
            from dlrover_tpu.parallel.sharding import CommConfig

            comm = CommConfig(
                update_sharding=args.update_sharding,
                bucket_mb=args.comm_bucket_mb,
                wire_dtype=args.comm_wire_dtype,
                wire_dtype_dcn=args.comm_wire_dtype_dcn,
            )
        self._builder = step_builder or TrainStepBuilder(
            cfg,
            self.mesh,
            optimizer,
            rules=rules,
            grad_accum=args.grad_accum,
            loss_fn=loss_fn,
            attn_impl=args.attn_impl,
            comm=comm,
            health_sentinels=args.health_sentinels,
        )
        self._step_fn = None
        self._block_fn = None
        # the step programs the compile recorder had seen when this
        # loop last looked, and the newest one's account
        self._compiles = compile_cache.watch_compiles()
        self._step_programs_seen = 0
        self._step_compile = None
        self._eval_fn = eval_step_fn
        self._batch_sharding = batch_sharding(self.mesh, rules)
        if jax.process_count() == 1:
            # the ONE device-placement point for training batches:
            # prefetch=0 degrades to plain per-batch device_put
            from dlrover_tpu.train.data_utils import prefetch_to_device

            self.train_iter = prefetch_to_device(
                self.train_iter, args.prefetch, self._batch_sharding
            )
        elif args.prefetch > 0:
            # multi-host: prefetch>0 opts the iterator into the trainer's
            # placement — each host yields its LOCAL rows, form_global_batch
            # assembles the global array (no cross-host exchange), and the
            # queue keeps `prefetch` assembled batches in flight ahead of
            # the step. prefetch=0 keeps the legacy contract (the caller's
            # iterator yields already-global arrays).
            from dlrover_tpu.train.data_utils import (
                form_global_batch,
                prefetch_to_device,
            )

            self.train_iter = prefetch_to_device(
                (
                    form_global_batch(b, self._batch_sharding)
                    for b in self.train_iter
                ),
                args.prefetch,
                self._batch_sharding,
            )
        self.state: Any = None
        # the process's step clock: the placement above ticks it, this
        # loop's measured step seconds live on it too
        self.timer = step_clock()
        self.spike_detector = (
            LossSpikeDetector(
                save_dir=os.path.join(args.output_dir, "loss_spikes")
            )
            if args.detect_loss_spikes
            else None
        )
        self._ckpt = None
        self.runtime_timer = None
        if args.profile_interval or args.health_sentinels:
            from dlrover_tpu.observability.runtime_timer import (
                RuntimeKernelTimer,
            )

            # profile_interval=0 + sentinels: a forced-only timer so the
            # watchdog's triggered captures can still sample a step
            self.runtime_timer = RuntimeKernelTimer(
                interval_steps=args.profile_interval
            )
        self.watchdog = None
        if args.health_sentinels:
            from dlrover_tpu.observability.watchdog import (
                Watchdog,
                WatchdogConfig,
            )

            self.watchdog = Watchdog(
                WatchdogConfig(
                    node_id=int(
                        os.environ.get(GraftEnv.NODE_ID, "-1") or -1
                    ),
                    capture_dir=os.environ.get(GraftEnv.TRACE_DIR)
                    or os.path.join(args.output_dir, "captures"),
                )
            )
        self.control = TrainerControl()
        self.callbacks = CallbackList(callbacks)
        if self.spike_detector is not None:
            self.callbacks.add(LossSpikeCallback(self.spike_detector))
        # planned exposed-collective µs, set by a caller that has a plan;
        # compared against the measured runtime-trace collective time →
        # OverlapDriftRecord. 0 = pure measurement.
        self.planned_exposed_us = 0.0
        # expected step time for this shape (PlanRecord.
        # planned_step_time_s); the watchdog's step_time_regression
        # baseline. 0 = no plan, drift detection off.
        self.planned_step_time_s = 0.0
        # restart>0 means we are recovering: the first completed step
        # closes the failover timeline ("first-step-back")
        self._first_step_pending = (
            int(os.environ.get(GraftEnv.RESTART_COUNT, "0") or 0) > 0
        )

    def add_callback(self, cb: Callback):
        self.callbacks.add(cb)

    # ---- checkpointing ---------------------------------------------------

    @property
    def checkpointer(self):
        if self._ckpt is None:
            from dlrover_tpu.checkpoint import Checkpointer

            self._ckpt = Checkpointer(
                os.path.join(self.args.output_dir, "checkpoints"),
                master_client=self.client if self.args.report_to_master
                else None,
            )
        return self._ckpt

    def _init_state(self):
        args = self.args
        if (
            args.resume
            and not args.resume_partial
            and self._init_state_fn is None
        ):
            # restore BEFORE init: a fresh state beside the restored one
            # is the train state twice in HBM
            self.state, resumed = restore_or_init_train_state(
                self.checkpointer,
                jax.random.key(args.seed),
                self.cfg,
                self.mesh,
                self.optimizer,
                comm=self._builder.comm_resolved,
                step=args.resume_from_step,
            )
            if resumed:
                logger.info("resumed from step %d", int(self.state["step"]))
            return
        if self._init_state_fn is not None:
            self.state = self._init_state_fn(jax.random.key(args.seed))
        else:
            self.state = init_train_state(
                jax.random.key(args.seed),
                self.cfg,
                self.mesh,
                self.optimizer,
                comm=self._builder.comm_resolved,
            )
        if not args.resume:
            return
        from dlrover_tpu.checkpoint.checkpointer import state_template

        # a caller's own init function gives no abstract template, and
        # a partial restore needs the LIVE state (missing leaves keep
        # its fresh values): these two restore beside the fresh state
        restored = self.checkpointer.load_checkpoint(
            self.state if args.resume_partial
            else state_template(self.state),
            shardings=jax.tree.map(lambda x: x.sharding, self.state),
            step=args.resume_from_step,
            partial=args.resume_partial,
        )
        if restored is not None:
            self.state = restored
            logger.info("resumed from step %d", int(self.state["step"]))

    # ---- loops -----------------------------------------------------------

    def train(self) -> Any:
        args = self.args
        if self.state is None:
            self._init_state()
        if self._step_fn is None:
            self._step_fn = self._builder.build()
        if (
            self.client is not None
            and args.report_to_master
            and jax.process_index() == 0
        ):
            # model statistics → master JobMeta → Brain optimizer input
            # (reference: master_client.py report_model_info)
            try:
                self.client.report_model_info(
                    model_name=self.cfg.name,
                    num_params=self.cfg.num_params(),
                    flops_per_token=self.cfg.flops_per_token(
                        self.cfg.max_seq
                    ),
                    seq_len=self.cfg.max_seq,
                )
            except Exception:  # noqa: BLE001
                logger.warning("model-info report failed", exc_info=True)
        control = self.control
        self.callbacks.fire("on_train_begin", self, control)
        self._step_programs_seen = self._compiles.step_programs
        if args.block_k > 1:
            if self._block_fn is None:
                self._block_fn = self._builder.build_block()
            last_saved, last_evaled = self._train_blockwise()
        else:
            last_saved, last_evaled = self._train_stepwise()
        if args.eval_at_end and int(self.state["step"]) != last_evaled:
            eval_metrics = self.evaluate()
            if eval_metrics:
                self.callbacks.fire(
                    "on_eval", self, int(self.state["step"]),
                    eval_metrics, control,
                )
        # final checkpoint so a clean exit is always resumable (skipped
        # when the loop's cadence already saved this exact step). Any
        # save at all — including callback-forced ones with
        # save_interval=0 — must be awaited before returning, or the
        # process can exit mid-persist.
        if args.save_interval:
            final_step = int(self.state["step"])
            if final_step != last_saved:
                self.checkpointer.save_checkpoint(final_step, self.state)
                last_saved = final_step
        if last_saved >= 0:
            self.checkpointer.wait_for_persist()
        self.callbacks.fire("on_train_end", self, control)
        return self.state

    # ---- telemetry producers --------------------------------------------

    def _note_step_compile(self, tracer):
        """Inside a ``train.step`` span. Where the step was traced,
        lowered and compiled (or fetched) in it — the first step of a
        worker, a new block size — that interval gets its name on the
        timeline: ``train.compile``, a child of the open span, laid out
        from the trace's start for the three phases' seconds. Any other
        step: one attribute read."""
        seen = self._compiles.step_programs
        if seen == self._step_programs_seen:
            return
        self._step_programs_seen = seen
        made = self._compiles.last_step
        cost = self._step_compile = {
            k: made[k]
            for k in ("trace_s", "lower_s", "backend_s", "cache_hit")
        }
        tracer.complete_span(
            "train.compile",
            # jax stamps the start on time.time()
            time.monotonic() - (time.time() - made["start"]),
            dur_s=cost["trace_s"] + cost["lower_s"] + cost["backend_s"],
            **cost,
        )

    def _emit_step_telemetry(
        self, step: int, loss: float, step_time_s: float,
        batch=None, n_steps: int = 1,
    ):
        """Per-step StepRecord onto the bus; closes the failover timeline
        on the first step after a restart. Disabled hub: two attribute
        reads and out — no allocation, no publish."""
        if self._first_step_pending:
            self._first_step_pending = False
            get_tracer().instant("failover.first_step", step=step)
            hub = telemetry.get_hub()
            if hub.enabled:
                hub.publish(
                    telemetry.ElasticEvent(
                        kind="first_step_back",
                        # with what the step's compile-or-fetch cost
                        detail=" ".join(
                            f"{k}={v}" for k, v in
                            {"step": step, **(self._step_compile or {})}.items()
                        ),
                    )
                )
        hub = telemetry.get_hub()
        if not hub.enabled:
            return
        tokens = 0
        if batch is not None:
            tok = batch.get("tokens")
            if tok is not None:
                tokens = int(getattr(tok, "size", 0)) // max(n_steps, 1)
        hub.publish(
            telemetry.StepRecord(
                step=step,
                loss=loss,
                step_time_s=step_time_s,
                tokens_per_s=(
                    tokens / step_time_s if step_time_s > 0 else 0.0
                ),
                accum=self.args.grad_accum,
            )
        )

    def _emit_kernel_telemetry(self, step: int):
        """After a runtime-timer sampled step: top-op KernelSamples plus
        the planned-vs-measured exposed-collective drift record."""
        rt = self.runtime_timer
        if rt is None or rt.sampled_at != step:
            return
        hub = telemetry.get_hub()
        if not hub.enabled:
            return
        for op in rt.breakdown[:8]:
            hub.publish(
                telemetry.KernelSample(
                    step=step, op=op.name, us=op.total_us,
                    share=op.fraction, block=rt.sampled_block_k,
                )
            )
        hub.publish(
            telemetry.overlap_drift(
                step, self.planned_exposed_us, rt.breakdown
            )
        )

    def _train_stepwise(self) -> Tuple[int, int]:
        """The classic one-dispatch-per-step loop (block_k=1)."""
        args = self.args
        control = self.control
        start = int(self.state["step"])
        window_loss = 0.0
        window_n = 0
        last_saved = -1
        last_evaled = -1
        t_log = time.perf_counter()
        for step in range(start + 1, args.max_steps + 1):
            # the four spans a device idle gap can be put down to; with
            # tracing off each is the shared null span
            tracer = get_tracer()
            try:
                # single-process: already device-placed by the
                # prefetch_to_device wrap in __init__; multi-host
                # batches arrive global via form_global_batch
                with tracer.span("train.input_wait", step=step):
                    batch = next(self.train_iter)
            except StopIteration:
                logger.info("data exhausted at step %d", step - 1)
                break
            self.timer.start()
            with tracer.step_span("train.step", step):
                if self.runtime_timer is not None:
                    self.state, metrics = self.runtime_timer.profiled_call(
                        step, self._step_fn, self.state, batch
                    )
                else:
                    self.state, metrics = self._step_fn(self.state, batch)
                self._note_step_compile(tracer)
            with tracer.span("train.readback", step=step):
                self.timer.stop(outputs=metrics["loss"])
                # ONE device→host transfer per step, sentinels or not —
                # the sentinel scalars ride the same readback as the
                # loss (dispatch-guard-pinned in tests/test_sentinels.py)
                host = jax.device_get(metrics)
            loss = float(host["loss"])
            hooks_span = tracer.span("train.hooks", step=step)
            self._emit_step_telemetry(step, loss, self.timer.last_s, batch)
            if self.runtime_timer is not None:
                self._emit_kernel_telemetry(step)
            if self.watchdog is not None:
                if (
                    self.watchdog.capture_pending
                    and self.runtime_timer is not None
                    and self.runtime_timer.sampled_at == step
                ):
                    # the force-armed sample just ran: attach it
                    self.watchdog.write_capture(
                        step,
                        self.runtime_timer.breakdown,
                        planned_exposed_us=self.planned_exposed_us,
                        block=self.runtime_timer.sampled_block_k,
                    )
                self.watchdog.observe(
                    step,
                    {k: float(v) for k, v in host.items()},
                    step_time_s=self.timer.last_s,
                    planned_step_time_s=self.planned_step_time_s,
                )
                if (
                    self.watchdog.capture_pending
                    and self.runtime_timer is not None
                ):
                    self.runtime_timer.force_next()
            window_loss += loss
            window_n += 1
            self.callbacks.fire(
                "on_step_end", self, step, {"loss": loss}, control
            )
            if control.should_log or (
                args.log_interval and step % args.log_interval == 0
            ):
                dt = time.perf_counter() - t_log
                t_log = time.perf_counter()
                logs = {
                    "loss": window_loss / max(window_n, 1),
                    "steps_per_s": window_n / max(dt, 1e-9),
                }
                self.callbacks.fire("on_log", self, step, logs, control)
                logger.info(
                    "step %d | loss %.4f | %.2f steps/s%s",
                    step,
                    logs["loss"],
                    logs["steps_per_s"],
                    " | lr %.3e" % logs["learning_rate"]
                    if "learning_rate" in logs
                    else "",
                )
                window_loss, window_n = 0.0, 0
            if self.client is not None and args.report_to_master:
                try:
                    self.client.report_global_step(
                        step, jax.process_count()
                    )
                except Exception:  # noqa: BLE001
                    logger.warning("global-step report failed", exc_info=True)
            if (
                args.memory_save_interval
                and step % args.memory_save_interval == 0
            ):
                from dlrover_tpu.checkpoint import StorageType

                self.checkpointer.save_checkpoint(
                    step, self.state, storage_type=StorageType.MEMORY
                )
            if control.should_save or (
                args.save_interval and step % args.save_interval == 0
            ):
                self.checkpointer.save_checkpoint(step, self.state)
                last_saved = step
                self.callbacks.fire("on_save", self, step, control)
            if control.should_eval or (
                args.eval_interval and step % args.eval_interval == 0
            ):
                eval_metrics = self.evaluate()
                last_evaled = step
                if eval_metrics:
                    logger.info(
                        "eval @ step %d | loss %.4f",
                        step,
                        eval_metrics["loss"],
                    )
                    self.callbacks.fire(
                        "on_eval", self, step, eval_metrics, control
                    )
            control.reset_step_flags()
            hooks_span.end()
            if control.should_stop:
                logger.info("training stopped by callback at step %d", step)
                break
        return last_saved, last_evaled

    # ---- fused multi-step loop ------------------------------------------

    def _next_block_k(self, step: int) -> int:
        """Largest block size from ``step`` that lands exactly on every
        state-touching cadence boundary (save/eval/memory-save) and on
        ``max_steps`` — the invariant that keeps fused cadences EXACT:
        boundaries only ever coincide with block ends, never fall
        inside a block.  Log cadence does not shrink blocks: logs need
        only the stacked metrics, which the drain replays per step."""
        args = self.args
        k = min(args.block_k, args.max_steps - step)
        for interval in (
            args.save_interval,
            args.eval_interval,
            args.memory_save_interval,
        ):
            if interval:
                k = min(k, interval - step % interval)
        return max(int(k), 1)

    def _train_blockwise(self) -> Tuple[int, int]:
        """K steps per device dispatch with async metrics readback.

        Each iteration dispatches one fused block, then drains the
        PREVIOUS block's stacked metrics while the new one computes
        (the device_get of finished results costs no device idle time).
        Per-step host work — loss windows, spike detection, on_step_end
        callbacks, exact-step logging — happens in the drain, against
        the true per-step values.  State-touching cadences run at block
        ends, which _next_block_k aligned to the boundaries; control
        flags raised during a drain are honored at the next boundary
        (worst-case response: one block).
        """
        import numpy as np

        args = self.args
        control = self.control
        step = int(self.state["step"])
        window = {"loss": 0.0, "n": 0, "t_log": time.perf_counter()}
        last_saved = -1
        last_evaled = -1
        pending = None  # (first_step, k, device_metrics, t_dispatch)

        def per_step_metrics(host, i, k):
            # one step's slice of the block's stacked [K] metric arrays
            out = {}
            for key, val in host.items():
                arr = np.asarray(val).reshape(-1)
                out[key] = float(arr[i] if arr.size == k else arr[0])
            return out

        def drain(first, k, metrics, t0):
            with get_tracer().span("train.readback", step=first):
                host = jax.device_get(metrics)  # previous block: finished
            self.timer.record(time.perf_counter() - t0, n_steps=k)
            per_step_s = self.timer.last_s
            losses = np.asarray(host["loss"]).reshape(-1)
            for i in range(k):
                s = first + i
                loss = float(losses[i])
                self._emit_step_telemetry(s, loss, per_step_s, n_steps=k)
                if self.watchdog is not None:
                    self.watchdog.observe(
                        s,
                        per_step_metrics(host, i, k),
                        step_time_s=per_step_s,
                        planned_step_time_s=self.planned_step_time_s,
                    )
                window["loss"] += loss
                window["n"] += 1
                self.callbacks.fire(
                    "on_step_end", self, s, {"loss": loss}, control
                )
                if control.should_log or (
                    args.log_interval and s % args.log_interval == 0
                ):
                    control.should_log = False
                    dt = time.perf_counter() - window["t_log"]
                    window["t_log"] = time.perf_counter()
                    logs = {
                        "loss": window["loss"] / max(window["n"], 1),
                        "steps_per_s": window["n"] / max(dt, 1e-9),
                    }
                    self.callbacks.fire("on_log", self, s, logs, control)
                    logger.info(
                        "step %d | loss %.4f | %.2f steps/s%s",
                        s,
                        logs["loss"],
                        logs["steps_per_s"],
                        " | lr %.3e" % logs["learning_rate"]
                        if "learning_rate" in logs
                        else "",
                    )
                    window["loss"], window["n"] = 0.0, 0
            if (
                self.watchdog is not None
                and self.watchdog.capture_pending
                and self.runtime_timer is not None
            ):
                # anomaly in this drain: force-sample the next block
                self.runtime_timer.force_next()

        exhausted = False
        while (
            step < args.max_steps
            and not control.should_stop
            and not exhausted
        ):
            tracer = get_tracer()
            batches = []
            with tracer.span("train.input_wait", step=step + 1):
                for _ in range(self._next_block_k(step)):
                    try:
                        batches.append(next(self.train_iter))
                    except StopIteration:
                        exhausted = True
                        break
                if batches:
                    block = jax.tree.map(
                        lambda *xs: jnp.stack(xs), *batches
                    )
            if not batches:
                logger.info("data exhausted at step %d", step)
                break
            k = len(batches)
            t0 = time.perf_counter()
            step_span = tracer.step_span("train.step", step + 1, block=k)
            if self.runtime_timer is not None:
                # profile when a sampled step falls inside this block
                sample = next(
                    (
                        s
                        for s in range(step + 1, step + k + 1)
                        if self.runtime_timer.should_sample(s)
                    ),
                    None,
                )
                if sample is not None:
                    self.state, metrics = self.runtime_timer.profiled_call(
                        sample, self._block_fn, self.state, block,
                        n_steps=k,
                    )
                    self._emit_kernel_telemetry(sample)
                    if (
                        self.watchdog is not None
                        and self.watchdog.capture_pending
                        and self.runtime_timer.sampled_at == sample
                    ):
                        # labeled as a K-step block capture, never
                        # passed off as one step's budget
                        self.watchdog.write_capture(
                            sample,
                            self.runtime_timer.breakdown,
                            planned_exposed_us=self.planned_exposed_us,
                            block=self.runtime_timer.sampled_block_k,
                        )
                else:
                    self.state, metrics = self._block_fn(self.state, block)
            else:
                self.state, metrics = self._block_fn(self.state, block)
            self._note_step_compile(tracer)
            step_span.end()
            hooks_span = tracer.span("train.hooks", step=step + k)
            if pending is not None:
                drain(*pending)
            pending = (step + 1, k, metrics, t0)
            step += k
            # block-boundary host actions on the just-dispatched state
            if self.client is not None and args.report_to_master:
                try:
                    self.client.report_global_step(
                        step, jax.process_count()
                    )
                except Exception:  # noqa: BLE001
                    logger.warning(
                        "global-step report failed", exc_info=True
                    )
            if (
                args.memory_save_interval
                and step % args.memory_save_interval == 0
            ):
                from dlrover_tpu.checkpoint import StorageType

                self.checkpointer.save_checkpoint(
                    step, self.state, storage_type=StorageType.MEMORY
                )
            if control.should_save or (
                args.save_interval and step % args.save_interval == 0
            ):
                self.checkpointer.save_checkpoint(step, self.state)
                last_saved = step
                self.callbacks.fire("on_save", self, step, control)
            if control.should_eval or (
                args.eval_interval and step % args.eval_interval == 0
            ):
                eval_metrics = self.evaluate()
                last_evaled = step
                if eval_metrics:
                    logger.info(
                        "eval @ step %d | loss %.4f",
                        step,
                        eval_metrics["loss"],
                    )
                    self.callbacks.fire(
                        "on_eval", self, step, eval_metrics, control
                    )
            control.reset_step_flags()
            hooks_span.end()
        if pending is not None:
            drain(*pending)
        # flags raised by the FINAL drain still get their boundary
        if control.should_save:
            self.checkpointer.save_checkpoint(step, self.state)
            last_saved = step
            self.callbacks.fire("on_save", self, step, control)
        if control.should_eval:
            eval_metrics = self.evaluate()
            last_evaled = step
            if eval_metrics:
                self.callbacks.fire(
                    "on_eval", self, step, eval_metrics, control
                )
        control.reset_step_flags()
        if control.should_stop:
            logger.info("training stopped by callback at step %d", step)
        return last_saved, last_evaled

    def evaluate(self) -> Dict[str, float]:
        if self.eval_iter_fn is None:
            return {}
        if self._eval_fn is None:
            self._eval_fn = build_eval_step(
                self.cfg, self.mesh, attn_impl=self.args.attn_impl
            )
        total, n = 0.0, 0
        for i, batch in enumerate(self.eval_iter_fn()):
            if i >= self.args.eval_steps:
                break
            batch = jax.device_put(batch, self._batch_sharding)
            metrics = self._eval_fn(self.state["params"], batch)
            total += float(metrics["loss"])
            n += 1
        return {"loss": total / max(n, 1), "batches": float(n)}
