"""Sharded train-state init and train-step builder.

The TPU-native core loop: one jitted function computes grads, applies the
optimizer, and XLA inserts every collective (psum over ``dp``/``fsdp`` for
grads, all-gathers for TP activations) from the sharding constraints — the
replacement for the reference's wrapper stack of DDP/FSDP/TP modules
(atorch auto/model_context.py apply-wrapper pipeline).

Gradient accumulation is a ``lax.scan`` over microbatches, which is also the
elasticity lever: the ElasticTrainer keeps the *global* batch constant when
the world shrinks by raising ``grad_accum`` (reference:
trainer/torch/elastic/trainer.py:48).
"""

import functools
import logging
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.common import compile_cache, device
from dlrover_tpu.models import decoder
from dlrover_tpu.models.config import ModelConfig
from dlrover_tpu.observability import sentinels as snt
from dlrover_tpu.observability.tracing import set_counter
from dlrover_tpu.parallel import sharding as shd

logger = logging.getLogger(__name__)

TrainState = Dict[str, Any]  # {"params", "opt_state", "step"}

# Host-offloaded optimizer state (reference parity: atorch's CPU-offload
# Adam, SURVEY §2.3 Optimizers). TPU-native: the moments live in
# pinned_host memory via sharding memory kinds — XLA streams them over
# the host DMA around the update, freeing ~2x param bytes of HBM. No
# custom op and no separate optimizer implementation needed. (On the CPU
# backend the Host space aliases device memory — a harmless no-op that
# keeps the same code path testable on the virtual mesh.)
_HOST = jax.memory.Space.Host
_DEVICE = jax.memory.Space.Device


def _to_memory_kind(tree, kind):
    return jax.tree.map(lambda x: jax.device_put(x, kind), tree)


def batch_sharding(mesh: Mesh, rules=None) -> NamedSharding:
    """Sharding for [B, S] token batches."""
    rules = dict(shd.DEFAULT_RULES, **(rules or {}))
    return NamedSharding(
        mesh, shd.logical_to_mesh_axes(("batch", "seq"), rules)
    )


def _is_quantized(x) -> bool:
    from dlrover_tpu.ops.quant import QuantizedArray

    return isinstance(x, QuantizedArray)


def _map_param_subtrees(
    opt_tree, params, param_shardings, param_leaf_fn, other_fn
):
    """Map over an optimizer-state tree, matching param-STRUCTURED
    subtrees (Adam mu/nu etc.) by tree structure, not leaf shape —
    same-shape params can carry transposed shardings, and a shape-keyed
    lookup would pin their moments to the wrong one.

    ``param_leaf_fn(leaf, param_sharding)`` is applied leaf-wise inside
    matched subtrees (QuantizedArray nodes treated as leaves);
    ``other_fn(subtree)`` covers everything else (step counters, …).
    The ONE structure-matching rule both the init constraints and the
    host-offload shardings build on."""
    pdef = jax.tree.structure(params)

    def is_param_tree(x):
        try:
            return (
                jax.tree.structure(x, is_leaf=_is_quantized) == pdef
            )
        except Exception:  # noqa: BLE001
            return False

    def con(sub):
        if is_param_tree(sub):
            return jax.tree.map(
                param_leaf_fn, sub, param_shardings,
                is_leaf=_is_quantized,
            )
        return other_fn(sub)

    return jax.tree.map(con, opt_tree, is_leaf=is_param_tree)


def _opt_state_host_shardings(opt_shape, params, param_shardings, mesh):
    """Per-leaf pinned_host NamedShardings for an optimizer-state tree:
    param-shaped subtrees inherit the param shardings (host kind), the
    rest (step counters, quantized-array innards) replicate on host."""
    rep = NamedSharding(mesh, P(), memory_kind="pinned_host")
    return _map_param_subtrees(
        opt_shape,
        params,
        param_shardings,
        param_leaf_fn=lambda leaf, s: jax.tree.map(lambda _: rep, leaf)
        if _is_quantized(leaf)
        else s.with_memory_kind("pinned_host"),
        other_fn=lambda sub: jax.tree.map(lambda _: rep, sub),
    )


# ---------------------------------------------------------------------------
# Weight-update sharding (ZeRO-1): gate resolution + flat optimizer state
# ---------------------------------------------------------------------------


def _flat_abs(plan: shd.PackPlan):
    return {
        "flat": jax.ShapeDtypeStruct(
            (plan.n_buckets, plan.bucket_elems), jnp.float32
        )
    }


def _effective_flat_optimizer(
    optimizer: optax.GradientTransformation, plan: shd.PackPlan
) -> optax.GradientTransformation:
    """The transformation actually run on the flat bucketed view.

    Most optimizers are elementwise over the view and run as-is. An
    optimizer whose init fn carries a ``_flat_factory`` attribute
    (optimizer.py's factored path) instead supplies a plan-aware flat
    equivalent: the factory knows the pack layout, so it can rebuild
    per-leaf views out of the flat stream and keep non-elementwise
    state (Adafactor row/col accumulators) per leaf rather than
    mis-factoring the bucket matrix.
    """
    factory = getattr(optimizer.init, "_flat_factory", None)
    return factory(plan) if factory is not None else optimizer


def _probe_flat_optimizer(
    optimizer: optax.GradientTransformation, plan: shd.PackPlan
) -> Optional[str]:
    """None when the optimizer's state is elementwise over the flat
    bucketed param view (so dp-sharding the flat axis shards the state)
    or the optimizer supplies a plan-aware flat equivalent
    (``_flat_factory``), else the reason it is not."""
    eff = _effective_flat_optimizer(optimizer, plan)
    try:
        opt_abs = jax.eval_shape(eff.init, _flat_abs(plan))
    except Exception as e:  # noqa: BLE001
        return f"optimizer.init rejected the flat param view: {e}"
    flat_shape = (plan.n_buckets, plan.bucket_elems)
    for leaf in jax.tree.leaves(opt_abs, is_leaf=_is_quantized):
        if _is_quantized(leaf):
            return "low-bit optimizer state (compiler-chosen shardings)"
        if eff is not optimizer:
            # plan-aware flat optimizer: per-leaf factored state is
            # expected; only (n_buckets, bucket_elems)-shaped leaves
            # get dp-sharded (_flat_opt_sharding), the rest replicate
            continue
        if tuple(leaf.shape) not in ((), flat_shape):
            return (
                f"optimizer state leaf of shape {tuple(leaf.shape)} is "
                "not elementwise over the flat view (factored states "
                "would mis-factor the bucket matrix)"
            )
    return None


# fallback reasons already logged, keyed (reason, config name): the
# resolver runs on every trace (builder init, abstract/init state, AOT
# prewarm), and re-warning the same fallback each time buries real
# warnings. The chosen reason also rides the dryrun's MULTICHIP-STATS
# lines (TrainStepBuilder.update_sharding_reason), which is where a
# fallback should be noticed.
_LOGGED_FALLBACKS: set = set()

# pack-plan cache: the resolver runs at least three times per job
# (builder init, abstract/init state, AOT prewarm) and each run used to
# re-trace the full model via jax.eval_shape(decoder.init) just to size
# buckets. ModelConfig is a frozen (hashable) dataclass, so the plan —
# a pure function of (config, dp, bucket_bytes, tie, mesh_axes) — is
# memoized on those inputs.
_PLAN_CACHE: Dict[Tuple, shd.PackPlan] = {}


def resolve_update_sharding(
    cfg: ModelConfig,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    comm: Optional[shd.CommConfig],
    loss_fn: Optional[Callable] = None,
    offload_opt_state: bool = False,
) -> Tuple[bool, Optional[str], Optional[shd.PackPlan]]:
    """(active, fallback_reason, pack_plan) for a requested CommConfig.

    Update sharding is an optimization, not a semantics change, so an
    unsupported combination falls back to the replicated update with a
    recorded reason instead of failing the job. Supported meshes: any
    whose non-dp axes are confined to fsdp/tp — on a pure-dp mesh the
    whole step runs in one fully-manual region; with fsdp/tp in play
    the gradient exchange runs in a PARTIAL-manual region (manual over
    dp, fsdp/tp left to the auto partitioner) and the plan still packs
    GLOBAL leaf shapes, because auto-axis values appear global-shaped
    inside the region. Also required: built-in loss, f32 params,
    flat-compatible optimizer state (elementwise, or a plan-aware
    ``_flat_factory`` equivalent — optimizer.py's factored path), no
    MoE/host-offload. ``cfg.fp8`` composes on pure-dp meshes only, and
    quantized wire dtypes (bf16/int8) need the pure-dp full-manual
    region — their ``all_to_all`` cannot lower partial-manually.
    """
    if comm is None or not comm.update_sharding:
        return False, None, None
    dp = mesh.shape.get("dp", 1)
    others = sorted(
        a for a, s in mesh.shape.items() if a != "dp" and s > 1
    )
    unsupported = [a for a in others if a not in ("fsdp", "tp")]
    reason = None
    if dp <= 1:
        reason = "mesh has dp<=1"
    elif unsupported:
        reason = f"non-dp mesh axes beyond fsdp/tp in use: {unsupported}"
    elif cfg.n_experts > 0:
        reason = "MoE routing/aux losses not supported in the manual region"
    elif offload_opt_state:
        reason = "offload_opt_state keeps moments host-resident already"
    elif loss_fn is not None:
        reason = "custom loss_fn (denom override unavailable)"
    elif others and cfg.fp8:
        reason = (
            "fp8 delayed-scaling state threads the pure-dp manual "
            "region only (no carry across a partial-manual region)"
        )
    elif others and comm.wire_for(mesh, "dp") != "float32":
        reason = (
            "quantized wire dtypes need a pure-dp mesh (all_to_all "
            "over dp cannot lower inside the partial-manual region)"
        )
    mesh_axes = ("dp",) + tuple(others)
    plan = None
    if reason is None:
        cache_key: Optional[Tuple] = None
        try:
            cache_key = (
                cfg, dp, comm.bucket_bytes, cfg.tie_embeddings, mesh_axes
            )
            plan = _PLAN_CACHE.get(cache_key)
        except TypeError:  # unhashable config subclass: skip the cache
            cache_key = None
    if reason is None and plan is None:
        params_abs = jax.eval_shape(
            lambda: decoder.init(jax.random.key(0), cfg)
        )
        try:
            plan = shd.build_pack_plan(
                params_abs,
                dp,
                comm.bucket_bytes,
                tie_embeddings=cfg.tie_embeddings,
                mesh_axes=mesh_axes,
            )
            if cache_key is not None:
                _PLAN_CACHE[cache_key] = plan
        except ValueError as e:
            reason = str(e)
    if reason is None:
        reason = _probe_flat_optimizer(optimizer, plan)
    if reason is not None:
        key = (reason, getattr(cfg, "name", ""))
        if key not in _LOGGED_FALLBACKS:
            _LOGGED_FALLBACKS.add(key)
            logger.warning(
                "update sharding requested but falling back to the "
                "replicated update (config %s): %s",
                key[1] or "<unnamed>",
                reason,
            )
        return False, reason, None
    return True, None, plan


def _flat_opt_sharding(leaf, plan: shd.PackPlan, mesh: Mesh):
    if tuple(leaf.shape) == (plan.n_buckets, plan.bucket_elems):
        return NamedSharding(mesh, P(None, "dp"))
    return NamedSharding(mesh, P())


def abstract_train_state(
    cfg: ModelConfig,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    rules=None,
    offload_opt_state: bool = False,
    comm: Optional[shd.CommConfig] = None,
):
    """``ShapeDtypeStruct`` tree matching ``init_train_state``'s output
    — shapes AND shardings — without materializing anything.

    Exists for AOT pre-compilation (train/prewarm.py): lowering the
    train step against abstract leaves requires the exact input
    shardings the live job will use, or the HLO (and therefore the
    persistent-cache key) diverges and the pre-warm buys nothing.

    ``offload_opt_state`` mirrors init's host-offload branch (moments
    born with pinned_host memory kinds). Low-bit (int8/int4) optimizer
    states are NOT supported: init leaves their quantized innards
    unconstrained (compiler-chosen shardings), which an AOT caller
    cannot reproduce deterministically — raise rather than silently
    pre-warm a key the live job will never hit.
    """
    param_shardings = shd.shardings_for_tree(
        mesh, decoder.logical_axes(cfg), rules
    )
    params_abs = jax.eval_shape(
        lambda: decoder.init(jax.random.key(0), cfg)
    )
    active, _, plan = resolve_update_sharding(
        cfg, mesh, optimizer, comm, offload_opt_state=offload_opt_state
    )
    if active:
        # ZeRO-1: the optimizer state lives on the flat bucketed view,
        # dp-sharded along the bucket axis (1/dp of the moments per
        # replica); params themselves stay in their usual shardings
        flat_opt = _effective_flat_optimizer(optimizer, plan)
        opt_abs = jax.eval_shape(flat_opt.init, _flat_abs(plan))
        rep = NamedSharding(mesh, P())
        shapes = {
            "params": params_abs,
            "opt_state": opt_abs,
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }
        sh = {
            "params": param_shardings,
            "opt_state": jax.tree.map(
                lambda l: _flat_opt_sharding(l, plan, mesh), opt_abs
            ),
            "step": rep,
        }
        if cfg.fp8:
            # pure-dp meshes never pipeline, so the delayed-scaling
            # state always rides the sharded step (replicated: the
            # histories are pmax-merged over dp every step)
            fp8_abs = jax.eval_shape(lambda: decoder.init_fp8_states(cfg))
            shapes["fp8"] = fp8_abs
            sh["fp8"] = jax.tree.map(lambda _: rep, fp8_abs)
        return jax.tree.map(
            lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
            shapes,
            sh,
        )
    opt_abs = jax.eval_shape(optimizer.init, params_abs)
    if any(_is_quantized(leaf) for leaf in jax.tree.leaves(
            opt_abs, is_leaf=_is_quantized)):
        raise NotImplementedError(
            "abstract_train_state: low-bit optimizer states carry "
            "compiler-chosen shardings the AOT path cannot reproduce"
        )
    rep = NamedSharding(mesh, P())
    if offload_opt_state and not device.on_cpu():
        opt_sh = _opt_state_host_shardings(
            opt_abs, params_abs, param_shardings, mesh
        )
    else:
        opt_sh = _map_param_subtrees(
            opt_abs,
            params_abs,
            param_shardings,
            param_leaf_fn=lambda leaf, s: s,
            other_fn=lambda sub: jax.tree.map(lambda _: rep, sub),
        )
    sh = {
        "params": param_shardings,
        "opt_state": opt_sh,
        "step": rep,
    }
    shapes = {
        "params": params_abs,
        "opt_state": opt_abs,
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    if cfg.fp8 and mesh.shape.get("pp", 1) == 1:
        fp8_abs = jax.eval_shape(lambda: decoder.init_fp8_states(cfg))
        sh["fp8"] = jax.tree.map(lambda _: rep, fp8_abs)
        shapes["fp8"] = fp8_abs
    return jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        shapes,
        sh,
    )


def state_shardings(
    cfg: ModelConfig,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    rules=None,
    offload_opt_state: bool = False,
    comm: Optional[shd.CommConfig] = None,
):
    """The NamedSharding tree ``init_train_state`` produces (see
    ``abstract_train_state``, of which this is the shardings-only
    view)."""
    return jax.tree.map(
        lambda a: a.sharding,
        abstract_train_state(
            cfg, mesh, optimizer, rules, offload_opt_state, comm
        ),
    )


def init_train_state(
    rng: jax.Array,
    cfg: ModelConfig,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    rules=None,
    offload_opt_state: bool = False,
    comm: Optional[shd.CommConfig] = None,
) -> TrainState:
    """Jit-initialise params + optimizer state directly into their shardings.

    Parameters never materialise unsharded: init runs under jit with
    ``out_shardings`` derived from the logical-axis rules, so a 7B model
    initialises straight into per-device shards (contrast the reference's
    meta-init + rematerialisation dance, atorch fsdp_init_util.py).

    With ``comm.update_sharding`` resolved active, the optimizer state is
    born on the flat bucketed param view, dp-sharded (see
    ``resolve_update_sharding``); pass the SAME comm the step builder
    resolved (``TrainStepBuilder.comm_resolved``) so state layout and
    step agree.

    The leaves carry the shardings of ``state_shardings`` as that spells
    them (``out_shardings``, not whatever the compiler derives: it drops
    mesh axes of size one, so ``P('tp', 'fsdp')`` comes back as ``P()``
    from a one-chip mesh). The jitted step lowers its arguments'
    shardings into the program text, and the text is the persistent
    compile cache's key: an initialised state, the abstract template
    and a state restored into the template must all spell alike, or a
    worker restarted after a crash recompiles the step it compiled
    before.

    Whatever this compiles is counted as the state's initialisation
    (``compile.init_state.s``, ``common/compile_cache.py``).
    """
    with compile_cache.watch_compiles().within(compile_cache.INIT_STATE):
        return _init_train_state(
            rng, cfg, mesh, optimizer, rules, offload_opt_state, comm
        )


def _init_train_state(
    rng, cfg, mesh, optimizer, rules, offload_opt_state, comm
) -> TrainState:
    param_shardings = shd.shardings_for_tree(
        mesh, decoder.logical_axes(cfg), rules
    )
    try:
        out_sh = state_shardings(
            cfg, mesh, optimizer, rules, offload_opt_state, comm
        )
    except NotImplementedError:
        # low-bit optimizer state: its innards keep the compiler's
        # shardings (see abstract_train_state), and so does the rest
        out_sh = None
    us_active, _, plan = resolve_update_sharding(
        cfg, mesh, optimizer, comm, offload_opt_state=offload_opt_state
    )
    if us_active:

        def f_us(rng):
            params = decoder.init(rng, cfg)
            params = jax.tree.map(
                jax.lax.with_sharding_constraint, params, param_shardings
            )
            flat = {"flat": shd.pack_flat(params, plan)}
            opt_state = _effective_flat_optimizer(optimizer, plan).init(
                flat
            )
            opt_state = jax.tree.map(
                lambda l: jax.lax.with_sharding_constraint(
                    l, _flat_opt_sharding(l, plan, mesh)
                ),
                opt_state,
            )
            state = {
                "params": params,
                "opt_state": opt_state,
                "step": jnp.zeros([], jnp.int32),
            }
            if cfg.fp8:
                state["fp8"] = decoder.init_fp8_states(cfg)
            return state

        return jax.jit(f_us, out_shardings=out_sh)(rng)
    # optimizer-state leaves (Adam moments etc.) mirror param shapes and
    # must be born with the SAME shardings — otherwise every step starts
    # by involuntarily resharding the moments (XLA's "involuntary full
    # rematerialization" warning, a full moment-tree copy per step)
    def _constrain_like_params(opt_state, params):
        # optax state nests whole param-shaped subtrees (Adam mu/nu
        # etc.) — matched by structure via _map_param_subtrees.
        # Quantized states are left as-is: they are 4-8x smaller, so the
        # per-step reshard this guards against is proportionally cheap.
        return _map_param_subtrees(
            opt_state,
            params,
            param_shardings,
            param_leaf_fn=lambda leaf, s: leaf
            if _is_quantized(leaf)
            else jax.lax.with_sharding_constraint(leaf, s),
            other_fn=lambda sub: sub,
        )

    def f(rng):
        params = decoder.init(rng, cfg)
        params = jax.tree.map(
            jax.lax.with_sharding_constraint, params, param_shardings
        )
        opt_state = optimizer.init(params)
        opt_state = _constrain_like_params(opt_state, params)
        state = {
            "params": params,
            "opt_state": opt_state,
            "step": jnp.zeros([], jnp.int32),
        }
        if cfg.fp8 and mesh.shape.get("pp", 1) == 1:
            # fp8 delayed-scaling amax histories: tiny, replicated.
            # Pipeline meshes carry NO fp8 state: they run stateless
            # current scaling (decoder.run_trunk's "current" mode)
            state["fp8"] = decoder.init_fp8_states(cfg)
        return state

    if not (offload_opt_state and not device.on_cpu()):
        return jax.jit(f, out_shardings=out_sh)(rng)

    # offload: the moments must be BORN in host memory — a post-jit
    # transfer would still hit the fully-resident HBM peak, which is
    # exactly the case offload exists for. Two phases: params on device,
    # then optimizer.init jitted with host-kind out_shardings.
    def f_params(rng):
        params = decoder.init(rng, cfg)
        return jax.tree.map(
            jax.lax.with_sharding_constraint, params, param_shardings
        )

    def f_opt(params):
        # NO device-kind sharding constraints here — out_shardings below
        # fully pins placement AND host memory kind, so the moments never
        # materialize HBM-resident (the point of offloading)
        return optimizer.init(params)

    params = jax.jit(f_params, out_shardings=param_shardings)(rng)
    opt_shape = jax.eval_shape(f_opt, params)
    opt_sh = _opt_state_host_shardings(
        opt_shape, params, param_shardings, mesh
    )
    opt_state = jax.jit(f_opt, out_shardings=opt_sh)(params)
    rep = NamedSharding(mesh, P())
    state = {
        "params": params,
        "opt_state": opt_state,
        "step": jax.device_put(jnp.zeros([], jnp.int32), rep),
    }
    if cfg.fp8 and mesh.shape.get("pp", 1) == 1:
        state["fp8"] = jax.jit(
            lambda: decoder.init_fp8_states(cfg), out_shardings=rep
        )()
    return state


def restore_or_init_train_state(
    checkpointer,
    rng: jax.Array,
    cfg: ModelConfig,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    rules=None,
    offload_opt_state: bool = False,
    comm: Optional[shd.CommConfig] = None,
    step: Optional[int] = None,
) -> Tuple[TrainState, bool]:
    """The state a (re)started worker trains from, and whether it came
    out of a checkpoint: ``checkpointer``'s newest (or ``step``'s)
    checkpoint restored into the abstract template, else a fresh
    ``init_train_state``.

    Restore comes BEFORE init, never after: an initialised state beside
    the restored one is the train state twice, and a recipe sized to
    the chip (GPT-2 XL with bf16 AdamW is 9.5 GB of a v5e's 16) does
    not have the room. Either way the leaves spell their shardings as
    ``state_shardings`` does, so the step compiled before a crash is
    found again in the compile cache after it.
    """
    def init():
        return init_train_state(
            rng, cfg, mesh, optimizer, rules, offload_opt_state, comm
        )

    state = None
    try:
        template = abstract_train_state(
            cfg, mesh, optimizer, rules, offload_opt_state, comm
        )
    except NotImplementedError:
        # a low-bit optimizer state has no abstract template (its
        # shardings are the compiler's to choose): this one alone
        # restores beside a fresh state and needs the room for both
        state = init()
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=x.sharding
            ),
            state,
        )
    restored = checkpointer.load_checkpoint(
        template,
        shardings=jax.tree.map(lambda a: a.sharding, template),
        step=step,
    )
    if restored is not None:
        return restored, True
    return (init() if state is None else state), False


class TrainStepBuilder:
    """Builds the jitted train step for (model config, mesh, strategy)."""

    def __init__(
        self,
        cfg: ModelConfig,
        mesh: Mesh,
        optimizer: optax.GradientTransformation,
        rules=None,
        grad_accum: int = 1,
        loss_fn: Optional[Callable] = None,
        attn_impl: str = "auto",
        offload_opt_state: bool = False,
        comm: Optional[shd.CommConfig] = None,
        health_sentinels: bool = False,
    ):
        # executables are made from here on: the compile recorder is on,
        # and what came before is ``setup.before_build_s``
        self._compiles = compile_cache.watch_compiles()
        self._compiles.first_build()
        self.cfg = cfg
        self.mesh = mesh
        self.optimizer = optimizer
        self.rules = rules
        self.grad_accum = grad_accum
        self.attn_impl = attn_impl
        self.offload_opt_state = offload_opt_state
        self.comm = comm
        # in-graph numeric-health scalars appended to the step metrics
        # (observability/sentinels.py); rides the existing metrics
        # readback — no extra host syncs, no extra collectives beyond
        # widening the metric psum the sharded region already issues
        self.health_sentinels = health_sentinels
        # resolved ZeRO-1 state: active flag, fallback reason (None when
        # active or never requested), and the static flat pack layout
        self.update_sharding, self.update_sharding_reason, self._plan = (
            resolve_update_sharding(
                cfg,
                mesh,
                optimizer,
                comm,
                loss_fn=loss_fn,
                offload_opt_state=offload_opt_state,
            )
        )
        self._wire = (
            comm.wire_for(mesh, "dp") if self.update_sharding else None
        )
        # resolved mode ("zero1" defers the gradient exchange to one
        # reduce-scatter per step; "zero2" exchanges every microbatch so
        # only the 1/dp shard survives the accumulation loop) and the
        # transformation actually run on the flat view (the optimizer
        # itself, or its plan-aware flat equivalent for factored state)
        self.update_mode = comm.update_mode if self.update_sharding else ""
        self._flat_opt = (
            _effective_flat_optimizer(optimizer, self._plan)
            if self.update_sharding
            else None
        )
        # hybrid (dp×fsdp / dp×tp) update sharding: the partial-manual
        # region suppresses the model's internal constraints, so params
        # are re-pinned to their rule shardings after the flat unpack
        self._param_shardings = (
            shd.shardings_for_tree(mesh, decoder.logical_axes(cfg), rules)
            if self.update_sharding and len(self._plan.mesh_axes) > 1
            else None
        )
        # switch-gating jitter needs a per-step rng; only the built-in
        # loss_fn accepts one (a custom loss_fn owns its rng handling)
        self._needs_rng = (
            loss_fn is None
            and cfg.n_experts > 0
            and cfg.moe_gating == "switch"
            and cfg.moe_jitter > 0.0
        )
        if cfg.fp8 and loss_fn is not None:
            raise ValueError(
                "cfg.fp8 threads fp8_states through the built-in "
                "loss_fn; a custom loss_fn cannot receive them"
            )
        self._loss_fn = loss_fn or functools.partial(
            decoder.loss_fn, cfg=cfg, mesh=mesh, attn_impl=attn_impl
        )

    def _grads(self, params, batch, rng=None, fp8=None):
        if self._needs_rng and rng is not None:
            loss_fn = functools.partial(self._loss_fn, rng=rng)
        else:
            loss_fn = self._loss_fn
        if fp8 == "current":
            # stateless current-scaling fp8 (pipeline meshes): nothing
            # to differentiate or thread — plain grads, no state out
            grad_fn = jax.value_and_grad(
                lambda p: loss_fn(p, batch, fp8_states="current"),
                has_aux=True,
            )
            (loss, metrics), grads = grad_fn(params)
            return loss, metrics, grads, None
        if fp8 is not None:
            # differentiate w.r.t. the fp8 state too: its "gradient" IS
            # the updated delayed-scaling state (ops/fp8.py convention)
            grad_fn = jax.value_and_grad(
                lambda p, f8: loss_fn(p, batch, fp8_states=f8),
                argnums=(0, 1),
                has_aux=True,
            )
            (loss, metrics), (grads, new_fp8) = grad_fn(params, fp8)
            return loss, metrics, grads, new_fp8
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (loss, metrics), grads = grad_fn(params, batch)
        return loss, metrics, grads, None

    def _accumulated_grads(self, params, batch, rng=None, fp8=None):
        """Microbatch scan: batch leading dim is [accum, micro_b, ...].

        fp8 delayed-scaling state advances ONCE per optimizer step, not
        once per microbatch: every microbatch quantizes against the
        SAME step-start scales (what an accum=1 step over the whole
        global batch would use), and the per-microbatch updated states
        merge by elementwise max. Each microbatch's new state is
        ``concat(hist[1:], amax_i)`` over the shared step-start history
        — all ≥ 0 — so the max is ``concat(hist[1:], max_i amax_i)``:
        exactly one history push carrying the global-batch amax,
        bitwise-matching the unfused single-step path (f32 max is
        exact). The stateless "current" mode has no carry entry."""
        a = self.grad_accum
        is_cur = fp8 == "current"

        def micro(carry, inp):
            mb, idx = inp
            if is_cur:
                g_acc, loss_acc = carry
                f8_acc = None
            else:
                g_acc, loss_acc, f8_acc = carry
            r = jax.random.fold_in(rng, idx) if rng is not None else None
            f8 = "current" if is_cur else fp8
            loss, _, g, new_f8 = self._grads(params, mb, rng=r, fp8=f8)
            g_acc = jax.tree.map(jnp.add, g_acc, g)
            if is_cur:
                return (g_acc, loss_acc + loss), None
            if fp8 is not None:
                f8_acc = jax.tree.map(jnp.maximum, f8_acc, new_f8)
            return (g_acc, loss_acc + loss, f8_acc), None

        zeros = jax.tree.map(jnp.zeros_like, params)
        mb_batch = jax.tree.map(
            lambda x: x.reshape((a, x.shape[0] // a) + x.shape[1:]), batch
        )
        loss0 = jnp.zeros([], jnp.float32)
        # zeros are a safe max-identity: histories hold amaxes (>= 0)
        f8_zero = (
            None if fp8 is None else jax.tree.map(jnp.zeros_like, fp8)
        )
        init = (zeros, loss0) if is_cur else (zeros, loss0, f8_zero)
        out, _ = jax.lax.scan(micro, init, (mb_batch, jnp.arange(a)))
        grads, loss = out[0], out[1]
        new_fp8 = None if is_cur else out[2]
        grads = jax.tree.map(lambda g: g / a, grads)
        return loss / a, {"loss": loss / a}, grads, new_fp8

    @property
    def comm_resolved(self) -> Optional[shd.CommConfig]:
        """The CommConfig iff update sharding resolved active — pass this
        to ``init_train_state``/``state_shardings`` so the optimizer
        state is laid out for the step that will actually run."""
        return self.comm if self.update_sharding else None

    def _sentinel_metrics(
        self, params, updates, loss, new_fp8, new_opt, counts
    ) -> Dict[str, Any]:
        """The health-sentinel scalars for one step (see
        observability/sentinels.py for the key contract).  ``counts`` is
        the [5] grad-count vector — computed on the gradient tree in the
        replicated path, or inside the sharded region's packed psum so
        the reduction rides the existing collective."""
        out = snt.counts_to_metrics(counts, snt.static_size(params))
        out["sent_update_ratio"] = snt.update_ratio(updates, params)
        out["sent_loss_nonfinite"] = snt.loss_nonfinite(loss)
        if new_fp8 is not None:
            out["sent_fp8_sat"] = snt.fp8_saturation(new_fp8)
        skips = snt.sanitizer_count(new_opt)
        if skips is not None:
            out["sent_sanitizer_skips"] = skips
        return out

    def _sharded_step_fn(
        self, state: TrainState, batch
    ) -> Tuple[TrainState, Dict]:
        """ZeRO-1 step: reduce-scatter grads → 1/dp optimizer shard →
        all-gather params (arxiv 2004.13336).

        One full-manual shard_map region computes per-rank local grads
        (loss normalized by the psum'd GLOBAL token count, so cotangents
        match the data-parallel program bit-for-bit), packs them into
        the plan's fixed buckets, and reduce-scatters bucket-by-bucket
        (f32 wire = bitwise psum_scatter; bf16/int8 = all_to_all with
        f32 accumulation, blockwise scales for int8). The optimizer then
        runs OUTSIDE the region on the flat ``P(None, "dp")``-sharded
        view — clip/fused/state_dtype compose unchanged, the partitioner
        keeps every elementwise op local — and a second tiny manual
        region applies ``p + u`` per rank and all-gathers the result.

        fp8 (``cfg.fp8``): the delayed-scaling state enters the region
        replicated (``P()``), each rank differentiates w.r.t. it (its
        cotangent IS the updated state, ops/fp8.py convention), and the
        per-rank updated histories merge with ``lax.pmax`` over dp —
        per-rank state differs ONLY in the freshly-pushed slot (local
        activation/grad amax over this rank's tokens; the prefix and
        the weight amax are replicated), so the pmax yields exactly the
        global-batch amax the unsharded program observes with its
        all-reduce-max, keeping the f32 wire bitwise. Quantization
        scales come from the step-START history, so gradients are
        unaffected by the merge order. Under grad_accum the microbatch
        states merge by elementwise max first (same once-per-step
        semantics as ``_accumulated_grads``).

        Hybrid meshes (dp×fsdp / dp×tp): the gradient region goes
        PARTIAL-manual — manual over dp only, fsdp/tp left to the auto
        partitioner, which inserts the model-axis collectives exactly
        as in the replicated program. Auto-axis values appear
        global-shaped inside the region, so the pack plan and the
        bucket exchange are unchanged; only the region's lowering mode
        and the accumulation structure differ (the 0.4.x partitioner
        cannot partition a ``lax.scan`` whose carry touches auto-axis-
        sharded values inside a partial-manual region, so accumulation
        unrolls as a Python loop there).

        Modes: ``zero2`` (the boolean default) reduce-scatters every
        microbatch and accumulates 1/dp shards — no full-gradient
        buffer survives the accumulation loop, and on the f32 wire the
        rounding order matches the unsharded program (which all-reduces
        per microbatch). ``zero1`` accumulates the full local gradient
        and defers to ONE exchange per step — a×fewer collectives, at
        the cost of full-gradient residency and a different (still
        deterministic) summation order.
        """
        cfg, mesh, plan = self.cfg, self.mesh, self._plan
        a, wire = self.grad_accum, self._wire
        tie = cfg.tie_embeddings
        zoo = len(plan.mesh_axes) > 1
        defer = self.update_mode == "zero1"
        sent = self.health_sentinels
        fp8 = state.get("fp8") if cfg.fp8 else None
        if a > 1:
            # microbatch split OUTSIDE the region so the (rank,
            # microbatch) data assignment matches _accumulated_grads
            batch = jax.tree.map(
                lambda x: x.reshape((a, x.shape[0] // a) + x.shape[1:]),
                batch,
            )
            batch_spec = P(None, "dp")
        else:
            batch_spec = P("dp")

        def vary(tree):
            # params enter the region replicated (unvarying over dp).
            # Differentiating an unvarying input makes jax all-reduce
            # its cotangent; marking it varying keeps each rank's
            # gradient local, for exchange() to reduce-scatter. Fresh
            # scan carries need the same mark to match their outputs.
            return jax.tree.map(
                lambda x: jax.lax.pcast(x, ("dp",), to="varying"), tree
            )

        def local_grads(params, f8, mb):
            params, f8 = vary(params), vary(f8)
            mask = mb.get("mask")
            if mask is None:
                mask = jnp.ones_like(mb["targets"], dtype=jnp.float32)
            local_tokens = jnp.sum(mask.astype(jnp.float32))
            denom = jnp.maximum(jax.lax.psum(local_tokens, "dp"), 1.0)

            def lf(p, z, f):
                # the region flag makes shd.constrain a no-op and (when
                # tied) aliases the lm-head's table read to z, so the
                # head cotangent separates from the lookup's — the two
                # ride separate reduce-scatters exactly like GSPMD's two
                # all-reduces in the unsharded lowering
                with shd.update_sharding_region(
                    tie_zero=z, unroll_scans=zoo
                ):
                    return decoder.loss_fn(
                        p,
                        mb,
                        cfg=cfg,
                        mesh=mesh,
                        attn_impl=self.attn_impl,
                        denom=denom,
                        fp8_states=f,
                    )

            nf8 = None
            if tie:
                z = vary(jnp.zeros(plan.shapes[0], jnp.float32))
                if f8 is not None:
                    (loss, metrics), (g, gz, nf8) = jax.value_and_grad(
                        lf, argnums=(0, 1, 2), has_aux=True
                    )(params, z, f8)
                else:
                    (loss, metrics), (g, gz) = jax.value_and_grad(
                        lambda p, z_: lf(p, z_, None),
                        argnums=(0, 1),
                        has_aux=True,
                    )(params, z)
            else:
                if f8 is not None:
                    (loss, metrics), (g, nf8) = jax.value_and_grad(
                        lambda p, f: lf(p, None, f),
                        argnums=(0, 1),
                        has_aux=True,
                    )(params, f8)
                else:
                    (loss, metrics), g = jax.value_and_grad(
                        lambda p: lf(p, None, None), has_aux=True
                    )(params)
                gz = None
            return loss, metrics, g, gz, nf8

        def exchange(g, gz):
            return shd.exchange_buckets(
                shd.pack_buckets(g, plan),
                plan,
                wire,
                axis="dp",
                tie_extra=gz if tie else None,
            )

        def region(params, f8, batch):
            if a > 1 and zoo:
                # UNROLLED microbatch loop: the 0.4.x partitioner dies
                # on a lax.scan touching auto-axis-sharded values inside
                # a partial-manual region, so hybrid meshes unroll.
                # zero2 exchanges per microbatch (shard-sized carry);
                # zero1 accumulates full local grads, one exchange.
                sh_acc = jnp.zeros(
                    (plan.n_buckets, plan.bucket_elems // plan.dp),
                    jnp.float32,
                )
                g_acc = gz_acc = None
                loss_acc = jnp.zeros([], jnp.float32)
                for i in range(a):
                    mb = jax.tree.map(lambda x: x[i], batch)
                    loss, _, g, gz, _ = local_grads(params, None, mb)
                    loss_acc = loss_acc + loss
                    if defer:
                        g_acc = (
                            g
                            if g_acc is None
                            else jax.tree.map(jnp.add, g_acc, g)
                        )
                        if tie:
                            gz_acc = gz if gz_acc is None else gz_acc + gz
                    else:
                        sh_acc = sh_acc + exchange(g, gz)
                shards = exchange(g_acc, gz_acc) if defer else sh_acc
                loc = {"loss": loss_acc}
                nf8 = None
            elif a > 1 and defer:
                # ZeRO-1 deferred exchange: accumulate the full local
                # gradient across the scan (like the replicated accum
                # path), then reduce-scatter ONCE — a×fewer collectives
                # than zero2, at full-gradient residency.
                def micro(carry, mb):
                    g_acc, gz_acc, loss_acc, f8_acc = carry
                    loss, _, g, gz, nf8 = local_grads(params, f8, mb)
                    g_acc = jax.tree.map(jnp.add, g_acc, g)
                    if tie:
                        gz_acc = gz_acc + gz
                    if f8 is not None:
                        f8_acc = jax.tree.map(jnp.maximum, f8_acc, nf8)
                    return (g_acc, gz_acc, loss_acc + loss, f8_acc), None

                init = (
                    jax.tree.map(jnp.zeros_like, params),
                    jnp.zeros(plan.shapes[0], jnp.float32) if tie else None,
                    jnp.zeros([], jnp.float32),
                    None if f8 is None else jax.tree.map(jnp.zeros_like, f8),
                )
                (g_acc, gz_acc, loss_acc, nf8), _ = jax.lax.scan(
                    micro, vary(init), batch
                )
                shards = exchange(g_acc, gz_acc)
                loc = {"loss": loss_acc}
            elif a > 1:
                # zero2 (the boolean default): reduce-scatter EVERY
                # microbatch and accumulate the shards — the order the
                # unsharded program rounds in (GSPMD all-reduces each
                # microbatch's grads before the scan carry add), so the
                # f32 wire stays bitwise. Same collective count as the
                # baseline, half the bytes, and no full-gradient buffer
                # across the scan.
                def micro(carry, mb):
                    sh_acc, loss_acc, f8_acc = carry
                    loss, _, g, gz, nf8 = local_grads(params, f8, mb)
                    shards = exchange(g, gz)
                    if f8 is not None:
                        f8_acc = jax.tree.map(jnp.maximum, f8_acc, nf8)
                    return (sh_acc + shards, loss_acc + loss, f8_acc), None

                zeros = jnp.zeros(
                    (plan.n_buckets, plan.bucket_elems // plan.dp),
                    jnp.float32,
                )
                f8_zero = (
                    None
                    if f8 is None
                    else jax.tree.map(jnp.zeros_like, f8)
                )
                (shards, loss_acc, nf8), _ = jax.lax.scan(
                    micro,
                    vary((zeros, jnp.zeros([], jnp.float32), f8_zero)),
                    batch,
                )
                loc = {"loss": loss_acc}
            else:
                _, loc, g, gz, nf8 = local_grads(params, f8, batch)
                shards = exchange(g, gz)
            if sent:
                # sentinel counts over THIS RANK's post-exchange shard of
                # the averaged gradient, packed with the metric scalars
                # into a single psum — the counts ride the metrics'
                # existing all-reduce instead of adding a collective.
                # Elementwise psum over the concatenation reduces each
                # lane exactly like a standalone scalar psum, so "loss"
                # stays bitwise identical to the sentinels-off lowering.
                cnt = snt.grad_counts(shards / a if a > 1 else shards)
                keys = list(loc)
                vec = jax.lax.psum(
                    jnp.concatenate(
                        [
                            jnp.stack(
                                [
                                    loc[k].astype(jnp.float32)
                                    for k in keys
                                ]
                            ),
                            cnt,
                        ]
                    ),
                    "dp",
                )
                metrics = {k: vec[i] for i, k in enumerate(keys)}
                metrics["_sent_counts"] = vec[len(keys):]
            else:
                metrics = {
                    k: jax.lax.psum(v, "dp") for k, v in loc.items()
                }
            if a > 1:
                metrics["loss"] = metrics["loss"] / a
            if f8 is not None:
                # global amax: per-rank states differ only in the new
                # slot (this rank's local amax); max over dp = the
                # unsharded program's all-reduce-max, exactly
                nf8 = jax.tree.map(
                    lambda h: jax.lax.pmax(h, "dp"), nf8
                )
            # this rank's quarter of the f32 master, in the stream's
            # coordinates, for the sharded optimizer: packed here so
            # the whole [n_buckets, bucket_elems] stream is never built.
            # The barrier orders the pack after the exchange: left free,
            # the scheduler puts the parameters' 1-D views beside the
            # gradients' at the step's memory peak (+0.25 GB compiled
            # for a v5e 2x2 at 24 layers of GPT-2 XL)
            params, shards = jax.lax.optimization_barrier((params, shards))
            fp = shd.pack_shard(params, plan, jax.lax.axis_index("dp"))
            return metrics, shards, nf8, fp

        sm_kwargs = {}
        if zoo:
            # partial-manual: dp is manual (the explicit psum_scatter /
            # psum collectives), fsdp/tp stay with the auto partitioner
            sm_kwargs["axis_names"] = {"dp"}
        metrics, grads_flat, new_fp8, params_flat = jax.shard_map(
            region,
            mesh=mesh,
            in_specs=(P(), P(), batch_spec),
            out_specs=(P(), P(None, "dp"), P(), P(None, "dp")),
            **sm_kwargs,
        )(state["params"], fp8, batch)
        if a > 1:
            # divide AFTER the exchange, where GSPMD's unsharded program
            # divides after its all-reduce — keeps the f32 wire bitwise
            grads_flat = grads_flat / a
        flat_sh = NamedSharding(mesh, P(None, "dp"))
        if zoo:
            # pin the flat stream's layout: the bucket axis dp-sharded,
            # replicated over fsdp/tp, so the optimizer sweep below is
            # purely elementwise-local (the HLO guard pins zero
            # cross-axis collectives on the moments)
            grads_flat = jax.lax.with_sharding_constraint(
                grads_flat, flat_sh
            )
            params_flat = jax.lax.with_sharding_constraint(
                params_flat, flat_sh
            )
        with jax.named_scope("zero.update"):
            updates, new_opt = self._flat_opt.update(
                {"flat": grads_flat},
                state["opt_state"],
                {"flat": params_flat},
            )

        def apply_region(fp, u):
            # per-rank `p + u` BEFORE the all-gather. Done in auto mode
            # the partitioner is free to gather `u` first, which splits
            # the optimizer's trailing `-lr * y` multiply from this add
            # and changes how the backend contracts the pair — a 1-ulp
            # params drift vs the unsharded step. Keeping the add inside
            # the manual region pins mult→add adjacency on every rank.
            return shd.gather_stream(fp + u, "dp")

        with jax.named_scope("zero.gather"):
            new_flat = jax.shard_map(
                apply_region,
                mesh=mesh,
                in_specs=(P(None, "dp"), P(None, "dp")),
                out_specs=P(),
                # the tiled all_gather IS replicated over dp, but the
                # public collective types its result as varying
                check_vma=False,
            )(params_flat, updates["flat"])
            params = shd.unpack_flat(new_flat, state["params"], plan)
        # what one rank moves for ZeRO in a step. Trace time, once per
        # compile, and values: a retrace cannot double them. zero2
        # exchanges every microbatch, zero1 once; the whole f32
        # parameter stream is gathered back
        set_counter(
            "zero.exchange_bytes",
            (1 if defer else a)
            * shd.exchange_payload_bytes(
                plan, wire, tie and bool(plan.tie_size)
            ),
        )
        set_counter("zero.gather_bytes", plan.padded * 4)
        if zoo:
            # the region suppressed the model's internal constraints;
            # re-pin the unpacked params to their rule shardings so the
            # next step (and checkpointing) sees the canonical layout
            params = jax.tree.map(
                jax.lax.with_sharding_constraint,
                params,
                self._param_shardings,
            )
        metrics = dict(metrics)
        counts = metrics.pop("_sent_counts", None)
        metrics["grad_norm"] = optax.global_norm(grads_flat)
        if self.health_sentinels:
            metrics.update(
                self._sentinel_metrics(
                    state["params"],
                    updates,
                    metrics["loss"],
                    new_fp8,
                    new_opt,
                    counts,
                )
            )
        new_state = {
            "params": params,
            "opt_state": new_opt,
            "step": state["step"] + 1,
        }
        if fp8 is not None:
            new_state["fp8"] = new_fp8
        return new_state, metrics

    def step_fn(self, state: TrainState, batch) -> Tuple[TrainState, Dict]:
        if self.update_sharding:
            return self._sharded_step_fn(state, batch)
        batch = jax.tree.map(
            lambda x: shd.constrain(
                x, self.mesh, "batch", "seq", rules=self.rules
            )
            if x.ndim >= 2
            else x,
            batch,
        )
        rng = None
        if self._needs_rng:
            # deterministic per-step jitter key: same across hosts (SPMD
            # lockstep), different every step
            rng = jax.random.fold_in(jax.random.key(17), state["step"])
        fp8 = state.get("fp8")
        if (
            fp8 is None
            and self.cfg.fp8
            and self.mesh.shape.get("pp", 1) > 1
        ):
            # pipeline meshes: stateless current-scaling fp8 (delayed-
            # scaling state cannot thread a pipeline schedule; see
            # decoder.run_trunk)
            fp8 = "current"
        if self.grad_accum > 1:
            loss, metrics, grads, new_fp8 = self._accumulated_grads(
                state["params"], batch, rng=rng, fp8=fp8
            )
        else:
            loss, metrics, grads, new_fp8 = self._grads(
                state["params"], batch, rng=rng, fp8=fp8
            )
        opt_state = state["opt_state"]
        if self.offload_opt_state:
            # stream the moments HBM-ward only for the update; the jitted
            # step's output shardings put the new state back on host
            opt_state = _to_memory_kind(opt_state, _DEVICE)
        with jax.named_scope("optimizer"):
            updates, new_opt = self.optimizer.update(
                grads, opt_state, state["params"]
            )
            params = optax.apply_updates(state["params"], updates)
        if self.offload_opt_state:
            new_opt = _to_memory_kind(new_opt, _HOST)
        metrics = dict(metrics)
        metrics["grad_norm"] = optax.global_norm(grads)
        if self.health_sentinels:
            metrics.update(
                self._sentinel_metrics(
                    state["params"],
                    updates,
                    loss,
                    new_fp8,
                    new_opt,
                    snt.grad_counts(grads),
                )
            )
        new_state = {
            "params": params,
            "opt_state": new_opt,
            "step": state["step"] + 1,
        }
        if new_fp8 is not None:
            new_state["fp8"] = new_fp8
        return new_state, metrics

    def build(self) -> Callable:
        """Return the jitted step with donated state."""
        self._compiles.step_program(self.step_fn.__name__)
        return jax.jit(self.step_fn, donate_argnums=(0,))

    # ---- fused multi-step block -----------------------------------------

    def block_fn(
        self, state: TrainState, batches
    ) -> Tuple[TrainState, Dict]:
        """Run K train steps as ONE device program.

        ``batches`` leaves carry a leading block axis: [K, ...] (e.g.
        tokens [K, B, S]).  A ``lax.scan`` over that axis applies
        ``step_fn`` K times — microbatch accumulation, fp8 state
        threading, and remat policies all compose unchanged because the
        scan body IS ``step_fn``.  Per-step metrics (loss, grad_norm,
        spike inputs) come back STACKED as [K] arrays, so the host
        touches the device once per block instead of once per step:
        Python dispatch, metric readback, and callback cadence checks
        amortize over K steps (cf. TorchTitan's overlap-everything
        loop).  The per-step rng derivation keys off the step counter in
        the carry, so a fused block and K sequential calls see identical
        randomness.
        """
        return jax.lax.scan(self.step_fn, state, batches)

    def build_block(self) -> Callable:
        """Jitted K-step block with donated state.

        One compiled program per distinct K (the trainer shrinks K at
        cadence boundaries, so a handful of sizes compile over a run).
        """
        if self.offload_opt_state:
            # the per-step HBM<->host moment streaming inside a scan
            # body would serialize against the scan carry; run offloaded
            # states unfused instead of silently deoptimizing
            raise NotImplementedError(
                "fused train blocks do not compose with "
                "offload_opt_state; use block_k=1"
            )
        self._compiles.step_program(self.block_fn.__name__)
        return jax.jit(self.block_fn, donate_argnums=(0,))


def build_eval_step(cfg: ModelConfig, mesh, rules=None, attn_impl="auto"):
    def eval_step(params, batch):
        _, metrics = decoder.loss_fn(
            params, batch, cfg=cfg, mesh=mesh, attn_impl=attn_impl
        )
        return metrics

    return jax.jit(eval_step)
