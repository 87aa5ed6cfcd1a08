"""Optimizer factory (optax).

Covers the reference's optimizer surface (atorch/atorch/optimizers: AdamW
paths, AGD agd.py, WSAM wsam.py, BF16/low-bit optimizer states) with optax
transforms. Low-bit (int8) optimizer state lives in
``dlrover_tpu/ops/quant.py`` and is applied as an optax wrapper.
"""

from typing import Optional

import jax
import jax.numpy as jnp
import optax
import optax.tree_utils as _otu


def warmup_cosine(
    peak_lr: float,
    warmup_steps: int = 100,
    decay_steps: int = 10000,
    end_lr_ratio: float = 0.1,
) -> optax.Schedule:
    return optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=peak_lr,
        warmup_steps=warmup_steps,
        decay_steps=decay_steps,
        end_value=peak_lr * end_lr_ratio,
    )


def build_schedule(
    name: str,
    peak_lr: float,
    warmup_steps: int = 100,
    decay_steps: int = 10000,
    end_lr_ratio: float = 0.1,
):
    """Named LR schedules (reference: atorch_trainer's HF-style
    lr_scheduler_type breadth — linear/cosine/constant/polynomial/
    inverse_sqrt). Returns an optax schedule fn, or the constant
    ``peak_lr`` for name="constant" without warmup."""
    if name == "warmup_cosine":
        return warmup_cosine(
            peak_lr, warmup_steps, decay_steps, end_lr_ratio
        )
    if name == "warmup_linear":
        return optax.join_schedules(
            [
                optax.linear_schedule(0.0, peak_lr, warmup_steps),
                optax.linear_schedule(
                    peak_lr, peak_lr * end_lr_ratio,
                    max(1, decay_steps - warmup_steps),
                ),
            ],
            [warmup_steps],
        )
    if name == "constant_with_warmup":
        return optax.join_schedules(
            [
                optax.linear_schedule(0.0, peak_lr, warmup_steps),
                optax.constant_schedule(peak_lr),
            ],
            [warmup_steps],
        )
    if name == "constant":
        return peak_lr
    if name == "polynomial":
        return optax.join_schedules(
            [
                optax.linear_schedule(0.0, peak_lr, warmup_steps),
                optax.polynomial_schedule(
                    peak_lr, peak_lr * end_lr_ratio, power=2.0,
                    transition_steps=max(1, decay_steps - warmup_steps),
                ),
            ],
            [warmup_steps],
        )
    if name == "inverse_sqrt":
        def sched(step):
            import jax.numpy as _jnp

            s = _jnp.maximum(step, 1)
            warm = peak_lr * s / max(warmup_steps, 1)
            decay = peak_lr * (max(warmup_steps, 1) / s) ** 0.5
            return _jnp.where(s < warmup_steps, warm, decay)

        return sched
    raise ValueError(f"unknown schedule {name!r}")


def _make_clip_fn(updates, grad_clip: float):
    """Per-leaf global-norm clip closure, numerically identical to
    ``optax.clip_by_global_norm(grad_clip)``: one global-norm
    reduction, then each leaf is scaled in its own dtype. Lets the
    fused/streamed optimizers fold clipping into their single state
    traversal instead of materializing a clipped gradient tree as a
    separate chain link."""
    if not grad_clip or grad_clip <= 0:
        return lambda g: g
    g_norm = optax.global_norm(updates)
    trigger = jnp.squeeze(g_norm < grad_clip)

    def clip_fn(g):
        return jax.lax.select(
            trigger, g, (g / g_norm.astype(g.dtype)) * grad_clip
        )

    return clip_fn


def fused_adamw(
    learning_rate,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    grad_clip: float = 0.0,
    state_dtype: Optional[str] = None,
) -> optax.GradientTransformation:
    """Single-traversal AdamW: global-norm clipping, the moment
    updates, decoupled weight decay, and the lr scaling all happen in
    one walk over the gradient tree — one read and one write per
    optimizer-state leaf.

    Why: ``optax.chain(clip_by_global_norm, adamw)`` is four chained
    transforms (clip, scale_by_adam, add_decayed_weights, scale_by_lr),
    each materializing a full update tree between links. At 1.4B params
    that is ~11 GiB of optimizer state + gradients walked repeatedly in
    an HBM-bound phase of the step. Here the chain's per-leaf math is
    applied verbatim inside one tree.map, so XLA sees a single fused
    elementwise region per leaf and the state streams through VMEM
    once.

    Numerics match the optax chain EXACTLY (pinned in
    tests/test_fused_optimizer.py): the clip trigger/scale formula is
    ``clip_by_global_norm``'s, the moment/bias-correction arithmetic is
    ``scale_by_adam``'s (including the safe int32 count increment and
    the schedule reading the PRE-increment count), decay is
    ``add_decayed_weights``, the sign flip is ``scale_by_learning_rate``.

    ``state_dtype``: None (f32 moments, matching ``optax.adamw`` on f32
    params) | "bfloat16" (bf16 mu like ``mu_dtype=bfloat16``) |
    "factored" (delegates to ``factored_adamw`` with the clip folded
    into ITS single traversal).
    """
    if state_dtype == "factored":
        return factored_adamw(
            learning_rate, b1=b1, b2=b2, eps=eps,
            weight_decay=weight_decay, grad_clip=grad_clip,
        )
    if state_dtype not in (None, "bfloat16"):
        raise ValueError(
            "fused_adamw supports state_dtype None/'bfloat16'/'factored'; "
            f"got {state_dtype!r} (quantized states keep their own fused "
            "streaming paths in ops/quant.py)"
        )
    mu_dtype = jnp.bfloat16 if state_dtype == "bfloat16" else None

    def _lr(step):
        return learning_rate(step) if callable(learning_rate) else learning_rate

    def init_fn(params):
        return {
            "step": jnp.zeros([], jnp.int32),
            # optax scale_by_adam state layout: mu in mu_dtype (param
            # dtype when None), nu in the param dtype
            "m": jax.tree.map(
                lambda p: jnp.zeros_like(p, mu_dtype or p.dtype), params
            ),
            "v": jax.tree.map(jnp.zeros_like, params),
        }

    def update_fn(updates, state, params=None):
        if weight_decay and params is None:
            raise ValueError("fused_adamw with weight_decay needs params")
        # optax numerics.safe_increment: saturate instead of wrapping
        max_t = jnp.iinfo(jnp.int32).max
        step = jnp.where(state["step"] < max_t, state["step"] + 1, max_t)
        # schedule parity with optax.scale_by_schedule: the lr for
        # update t reads schedule(count BEFORE increment)
        lr = _lr(state["step"])
        p_tree = params if params is not None else updates
        clip = _make_clip_fn(updates, grad_clip)

        def leaf(g, m, v, p):
            gc = clip(g)
            m2 = (1 - b1) * gc + b1 * m
            v2 = (1 - b2) * (gc * gc) + b2 * v
            # optax's tree_bias_correction is a jitted region, where
            # XLA rewrites the scalar divide to a reciprocal multiply;
            # route through it so eager parity is BITWISE, not 1-ulp
            mhat = _otu.tree_bias_correction(m2, b1, step)
            vhat = _otu.tree_bias_correction(v2, b2, step)
            u = mhat / (jnp.sqrt(vhat) + eps)
            if weight_decay:
                u = u + weight_decay * p
            if callable(learning_rate):
                u = jnp.array(-lr, dtype=u.dtype) * u
            else:
                u = -lr * u
            return u, m2.astype(mu_dtype) if mu_dtype else m2, v2

        out = jax.tree.map(
            leaf, updates, state["m"], state["v"], p_tree
        )
        is_triple = lambda x: isinstance(x, tuple)
        return (
            jax.tree.map(lambda o: o[0], out, is_leaf=is_triple),
            {
                "step": step,
                "m": jax.tree.map(lambda o: o[1], out, is_leaf=is_triple),
                "v": jax.tree.map(lambda o: o[2], out, is_leaf=is_triple),
            },
        )

    return optax.GradientTransformation(init_fn, update_fn)


def factored_adamw(
    learning_rate,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    m_dtype=jnp.bfloat16,
    min_factored_size: int = 128,
    grad_clip: float = 0.0,
) -> optax.GradientTransformation:
    """AdamW momentum + Adafactor-style factored second moment.

    For every matrix-shaped parameter the per-element variance nu is
    replaced by its rank-1 nonnegative factorization (row means R and
    column means C with v_hat = R*C / mean(R), exactly Adafactor's
    estimator, Shazeer & Stern 2018); vectors/scalars keep exact nu.
    First moment stays dense bf16 — this is the "Adafactor with
    momentum" / CAME family that trained T5 and PaLM.

    Why it exists here: on a 16 GiB v5e training 1.4B params, dense nu
    costs 2.7 GiB of HBM and ~5.4 GiB of optimizer bandwidth per step.
    Factoring frees both — the HBM goes to activations (a larger
    batch, or ``remat: none``), the bandwidth shortens the optimizer
    phase outright. Reference capability analog: atorch low-bit states
    (low_bit/functional.py) compress nu 4x; factoring compresses it
    ~1000x with a weaker (but battle-tested) estimator.
    """

    def _lr(step):
        return learning_rate(step) if callable(learning_rate) else learning_rate

    def _factored(p) -> bool:
        return (
            p.ndim >= 2
            and p.shape[-1] >= min_factored_size
            and p.shape[-2] >= min_factored_size
        )

    def init_fn(params):
        def m0(p):
            return jnp.zeros_like(
                p, m_dtype if p.ndim >= 1 else jnp.float32
            )

        def v0(p):
            if _factored(p):
                return {
                    "r": jnp.zeros(p.shape[:-1], jnp.float32),
                    "c": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32),
                }
            return jnp.zeros_like(p, jnp.float32)

        return {
            "step": jnp.zeros([], jnp.int32),
            "m": jax.tree.map(m0, params),
            "v": jax.tree.map(v0, params),
        }

    def update_fn(updates, state, params=None):
        if weight_decay and params is None:
            raise ValueError("factored_adamw with weight_decay needs params")
        step = state["step"] + 1
        t = step.astype(jnp.float32)
        bc1 = 1 - b1**t
        bc2 = 1 - b2**t
        # schedule parity with optax.scale_by_schedule: the lr for
        # update t reads schedule(count BEFORE increment) — bias
        # correction uses the incremented count
        lr = _lr(state["step"])
        p_tree = params if params is not None else updates
        # grad_clip folded into this same traversal (fused_adamw path)
        clip = _make_clip_fn(updates, grad_clip)

        from dlrover_tpu.ops.quant import adamw_direction, adamw_m_ema

        def leaf(g, m, v, p):
            g32 = clip(g).astype(jnp.float32)
            m2 = adamw_m_ema(g32, m.astype(jnp.float32), b1)
            g2 = g32 * g32
            if isinstance(v, dict):
                r2 = b2 * v["r"] + (1 - b2) * jnp.mean(g2, axis=-1)
                c2 = b2 * v["c"] + (1 - b2) * jnp.mean(g2, axis=-2)
                # v_hat = outer(r, c) / mean(r): exact when nu is rank-1
                denom = jnp.maximum(jnp.mean(r2, axis=-1, keepdims=True),
                                    1e-30)
                vhat = (r2 / denom)[..., None] * c2[..., None, :]
                new_v = {"r": r2, "c": c2}
            else:
                vhat = b2 * v + (1 - b2) * g2
                new_v = vhat
            upd = adamw_direction(
                m2, vhat, bc1, bc2, eps, weight_decay,
                p.astype(jnp.float32) if weight_decay else None,
            )
            return (-lr * upd).astype(g.dtype), m2.astype(m.dtype), new_v

        # the v tree nests {"r","c"} dicts below the grads' leaf
        # positions — flatten_up_to collapses them back to one entry per
        # grad leaf so the trees zip despite the ragged structure
        gdef = jax.tree.structure(updates)
        g_leaves = gdef.flatten_up_to(updates)
        m_leaves = gdef.flatten_up_to(state["m"])
        v_leaves = gdef.flatten_up_to(state["v"])
        p_leaves = gdef.flatten_up_to(p_tree)
        out = [
            leaf(g, m, v, p)
            for g, m, v, p in zip(g_leaves, m_leaves, v_leaves, p_leaves)
        ]
        return (
            jax.tree.unflatten(gdef, [o[0] for o in out]),
            {
                "step": step,
                "m": jax.tree.unflatten(gdef, [o[1] for o in out]),
                "v": jax.tree.unflatten(gdef, [o[2] for o in out]),
            },
        )

    # advertise the plan-aware flat equivalent to the update-sharding
    # resolver (train_step._effective_flat_optimizer). Attached to the
    # init FUNCTION because GradientTransformation is a NamedTuple and
    # refuses attribute assignment.
    init_fn._flat_factory = lambda plan: flat_factored_adamw(
        plan,
        learning_rate,
        b1=b1,
        b2=b2,
        eps=eps,
        weight_decay=weight_decay,
        m_dtype=m_dtype,
        min_factored_size=min_factored_size,
        grad_clip=grad_clip,
    )
    return optax.GradientTransformation(init_fn, update_fn)


def flat_factored_adamw(
    plan,
    learning_rate,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    m_dtype=jnp.bfloat16,
    min_factored_size: int = 128,
    grad_clip: float = 0.0,
) -> optax.GradientTransformation:
    """``factored_adamw`` reconstituted over a PackPlan's flat view.

    The ZeRO-1 update path hands the optimizer ONE leaf — the packed
    ``[n_buckets, bucket_elems]`` gradient stream — which a naively
    applied factored estimator would mis-factor (row/col means of the
    bucket matrix mean nothing). This transformation knows the pack
    layout: it rebuilds each parameter's view out of the flat stream
    (``flat.reshape(-1)[off:off+size].reshape(shape)``), runs
    ``factored_adamw``'s exact per-leaf math on the views, and repacks.

    State layout: the first moment stays ONE flat bf16
    ``[n_buckets, bucket_elems]`` leaf — flat-shaped, so the update
    sharding keeps it dp-sharded like the dense-Adam moments — while
    the second moment is a per-leaf tuple of Adafactor ``{"r","c"}``
    factor pairs (full f32 nu for leaves under ``min_factored_size``),
    replicated: the factors are the ~1000x-compressed part, so
    replicating them costs less than the bucket padding. Zero padding
    in the stream stays zero through the update (``m_ema`` and the
    repack both preserve it).
    """

    def _lr(step):
        return learning_rate(step) if callable(learning_rate) else learning_rate

    shapes, sizes, offsets = plan.shapes, plan.sizes, plan.offsets
    flat_shape = (plan.n_buckets, plan.bucket_elems)

    def _factored(shape) -> bool:
        return (
            len(shape) >= 2
            and shape[-1] >= min_factored_size
            and shape[-2] >= min_factored_size
        )

    def _views(flat):
        s = flat.reshape(-1)
        return [
            s[o : o + n].reshape(shp)
            for o, n, shp in zip(offsets, sizes, shapes)
        ]

    def _repack(leaves, dtype):
        # slice writes into zeros, not concatenate + pad: on jax 0.4.x a
        # concatenate mixing auto-axis-sharded operands with fresh zeros
        # came back scaled by an unrelated mesh-axis size (ROADMAP D4:
        # parallel.sharding's rows dropped this spelling under 0.9)
        flat = jnp.zeros((plan.padded,), dtype)
        off = 0
        for l in leaves:
            flat = jax.lax.dynamic_update_slice(
                flat, l.reshape(-1).astype(dtype), (off,)
            )
            off += int(l.size)
        return flat.reshape(flat_shape)

    def init_fn(flat_params):
        del flat_params  # layout comes from the plan, not the value
        v = []
        for shp in shapes:
            if _factored(shp):
                v.append(
                    {
                        "r": jnp.zeros(shp[:-1], jnp.float32),
                        "c": jnp.zeros(shp[:-2] + shp[-1:], jnp.float32),
                    }
                )
            else:
                v.append(jnp.zeros(shp, jnp.float32))
        return {
            "step": jnp.zeros([], jnp.int32),
            "m": jnp.zeros(flat_shape, m_dtype),
            "v": tuple(v),
        }

    def update_fn(updates, state, params=None):
        if weight_decay and params is None:
            raise ValueError(
                "flat_factored_adamw with weight_decay needs params"
            )
        step = state["step"] + 1
        t = step.astype(jnp.float32)
        bc1 = 1 - b1**t
        bc2 = 1 - b2**t
        # schedule parity with optax.scale_by_schedule (see
        # factored_adamw): lr reads the PRE-increment count
        lr = _lr(state["step"])
        clip = _make_clip_fn(updates, grad_clip)

        from dlrover_tpu.ops.quant import adamw_direction, adamw_m_ema

        g_views = _views(clip(updates["flat"]))
        m_views = _views(state["m"])
        p_views = (
            _views(params["flat"]) if params is not None else g_views
        )
        upds, m2s, v2s = [], [], []
        for g, m, v, p in zip(g_views, m_views, state["v"], p_views):
            g32 = g.astype(jnp.float32)
            m2 = adamw_m_ema(g32, m.astype(jnp.float32), b1)
            g2 = g32 * g32
            if isinstance(v, dict):
                r2 = b2 * v["r"] + (1 - b2) * jnp.mean(g2, axis=-1)
                c2 = b2 * v["c"] + (1 - b2) * jnp.mean(g2, axis=-2)
                denom = jnp.maximum(
                    jnp.mean(r2, axis=-1, keepdims=True), 1e-30
                )
                vhat = (r2 / denom)[..., None] * c2[..., None, :]
                new_v = {"r": r2, "c": c2}
            else:
                vhat = b2 * v + (1 - b2) * g2
                new_v = vhat
            upd = adamw_direction(
                m2, vhat, bc1, bc2, eps, weight_decay,
                p.astype(jnp.float32) if weight_decay else None,
            )
            upds.append((-lr * upd).astype(jnp.float32))
            m2s.append(m2.astype(m_dtype))
            v2s.append(new_v)
        return (
            {"flat": _repack(upds, jnp.float32)},
            {
                "step": step,
                "m": _repack(m2s, m_dtype),
                "v": tuple(v2s),
            },
        )

    return optax.GradientTransformation(init_fn, update_fn)


def streamed_offload_adamw(
    learning_rate,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    grad_clip: float = 0.0,
) -> optax.GradientTransformation:
    """AdamW whose moments live in pinned host memory, streamed per leaf.

    The legacy offload path (TrainStepBuilder.offload_opt_state) moves
    the WHOLE moment tree HBM-ward before the update — a transient
    device working set of 2x param bytes, exactly the peak offload
    exists to avoid (ADVICE r1 #1 / VERDICT r2 #8). Here the update
    walks the leaves in a serialized chain: each leaf's host->device
    transfer is data-dependent (via lax.optimization_barrier) on the
    previous leaf's computed update, so XLA cannot hoist the transfers
    together and the device-resident moment working set is bounded by
    the LARGEST LEAF (m+v), not the tree. accelerate/analyser.py models
    this bound for the `offload_opt` strategy tier.

    Drop-in for optax.adamw inside a chain (grad clipping composes in
    front). Moments are placed on host inside update_fn; pair with
    ``init_train_state(offload_opt_state=True)`` so they are BORN on
    host too. Reference capability: atorch's CPU-offload Adam
    (SURVEY §2.3 optimizers).
    """
    from dlrover_tpu.ops.quant import adamw_direction, adamw_moments

    _host = jax.memory.Space.Host
    _dev = jax.memory.Space.Device

    def _lr(step):
        return learning_rate(step) if callable(learning_rate) else learning_rate

    def init_fn(params):
        zeros = lambda p: jnp.zeros_like(p, jnp.float32)
        return {
            "step": jnp.zeros([], jnp.int32),
            "m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params),
        }

    def update_fn(updates, state, params=None):
        if weight_decay and params is None:
            raise ValueError(
                "streamed_offload_adamw with weight_decay needs params"
            )
        step = state["step"] + 1
        t = step.astype(jnp.float32)
        bc1 = 1 - b1**t
        bc2 = 1 - b2**t
        # schedule parity with optax.scale_by_schedule: the lr for
        # update t reads schedule(count BEFORE increment) — bias
        # correction uses the incremented count
        lr = _lr(state["step"])
        p_tree = params if params is not None else updates

        gdef = jax.tree.structure(updates)
        g_leaves = jax.tree.leaves(updates)
        m_leaves = gdef.flatten_up_to(state["m"])
        v_leaves = gdef.flatten_up_to(state["v"])
        p_leaves = gdef.flatten_up_to(p_tree)

        # grad_clip folded into the streamed walk: the norm reduction
        # runs on the device-resident grads before any moment transfer
        clip = _make_clip_fn(updates, grad_clip)

        token = step.astype(jnp.float32)
        out_u, out_m, out_v = [], [], []
        for g, m_h, v_h, p in zip(g_leaves, m_leaves, v_leaves, p_leaves):
            # serialize THE TRANSFER: the host values only become
            # consumable after the previous leaf's token, so the
            # host->device copy cannot be hoisted to the front
            m_h, v_h, tok = jax.lax.optimization_barrier(
                (m_h, v_h, token)
            )
            m32 = jax.device_put(m_h, _dev)
            v32 = jax.device_put(v_h, _dev)
            g32 = clip(g).astype(jnp.float32)
            m2, v2 = adamw_moments(g32, m32, v32, b1, b2)
            upd = adamw_direction(
                m2, v2, bc1, bc2, eps, weight_decay,
                p.astype(jnp.float32) if weight_decay else None,
            )
            out_u.append((-lr * upd).astype(g.dtype))
            out_m.append(jax.device_put(m2, _host))
            out_v.append(jax.device_put(v2, _host))
            token = m2.ravel()[0] + tok * 0
        return (
            jax.tree.unflatten(gdef, out_u),
            {
                "step": step,
                "m": jax.tree.unflatten(gdef, out_m),
                "v": jax.tree.unflatten(gdef, out_v),
            },
        )

    return optax.GradientTransformation(init_fn, update_fn)


def agd(
    learning_rate,
    b1: float = 0.9,
    b2: float = 0.999,
    delta: float = 1e-5,
    eps: float = 1e-8,
) -> optax.GradientTransformation:
    """AGD optimizer (reference: atorch/optimizers/agd.py, NeurIPS'23).

    Auto-switches between gradient descent and adaptive step by comparing
    the gradient-difference preconditioner against ``delta``.
    """

    def init_fn(params):
        return {
            "step": jnp.zeros([], jnp.int32),
            "m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(jnp.zeros_like, params),
            "prev_g": jax.tree.map(jnp.zeros_like, params),
        }

    def update_fn(updates, state, params=None):
        step = state["step"] + 1
        t = step.astype(jnp.float32)

        def upd(g, m, v, pg):
            # gradient difference replaces the raw gradient in the second
            # moment — the AGD preconditioner.
            diff = g - b1 * pg
            m2 = b1 * m + (1 - b1) * g
            v2 = b2 * v + (1 - b2) * (diff * diff)
            mhat = m2 / (1 - b1**t)
            vhat = v2 / (1 - b2**t)
            denom = jnp.maximum(jnp.sqrt(vhat) / delta, 1.0)
            return -mhat / (denom * delta + eps), m2, v2, g

        flat = jax.tree.map(
            upd, updates, state["m"], state["v"], state["prev_g"]
        )
        out = jax.tree.map(lambda x: x[0], flat, is_leaf=lambda x: isinstance(x, tuple))
        m = jax.tree.map(lambda x: x[1], flat, is_leaf=lambda x: isinstance(x, tuple))
        v = jax.tree.map(lambda x: x[2], flat, is_leaf=lambda x: isinstance(x, tuple))
        pg = jax.tree.map(lambda x: x[3], flat, is_leaf=lambda x: isinstance(x, tuple))
        lr = learning_rate(step) if callable(learning_rate) else learning_rate
        out = jax.tree.map(lambda u: lr * u, out)
        return out, {"step": step, "m": m, "v": v, "prev_g": pg}

    return optax.GradientTransformation(init_fn, update_fn)


def wsam(
    base: optax.GradientTransformation,
    rho: float = 0.05,
    gamma: float = 0.9,
) -> optax.GradientTransformation:
    """Weighted Sharpness-Aware Minimization (reference:
    atorch/optimizers/wsam.py, KDD'23).

    Minimizes ``L + γ/(1-γ)·(L_sam − L)`` — γ interpolates vanilla descent
    (γ=0) through SAM (γ=0.5) to sharpness-dominated (γ→1). Implemented as
    an alternating two-phase transform (the optax-contrib SAM pattern):

    - even phase: cache params-point gradient, move to the adversarial
      point ``w + ρ·g/‖g‖`` (base state untouched);
    - odd phase: combine the cached and adversarial gradients into the
      WSAM gradient, step ``base`` with it from the *original* point
      (undoing the ascent offset in the same update).

    Each optimizer "step" therefore consumes two train-loop iterations /
    gradient evaluations, like the reference's closure-based torch impl.
    """
    if not 0.0 <= gamma < 1.0:
        raise ValueError(f"wsam gamma must be in [0, 1), got {gamma}")
    coef = gamma / (1.0 - gamma)

    def init_fn(params):
        return {
            "phase": jnp.zeros([], jnp.int32),
            "grad_cache": jax.tree.map(jnp.zeros_like, params),
            "ascent": jax.tree.map(jnp.zeros_like, params),
            "base": base.init(params),
        }

    def ascent_phase(updates, state, params):
        gnorm = optax.global_norm(updates)
        scale = rho / (gnorm + 1e-12)
        ascent = jax.tree.map(lambda g: g * scale, updates)
        return ascent, {
            "phase": state["phase"] + 1,
            "grad_cache": updates,
            "ascent": ascent,
            "base": state["base"],
        }

    def descent_phase(updates, state, params):
        g_w = jax.tree.map(
            lambda gs, g: g + coef * (gs - g), updates, state["grad_cache"]
        )
        step, base_state = base.update(g_w, state["base"], params)
        # net move: undo the ascent offset, then apply the base step
        out = jax.tree.map(lambda s, a: s - a, step, state["ascent"])
        return out, {
            "phase": state["phase"] + 1,
            "grad_cache": jax.tree.map(jnp.zeros_like, updates),
            "ascent": jax.tree.map(jnp.zeros_like, updates),
            "base": base_state,
        }

    def update_fn(updates, state, params=None):
        return jax.lax.cond(
            state["phase"] % 2 == 0,
            ascent_phase,
            descent_phase,
            updates,
            state,
            params,
        )

    return optax.GradientTransformation(init_fn, update_fn)


def with_grad_sanitizer(
    tx: optax.GradientTransformation, mode: str
) -> optax.GradientTransformation:
    """Chain ``numeric.sanitize_grads(mode)`` IN FRONT of ``tx`` (the
    guard must see the raw gradients, before any clip rescales a spike
    into range).

    Keeps the wrapped optimizer reachable from the ZeRO update-sharding
    path: the sanitizer's state is a scalar counter (which the flat
    probe threads natively), and when ``tx`` advertises a plan-aware
    ``_flat_factory`` it is re-advertised with the same guard chained
    onto the flat stream — "skip"/"zero" act elementwise, so they mean
    the same thing on the packed ``[n_buckets, bucket_elems]`` view.
    """
    from dlrover_tpu.observability.numeric import sanitize_grads

    wrapped = optax.chain(sanitize_grads(mode), tx)
    factory = getattr(tx.init, "_flat_factory", None)
    if factory is not None:
        wrapped.init._flat_factory = lambda plan: optax.chain(
            sanitize_grads(mode), factory(plan)
        )
    return wrapped


def make_optimizer(
    name: str = "adamw",
    learning_rate: float = 3e-4,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
    warmup_steps: int = 100,
    decay_steps: int = 100000,
    schedule: str = "warmup_cosine",
    state_dtype: Optional[str] = None,
    offload_states: bool = False,
    fused: bool = False,
    sanitize_grads: Optional[str] = None,
) -> optax.GradientTransformation:
    """Build the training optimizer.

    ``state_dtype="bfloat16"`` keeps first/second moments in bf16
    (reference: atorch BF16Optimizer); ``"int8"`` uses the block-quantized
    states from ``ops/quant.py`` (reference: low_bit/functional.py);
    ``"mixed8"`` keeps bf16 momentum with int8 variance; ``"factored"``
    keeps bf16 momentum with an Adafactor-factored variance.
    ``offload_states=True`` (adamw only) keeps f32 moments in pinned
    host memory, streamed through HBM one leaf at a time
    (streamed_offload_adamw) — pair with
    ``init_train_state(offload_opt_state=True)``.
    ``fused=True`` (adamw only) folds the global-norm clip, weight
    decay and moment/param updates into one tree traversal
    (``fused_adamw``) — numerically identical to the chain, one read +
    one write per state leaf. Composes with state_dtype
    None/"bfloat16"/"factored" and with ``offload_states`` (the
    streamed walk absorbs the clip).
    ``sanitize_grads`` ("skip"/"zero") chains the non-finite gradient
    guard from ``observability/numeric.py`` in front of everything (see
    ``with_grad_sanitizer``).
    """
    if sanitize_grads is not None:
        return with_grad_sanitizer(
            make_optimizer(
                name=name,
                learning_rate=learning_rate,
                weight_decay=weight_decay,
                b1=b1,
                b2=b2,
                grad_clip=grad_clip,
                warmup_steps=warmup_steps,
                decay_steps=decay_steps,
                schedule=schedule,
                state_dtype=state_dtype,
                offload_states=offload_states,
                fused=fused,
            ),
            sanitize_grads,
        )
    if schedule in ("none", "const", "constant"):
        lr = learning_rate
    else:
        lr = build_schedule(
            schedule, learning_rate, warmup_steps, decay_steps
        )

    if fused and name != "adamw":
        raise ValueError(
            f"fused=True is an adamw fast path; got name={name!r}"
        )
    if fused and state_dtype not in (None, "bfloat16", "factored"):
        raise ValueError(
            "fused=True composes with state_dtype None/'bfloat16'/"
            f"'factored' (got {state_dtype!r}); the int8/int4/mixed "
            "paths already stream their own fused updates"
        )

    chain = []
    if grad_clip and grad_clip > 0 and not fused:
        chain.append(optax.clip_by_global_norm(grad_clip))

    if offload_states:
        if name != "adamw" or state_dtype is not None:
            raise ValueError(
                "offload_states streaming is implemented for plain adamw "
                "(f32 host moments); got name="
                f"{name!r} state_dtype={state_dtype!r}"
            )
        chain.append(
            streamed_offload_adamw(
                lr, b1=b1, b2=b2, weight_decay=weight_decay,
                grad_clip=grad_clip if fused else 0.0,
            )
        )
        return optax.chain(*chain)

    if fused:
        return fused_adamw(
            lr, b1=b1, b2=b2, weight_decay=weight_decay,
            grad_clip=grad_clip or 0.0, state_dtype=state_dtype,
        )

    if name == "adamw" and state_dtype == "factored":
        # Adafactor-factored nu + bf16 momentum (see factored_adamw):
        # ~2.7 GiB of HBM and ~5 GiB/step of bandwidth back at 1.4B
        inner = factored_adamw(
            lr, b1=b1, b2=b2, weight_decay=weight_decay
        )
        chain.append(inner)
        tx = optax.chain(*chain)
        # re-advertise the flat factory through the chain wrapper so the
        # update-sharding probe still sees it; the clip link re-wraps as
        # clip-on-the-flat-stream (same global norm — padding is zero)
        inner_factory = inner.init._flat_factory
        if grad_clip and grad_clip > 0:
            tx.init._flat_factory = lambda plan: optax.chain(
                optax.clip_by_global_norm(grad_clip),
                inner_factory(plan),
            )
        else:
            tx.init._flat_factory = inner_factory
        return tx

    if name == "adamw" and state_dtype in ("mixed8", "mixed4"):
        # bf16 momentum + int8/int4 blockwise variance: frees ~75% of
        # nu's HBM with Adafactor-grade variance fidelity; cheaper per
        # step than bf16 nu (less optimizer bandwidth).
        from dlrover_tpu.ops.quant import mixed_adamw

        chain.append(
            mixed_adamw(
                lr,
                b1=b1,
                b2=b2,
                weight_decay=weight_decay,
                v_bits=8 if state_dtype == "mixed8" else 4,
            )
        )
        return optax.chain(*chain)

    if name == "adamw" and state_dtype in ("int8", "int4"):
        # fused streaming path: chunked dequant-update-requant keeps the
        # float32 working set O(chunk) — the generic wrapper below would
        # materialise full f32 moments every step (OOM at >=1B params)
        from dlrover_tpu.ops.quant import lowbit_adamw

        chain.append(
            lowbit_adamw(
                lr,
                b1=b1,
                b2=b2,
                weight_decay=weight_decay,
                bits=8 if state_dtype == "int8" else 4,
            )
        )
        return optax.chain(*chain)

    if name == "adamw":
        mu_dtype = None
        if state_dtype == "bfloat16":
            mu_dtype = jnp.bfloat16
        chain.append(
            optax.adamw(
                lr, b1=b1, b2=b2, weight_decay=weight_decay, mu_dtype=mu_dtype
            )
        )
    elif name == "adam":
        chain.append(optax.adam(lr, b1=b1, b2=b2))
    elif name == "agd":
        chain.append(agd(lr if callable(lr) else (lambda s: lr), b1=b1, b2=b2))
        if weight_decay:
            chain.append(optax.add_decayed_weights(-weight_decay))
    elif name == "sgd":
        chain.append(optax.sgd(lr, momentum=0.9))
    elif name == "lion":
        chain.append(optax.lion(lr, weight_decay=weight_decay))
    elif name == "wsam":
        chain.append(
            wsam(
                optax.adamw(
                    lr, b1=b1, b2=b2, weight_decay=weight_decay
                )
            )
        )
    else:
        raise ValueError(f"unknown optimizer {name}")

    if state_dtype in ("int8", "int4"):
        if name == "wsam":
            # quantizing wsam's ascent/grad_cache leaves would subtract a
            # lossy ascent from the exact one applied to params, leaking
            # quantization error straight into the weights every 2 steps
            raise ValueError(
                "wsam is incompatible with low-bit optimizer state; use "
                "state_dtype=None or 'bfloat16'"
            )
        from dlrover_tpu.ops.quant import quantize_optimizer_state

        bits = 8 if state_dtype == "int8" else 4
        return quantize_optimizer_state(optax.chain(*chain), bits=bits)
    return optax.chain(*chain)


def opt_state_bytes_per_replica(opt_state) -> int:
    """Bytes of optimizer state ONE data-parallel replica holds.

    Leaves carrying a sharding count only their per-device shard (the
    ZeRO-1 flat moments are ``P(None, "dp")``-sharded, so each replica
    holds 1/dp of them); replicated or host-side leaves count in full.
    Works on live arrays and on ``jax.eval_shape``/abstract states with
    ``.sharding`` attached.
    """
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(opt_state):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and hasattr(sharding, "shard_shape"):
            try:
                shape = sharding.shard_shape(tuple(shape))
            except Exception:
                pass
        total += int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
    return total
