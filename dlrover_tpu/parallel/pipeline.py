"""Pipeline parallelism: collective-permute microbatching over the pp axis.

Reference: atorch's PiPPy-based pipeline
(auto/opt_lib/pipeline_parallel_optimization.py:56, compilers/pipe_compiler/
distributed_pippy_compiler.py) — stage graphs executed over torch RPC with
an interleaved schedule (compilers/pipe_compiler/StageInterleaver.py). None
of that maps to TPU: XLA compiles one SPMD program, so the pipeline here is
the *collective* formulation (scaling-book style): layer parameters are
sharded over the ``pp`` mesh axis, microbatch activations rotate
stage→stage with ``ppermute``, and the whole schedule is a ``lax.scan``
inside one ``shard_map`` that is manual over ``pp`` only — every other
axis (dp/fsdp/tp/ep) stays visible to GSPMD, so FSDP/TP sharding
constraints inside the stage body keep working unchanged.

Schedules:
- GPipe fill-drain (``interleave=1``): M + P − 1 ticks, bubble
  (P−1)/(M+P−1).
- Interleaved / circular (``interleave=v>1``): each device owns v
  NON-ADJACENT layer chunks (virtual stage vs = j·P + s lives on device
  s at local slot j), activations lap the ring v times, M·v + P − 1
  ticks → bubble (P−1)/(M·v+P−1) — the v× bubble cut of the reference's
  StageInterleaver, expressed as one SPMD scan.

Stage-boundary dtype: hops ride at the COMPUTE dtype by default
(``boundary_dtype=None`` → ``x.dtype``) — for a bf16 model that halves
the ICI bytes per hop, and it is numerically free: stage outputs are
already bf16-quantized, so a wider f32 hop would carry the same values.
Sub-32-bit hops move as raw uint16 bits (``_bits_ppermute``) so AD never
differentiates a narrow collective directly. Two XLA:SPMD partitioner
pitfalls shape this code, both manifesting as the "Invalid binary
instruction opcode copy" CHECK crash: (a) differentiating a bf16
``ppermute`` chain (avoided by the bits ride + custom transpose), and
(b) cotangents flowing back through a sub-32-bit microbatch FEED — the
``jnp.where`` select + ``dynamic_index`` transpose over a bf16 ``xs``
(avoided by keeping the feed/select path f32; it is device-local, so
this costs no ICI traffic). Parity:
test_pipeline.py::test_bf16_boundary_matches_f32.

Gradients come from plain ``jax.grad`` through the scan — ``ppermute``'s
transpose is the reverse permute, which *is* the backward pipeline.
"""

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def interleaved_chunk_order(pp: int, v: int) -> np.ndarray:
    """Storage-chunk index applied at each virtual-stage position.

    Layer storage is contiguously sharded over pp: device s holds
    storage chunks [s·v, (s+1)·v). Virtual stage vs = j·P + s runs
    device s's local slot j = storage chunk s·v + j. Every layer-apply
    path (pipelined or not) must use THIS order for the network to be
    the same function on every mesh."""
    return np.array(
        [(vs % pp) * v + (vs // pp) for vs in range(pp * v)], np.int32
    )


def semantic_layer_perm(n_layer: int, pp: int, v: int) -> np.ndarray:
    """Storage-layer indices in semantic (virtual-stage) order."""
    cl = n_layer // (pp * v)
    chunks = interleaved_chunk_order(pp, v)
    return (
        chunks[:, None] * cl + np.arange(cl, dtype=np.int32)[None, :]
    ).reshape(-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _bits_ppermute(x, axis, perm):
    """ppermute that moves raw bits (uintN on the wire).

    Differentiating a bf16 collective chain through the pipeline scan
    crashes XLA ("Invalid binary instruction opcode copy"), which is why
    round 1 paid double ICI bytes upcasting boundaries to f32. Moving
    the SAME bits as uint16 sidesteps the miscompile: AD never sees the
    integer collective (this custom_vjp supplies the transpose — the
    reverse ring permute of the cotangent bits)."""
    return _bits_move(x, axis, perm)


def _bits_move(x, axis, perm):
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return jax.lax.ppermute(x, axis, perm)
    uint = jnp.dtype(f"uint{x.dtype.itemsize * 8}")
    bits = jax.lax.bitcast_convert_type(x, uint)
    moved = jax.lax.ppermute(bits, axis, perm)
    return jax.lax.bitcast_convert_type(moved, x.dtype)


def _bits_ppermute_fwd(x, axis, perm):
    return _bits_move(x, axis, perm), None


def _bits_ppermute_bwd(axis, perm, _, g):
    inv = tuple((dst, src) for (src, dst) in perm)
    return (_bits_move(g, axis, inv),)


_bits_ppermute.defvjp(_bits_ppermute_fwd, _bits_ppermute_bwd)


def pipeline_apply(
    body_fn: Callable,  # (x_mb [b,S,D], layer_tree, pos_mb [b,S]) -> x_mb
    layers: Any,  # pytree, leaves [L, ...] — leading axis sharded over pp
    x: jax.Array,  # [B, S, D]
    positions: jax.Array,  # [B, S]
    mesh: Mesh,
    num_microbatches: Optional[int] = None,
    axis: str = "pp",
    interleave: int = 1,
    boundary_dtype=None,  # stage-hop dtype; None → compute (x.dtype)
) -> jax.Array:
    """Run the layer stack as a pp-stage pipeline; returns [B, S, D].

    Each pp rank owns a contiguous storage block of L/pp layers, split
    into ``interleave`` chunks (see ``interleaved_chunk_order``). Stage 0
    feeds a new microbatch every tick of its free slots; activations hop
    one stage per tick over ICI, wrapping pp−1 → 0 between laps.
    """
    pp = mesh.shape[axis]
    if pp == 1:
        raise ValueError("pipeline_apply requires a pp axis > 1")
    v = max(1, int(interleave))
    b_global = x.shape[0]
    m = num_microbatches or pp
    if b_global % m:
        raise ValueError(
            f"global batch {b_global} not divisible by {m} microbatches"
        )
    if v > 1 and m % pp:
        raise ValueError(
            f"interleaved schedule needs microbatches ({m}) divisible "
            f"by pp ({pp})"
        )

    compute_dtype = x.dtype
    bdt = jnp.dtype(boundary_dtype or compute_dtype)

    def local(stage_ids, layers_blk, x_all, pos_all):
        # own pp rank via a pp-sharded iota input rather than
        # lax.axis_index: partial-manual shard_map on jax 0.4.x lowers
        # axis_index to a PartitionId the SPMD partitioner rejects
        stage = stage_ids[0]

        # Split batch into microbatches WITHOUT concentrating a microbatch
        # on one dp/fsdp shard: reshape so the (auto-)sharded row dim stays
        # outermost within each microbatch.
        def to_mb(t):
            r = t.reshape((b_global // m, m) + t.shape[1:])
            return r.swapaxes(0, 1)  # [M, B/M, ...]

        xs, pos = to_mb(x_all), to_mb(pos_all)

        # local storage block [L/pp, ...] → v chunks [v, cl, ...]
        def to_chunks(t):
            return t.reshape((v, t.shape[0] // v) + t.shape[1:])

        chunks = jax.tree.map(to_chunks, layers_blk)

        def stage_apply(act, p, chunk_idx):
            blk = jax.tree.map(
                lambda t: jax.lax.dynamic_index_in_dim(
                    t, chunk_idx, 0, keepdims=False
                ),
                chunks,
            )

            def scan_body(c, layer):
                return body_fn(c, layer, p), None

            out, _ = jax.lax.scan(
                scan_body, act.astype(compute_dtype), blk
            )
            return out.astype(bdt)

        # interleaved: wraparound ring — stage pp-1 feeds stage 0 for
        # the next lap. Fill-drain (v=1) has no next lap, so it keeps
        # the edge-less perm: the wrap hop would ship a full microbatch
        # every tick only for stage 0 to discard it (and that edge can
        # cross DCN on a multi-slice mesh).
        if v > 1:
            perm = tuple((i, (i + 1) % pp) for i in range(pp))
        else:
            perm = tuple((i, i + 1) for i in range(pp - 1))

        def step(carry, t):
            buf, outs = carry
            # stream position u: stage s at tick t works on the item its
            # predecessor handled at t-1. m/j derivation (P | M groups):
            #   m = (u // (P·v))·P + u mod P      (microbatch)
            #   j = (u mod (P·v)) // P            (lap / local chunk)
            u = t - stage
            mb = jnp.clip(
                (u // (pp * v)) * pp + jax.lax.rem(u, pp), 0, m - 1
            )
            j = jnp.clip(jax.lax.rem(u, pp * v) // pp, 0, v - 1)
            active = (u >= 0) & (u < m * v)
            inp = jax.lax.dynamic_index_in_dim(xs, mb, 0, keepdims=False)
            p_cur = jax.lax.dynamic_index_in_dim(
                pos, mb, 0, keepdims=False
            )
            # the select runs in f32 regardless of boundary dtype: the
            # cotangent flowing back through a sub-32-bit xs feed (the
            # where transpose + dynamic_update accumulation) is what
            # trips XLA:SPMD's "Invalid binary instruction opcode copy"
            # check — only the ppermute hop itself needs to be narrow
            cur = jnp.where(
                (stage == 0) & (j == 0), inp, buf.astype(jnp.float32)
            )
            out = stage_apply(cur, p_cur, j)
            outs_upd = jax.lax.dynamic_update_index_in_dim(
                outs, out.astype(jnp.float32), mb, 0
            )
            outs = jnp.where(
                (stage == pp - 1) & (j == v - 1) & active, outs_upd, outs
            )
            # f32 hops use the plain collective (known-good); narrower
            # ones ride as bits so AD sees only this custom transpose
            if bdt.itemsize < 4:
                buf = _bits_ppermute(out, axis, perm)
            else:
                buf = jax.lax.ppermute(out, axis, perm)
            return (buf, outs), None

        init = (
            jnp.zeros(xs.shape[1:], bdt),
            jnp.zeros(xs.shape, jnp.float32),
        )
        # the carry varies over pp from the first tick on
        init = jax.lax.pcast(init, (axis,), to="varying")
        (_, outs), _ = jax.lax.scan(
            step, init, jnp.arange(m * v + pp - 1)
        )
        # results accumulate on the last stage only; psum replicates them
        # back across pp (zeros elsewhere contribute nothing). f32: the
        # sum is exact regardless of stage count.
        outs = jax.lax.psum(outs, axis)
        return outs.swapaxes(0, 1).reshape(x_all.shape)

    layer_specs = jax.tree.map(lambda _: P(axis), layers)
    out = jax.shard_map(
        local,
        mesh=mesh,
        axis_names={axis},
        in_specs=(P(axis), layer_specs, P(), P()),
        out_specs=P(),
    )(
        jnp.arange(pp, dtype=jnp.int32),
        layers,
        x.astype(jnp.float32),
        positions,
    )
    return out.astype(compute_dtype)


def pipeline_bubble_fraction(
    pp: int, num_microbatches: int, interleave: int = 1
) -> float:
    """Idle fraction of the schedule: (P−1)/(M·v + P−1)."""
    if pp <= 1:
        return 0.0
    return (pp - 1) / (num_microbatches * max(1, interleave) + pp - 1)


def validate_pipeline_config(cfg, mesh_cfg) -> None:
    """Raise early on configs the pipeline cannot run."""
    pp = mesh_cfg.pp
    if pp <= 1:
        return
    v = max(1, getattr(cfg, "pp_interleave", 1))
    if cfg.n_layer % (pp * v):
        raise ValueError(
            f"n_layer={cfg.n_layer} not divisible by pp·interleave="
            f"{pp}·{v}"
        )
    if v > 1:
        m = cfg.pp_microbatches or pp
        if m % pp:
            raise ValueError(
                f"pp_interleave={v} needs pp_microbatches ({m}) "
                f"divisible by pp ({pp})"
            )
        stages = getattr(cfg, "pp_stages", 0)
        if stages and stages != pp:
            raise ValueError(
                f"cfg.pp_stages={stages} does not match mesh pp={pp}: "
                "the interleaved layer order depends on the stage count, "
                "so the checkpoint would be a different network"
            )
    if mesh_cfg.sp > 1:
        raise ValueError(
            "pp>1 with sp>1 is unsupported: sequence-parallel attention "
            "uses its own shard_map which cannot nest under the pipeline's "
            "manual pp region"
        )
    if getattr(cfg, "n_experts", 0) > 0:
        if getattr(cfg, "moe_alltoall", False) and mesh_cfg.ep > 1:
            raise ValueError(
                "pp>1 with moe_alltoall is unsupported: the explicit "
                "all-to-all dispatch is a shard_map which cannot nest "
                "under the pipeline's manual pp region; use the dense "
                "einsum dispatch (moe_alltoall=False)"
            )
        if (
            getattr(cfg, "moe_aux_coef", 0.0)
            or getattr(cfg, "moe_z_coef", 0.0)
            or getattr(cfg, "moe_jitter", 0.0)
        ):
            raise ValueError(
                "pp>1 does not collect MoE router aux losses (or jitter "
                "rng) across pipeline stages; set moe_aux_coef, "
                "moe_z_coef and moe_jitter to 0 under pipeline "
                "parallelism"
            )
