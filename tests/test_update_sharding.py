"""ZeRO-1 weight-update sharding: HLO guard, parity rollouts, gates.

The contract under test (train/train_step.py resolve_update_sharding +
parallel/sharding.py exchange path):

- Gradients leave the backward as bucketed reduce-scatters (f32 wire)
  or all-to-alls (bf16/int8 wire) — never as a full-gradient
  all-reduce. Small scalar all-reduces (loss psum, denom) are fine.
- The optimizer steps a ``[n_buckets, bucket_elems/dp]`` shard per
  rank, so state bytes per replica drop by ~dp (plus bucket padding).
- On the f32 wire the whole rollout is BITWISE identical to the
  replicated update for the untied-embedding configs: the manual
  apply region pins the ``-lr*y`` mult → ``p+u`` add adjacency the
  XLA:CPU contraction pass otherwise splits across the all-gather.

Known 1-ulp-origin codegen artifacts (pinned by tolerance, not
bitwise — each traced to a fusion-boundary difference, measured over
6 steps on the tiny f32 config):

- tie_embeddings: the replicated baseline inlines the lookup+head
  cotangent add into the embedding's nu (variance) fusion; sharded
  can't. Embedding nu diverges by 1 ulp from step 1 (worst param rel
  ~2.5e-3 by step 6; losses agree to ~1e-6).
- grad_accum > 1: the per-microbatch scatter-add into the embedding
  grad rounds differently under the scan (~1e-3 worst rel).
- grad_clip chains: global_norm sums flat buckets vs per-leaf trees
  in different orders (~6e-3 worst rel, dloss ~5e-7).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models.config import get_config
from dlrover_tpu.parallel import sharding as shd
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, single_device_mesh
from dlrover_tpu.train.optimizer import make_optimizer, opt_state_bytes_per_replica
from dlrover_tpu.train.train_step import (
    TrainStepBuilder,
    init_train_state,
    resolve_update_sharding,
)

DP = 8


def tiny_cfg(**kw):
    kw.setdefault("dtype", "float32")
    return get_config(
        "tiny",
        n_layer=2,
        d_model=64,
        d_ff=128,
        n_head=4,
        vocab_size=128,
        max_seq=32,
        **kw,
    )


def dp_mesh():
    return build_mesh(MeshConfig(dp=-1))


def comm_cfg(**kw):
    kw.setdefault("bucket_mb", 0.05)
    return shd.CommConfig(update_sharding=True, **kw)


def batches(n, batch=16, vocab=128, seed=0):
    rng = np.random.RandomState(seed)
    for _ in range(n):
        base = rng.randint(0, vocab, size=(batch, 33))
        yield {
            "tokens": jnp.asarray(base[:, :-1], jnp.int32),
            "targets": jnp.asarray(base[:, 1:], jnp.int32),
        }


def rollout_pair(cfg, opt_fn, comm, steps=6, batch=16, accum=1):
    """Run replicated and sharded builders in lockstep; return final
    (state_u, state_s, metrics_u, metrics_s)."""
    mesh = dp_mesh()
    bu = TrainStepBuilder(cfg, mesh, opt_fn(), grad_accum=accum)
    bs = TrainStepBuilder(cfg, mesh, opt_fn(), grad_accum=accum, comm=comm)
    assert bs.update_sharding, bs.update_sharding_reason
    su = init_train_state(jax.random.key(0), cfg, mesh, bu.optimizer)
    ss = init_train_state(
        jax.random.key(0), cfg, mesh, bs.optimizer, comm=bs.comm_resolved
    )
    fu = jax.jit(bu.step_fn)
    fs = jax.jit(bs.step_fn)
    mu = ms = None
    for b in batches(steps, batch=batch):
        su, mu = fu(su, b)
        ss, ms = fs(ss, b)
    return su, ss, mu, ms


def params_worst_rel(pu, ps, floor=1e-30):
    """Worst elementwise |x-y|/max(|x|, floor) over the tree. The
    default floor makes this a pure relative error (right for the
    1-ulp-origin artifacts, whose error scales with the value); lossy
    wires pass a floor near the weight scale so near-zero params don't
    dominate the ratio."""
    worst = 0.0
    for x, y in zip(jax.tree.leaves(pu), jax.tree.leaves(ps)):
        x, y = np.asarray(x), np.asarray(y)
        worst = max(
            worst,
            float(np.max(np.abs(x - y) / np.maximum(np.abs(x), floor))),
        )
    return worst


# ---------------------------------------------------------------------------
# Gates: unsupported combinations fall back with a recorded reason
# ---------------------------------------------------------------------------


def test_gate_dp1_falls_back():
    cfg = tiny_cfg()
    active, reason, plan = resolve_update_sharding(
        cfg, single_device_mesh(), optax.adamw(1e-3), comm_cfg()
    )
    assert not active and plan is None
    assert "dp" in reason


def test_gate_non_dp_axes():
    """Axes beyond fsdp/tp (here pp) still refuse — the exchange is
    only defined over dp with fsdp/tp left to the auto partitioner."""
    cfg = tiny_cfg()
    mesh = build_mesh(MeshConfig(dp=-1, pp=2))
    active, reason, _ = resolve_update_sharding(
        cfg, mesh, optax.adamw(1e-3), comm_cfg()
    )
    assert not active
    assert "non-dp" in reason


def test_gate_hybrid_meshes_activate():
    """dp×tp and dp×fsdp are in the zoo now: the resolve must come back
    active with the mesh axes recorded on the plan (the partial-manual
    region and the resharding refusal both key off mesh_axes)."""
    cfg = tiny_cfg()
    for kw, axes in (
        ({"tp": 2}, ("dp", "tp")),
        ({"fsdp": 2}, ("dp", "fsdp")),
    ):
        mesh = build_mesh(MeshConfig(dp=-1, **kw))
        active, reason, plan = resolve_update_sharding(
            cfg, mesh, optax.adamw(1e-3), comm_cfg()
        )
        assert active, reason
        assert plan.mesh_axes == axes
        assert plan.dp == mesh.shape["dp"]


def test_gate_hybrid_mesh_quantized_wire_falls_back():
    """bf16/int8 wires ride all_to_all, which cannot lower inside the
    partial-manual region — hybrid meshes must refuse, pure-dp keeps
    working."""
    cfg = tiny_cfg()
    mesh = build_mesh(MeshConfig(dp=-1, tp=2))
    active, reason, _ = resolve_update_sharding(
        cfg, mesh, optax.adamw(1e-3), comm_cfg(wire_dtype="bfloat16")
    )
    assert not active
    assert "wire" in reason or "pure-dp" in reason
    active, reason, _ = resolve_update_sharding(
        cfg, dp_mesh(), optax.adamw(1e-3), comm_cfg(wire_dtype="bfloat16")
    )
    assert active, reason


def test_gate_hybrid_mesh_fp8_falls_back():
    """fp8 delayed-scaling state threads the pure-dp manual region
    only; on a hybrid mesh the resolve refuses rather than dropping the
    scaling state."""
    cfg = tiny_cfg(fp8=True)
    mesh = build_mesh(MeshConfig(dp=-1, tp=2))
    active, reason, _ = resolve_update_sharding(
        cfg, mesh, optax.adamw(1e-3), comm_cfg()
    )
    assert not active
    assert "fp8" in reason


def test_update_mode_semantics():
    """CommConfig mode strings: False=off, "zero1"=deferred exchange,
    "zero2"=per-microbatch scatter, True=legacy alias for zero2."""
    assert shd.CommConfig().update_mode == ""
    assert shd.CommConfig(update_sharding="zero1").update_mode == "zero1"
    assert shd.CommConfig(update_sharding="zero2").update_mode == "zero2"
    assert shd.CommConfig(update_sharding=True).update_mode == "zero2"
    with pytest.raises(ValueError):
        shd.CommConfig(update_sharding="zero3")


def test_gate_offload_and_custom_loss():
    cfg = tiny_cfg()
    mesh = dp_mesh()
    active, reason, _ = resolve_update_sharding(
        cfg, mesh, optax.adamw(1e-3), comm_cfg(), offload_opt_state=True
    )
    assert not active and "offload" in reason
    active, reason, _ = resolve_update_sharding(
        cfg, mesh, optax.adamw(1e-3), comm_cfg(), loss_fn=lambda *a: 0.0
    )
    assert not active and "loss_fn" in reason


def test_gate_factored_optimizer_rejected():
    """adafactor's state is row/col-factored — a flat-offset shard of
    it is meaningless, so the optimizer probe must refuse."""
    cfg = tiny_cfg()
    active, reason, _ = resolve_update_sharding(
        cfg, dp_mesh(), optax.adafactor(1e-3), comm_cfg()
    )
    assert not active
    assert reason


def test_builder_falls_back_not_fails():
    """An unsupported combo builds a working replicated step."""
    cfg = tiny_cfg(n_experts=2)
    b = TrainStepBuilder(cfg, dp_mesh(), optax.adamw(1e-3), comm=comm_cfg())
    assert not b.update_sharding
    assert b.comm_resolved is None
    assert "MoE" in b.update_sharding_reason


# ---------------------------------------------------------------------------
# Wire format roundtrips
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize(
    # 0.008 MB rounds up to 4096-element buckets, most rows inside one
    # leaf; 0.05 MB to 14336, most rows straddling leaves
    "bucket_mb", [0.008, 0.05], ids=["rows-in-leaves", "rows-straddle"]
)
def test_stream_values_and_coordinates(tied, bucket_mb):
    """Every spelling of the stream holds the same values at the same
    coordinates as a plain concatenation of the leaves, bit for bit:
    the whole stream, each rank's shard packed alone, the tied head's
    own buckets, and back again through the gather."""
    cfg = tiny_cfg(tie_embeddings=tied)
    mesh = dp_mesh()
    b = TrainStepBuilder(
        cfg, mesh, optax.adamw(1e-3), comm=comm_cfg(bucket_mb=bucket_mb)
    )
    plan = b._plan
    nb, e, q = plan.n_buckets, plan.bucket_elems, plan.bucket_elems // DP
    state = init_train_state(jax.random.key(0), cfg, mesh, optax.adamw(1e-3))
    params = state["params"]
    leaves = [np.asarray(x).reshape(-1) for x in jax.tree.leaves(params)]
    want = np.zeros(plan.padded, np.float32)
    want[: plan.total] = np.concatenate(leaves)
    want = want.reshape(nb, e)
    inside = sum(
        any(o <= i * e and (i + 1) * e <= o + n
            for o, n in zip(plan.offsets, plan.sizes))
        for i in range(nb)
    )
    assert 0 < inside < nb  # both kinds of row are here

    flat = shd.pack_flat(params, plan)
    np.testing.assert_array_equal(np.asarray(flat), want)
    np.testing.assert_array_equal(
        np.stack([np.asarray(r) for r in shd.pack_buckets(params, plan)]),
        want,
    )
    shard = jax.jit(lambda p, i: shd.pack_shard(p, plan, i))
    for r in range(DP):
        np.testing.assert_array_equal(
            np.asarray(shard(params, jnp.int32(r))),
            want[:, r * q : (r + 1) * q],
        )
    if tied:
        rows = shd.pack_buckets([params["embed"]["tokens"]], plan,
                                plan.n_tie_buckets)
        tie = np.zeros(plan.n_tie_buckets * e, np.float32)
        tie[: plan.tie_size] = leaves[0]
        np.testing.assert_array_equal(
            np.stack([np.asarray(r) for r in rows]),
            tie.reshape(plan.n_tie_buckets, e),
        )

    gathered = jax.shard_map(
        lambda s: shd.gather_stream(s, "dp"),
        mesh=mesh,
        in_specs=jax.sharding.PartitionSpec(None, "dp"),
        out_specs=jax.sharding.PartitionSpec(),
        check_vma=False,
    )(flat)
    assert gathered.shape == (nb, e // 128, 128)
    np.testing.assert_array_equal(np.asarray(gathered).reshape(nb, e), want)
    for stream in (flat, gathered):
        back = shd.unpack_flat(stream, params, plan)
        for x, y in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
            assert x.shape == y.shape and x.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# HLO guard + state bytes (one compile, several assertions)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def compiled_sharded():
    cfg = tiny_cfg(tie_embeddings=False)
    mesh = dp_mesh()
    comm = comm_cfg()
    b = TrainStepBuilder(cfg, mesh, optax.adamw(1e-3), comm=comm)
    assert b.update_sharding, b.update_sharding_reason
    state = init_train_state(
        jax.random.key(0), cfg, mesh, b.optimizer, comm=b.comm_resolved
    )
    batch = next(batches(1))
    compiled = jax.jit(b.step_fn).lower(state, batch).compile()
    return cfg, comm, b, state, compiled


def test_hlo_has_rs_and_ag(compiled_sharded):
    from dlrover_tpu.observability.runtime_timer import collective_stats

    _, _, _, _, compiled = compiled_sharded
    stats = collective_stats(compiled.as_text())
    counts = stats["counts"]
    assert counts.get("reduce-scatter", 0) > 0, counts
    assert counts.get("all-gather", 0) > 0, counts


def test_hlo_no_full_gradient_all_reduce(compiled_sharded):
    """Every all-reduce left in the program must be a small scalar-ish
    reduction (loss, denom) — the gradient payload rides the
    reduce-scatters. Guard: no f32 all-reduce result within 2x of the
    total parameter count."""
    cfg, _, b, _, compiled = compiled_sharded
    n_params = b._plan.total
    for line in compiled.as_text().splitlines():
        if " = " not in line:
            continue
        rhs = line.split(" = ", 1)[1]
        if "all-reduce(" not in rhs:
            continue
        head = rhs.split("all-reduce(", 1)[0]
        elems = sum(
            int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
            for _, dims in re.findall(r"(f32|bf16)\[([0-9,]*)\]", head)
        )
        assert elems < n_params // 2, (
            f"full-gradient-sized all-reduce survived: {line.strip()[:160]}"
        )


def test_opt_state_bytes_per_replica(compiled_sharded):
    cfg, comm, b, state, _ = compiled_sharded
    mesh = dp_mesh()
    full_state = init_train_state(jax.random.key(0), cfg, mesh, optax.adamw(1e-3))
    full = opt_state_bytes_per_replica(full_state["opt_state"])
    rep = opt_state_bytes_per_replica(state["opt_state"])
    assert rep <= full / DP + 3 * comm.bucket_bytes, (rep, full)


def test_sharded_step_loss_matches_replicated(compiled_sharded):
    cfg, _, b, state, compiled = compiled_sharded
    mesh = dp_mesh()
    bu = TrainStepBuilder(cfg, mesh, optax.adamw(1e-3))
    su = init_train_state(jax.random.key(0), cfg, mesh, bu.optimizer)
    batch = next(batches(1))
    _, mu = jax.jit(bu.step_fn)(su, batch)
    _, ms = compiled(state, batch)
    assert abs(float(mu["loss"]) - float(ms["loss"])) < 1e-6


# ---------------------------------------------------------------------------
# Parity rollouts (slow: each compiles two step programs and runs 6 steps)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_bitwise_f32_wire_untied():
    """The acceptance bar: f32-wire training is bitwise identical to
    the replicated update over a multi-step rollout."""
    su, ss, mu, ms = rollout_pair(
        tiny_cfg(tie_embeddings=False), lambda: optax.adamw(1e-3), comm_cfg()
    )
    for x, y in zip(jax.tree.leaves(su["params"]), jax.tree.leaves(ss["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert float(mu["loss"]) == float(ms["loss"])


@pytest.mark.slow
def test_fused_adamw_composes_tied():
    """fused_adamw path composes with update sharding; tied embeddings
    carry the usual nu-fusion artifact so this pins a tight tolerance
    rather than bitwise (~5e-5 rel measured on the embedding)."""
    su, ss, mu, ms = rollout_pair(
        tiny_cfg(),
        lambda: make_optimizer(
            learning_rate=1e-3, warmup_steps=2, decay_steps=10,
            grad_clip=0.0, fused=True,
        ),
        comm_cfg(),
    )
    assert params_worst_rel(su["params"], ss["params"]) < 1e-3
    assert abs(float(mu["loss"]) - float(ms["loss"])) < 1e-6


@pytest.mark.slow
@pytest.mark.parametrize(
    "name,cfg_kw,accum,batch,tol",
    [
        # tied: baseline inlines the tied-cotangent add into embed's nu
        # fusion; 1 ulp at step 1 compounds to ~2.5e-3 by step 6.
        ("tied", {}, 1, 16, 1e-2),
        # accum: per-microbatch embed scatter-add rounds differently
        # under the scan (~9e-4 measured).
        ("accum4-untied", {"tie_embeddings": False}, 4, 32, 5e-3),
        ("accum2-tied", {}, 2, 32, 1e-2),
    ],
)
def test_tolerance_pinned_adamw(name, cfg_kw, accum, batch, tol):
    su, ss, mu, ms = rollout_pair(
        tiny_cfg(**cfg_kw), lambda: optax.adamw(1e-3), comm_cfg(),
        accum=accum, batch=batch,
    )
    assert params_worst_rel(su["params"], ss["params"]) < tol
    assert abs(float(mu["loss"]) - float(ms["loss"])) < 1e-5


@pytest.mark.slow
def test_tolerance_pinned_clip_chain():
    """grad_clip>0: global_norm sums flat buckets vs per-leaf trees in
    different orders (~6e-3 worst rel measured, dloss ~5e-7)."""
    su, ss, mu, ms = rollout_pair(
        tiny_cfg(tie_embeddings=False),
        lambda: make_optimizer(
            learning_rate=1e-3, warmup_steps=2, decay_steps=10, grad_clip=1.0
        ),
        comm_cfg(),
    )
    assert params_worst_rel(su["params"], ss["params"]) < 3e-2
    assert abs(float(mu["loss"]) - float(ms["loss"])) < 1e-5


@pytest.mark.slow
@pytest.mark.parametrize(
    "wire,param_tol,loss_tol",
    [("bfloat16", 0.02, 1e-3), ("int8", 0.05, 5e-3)],
)
def test_tolerance_pinned_quantized_wire(wire, param_tol, loss_tol):
    """Lossy wires trade gradient precision for bytes; the rollout must
    stay close. Drift is pinned as per-leaf relative RMS — individual
    near-zero params wander by the quantization step size (expected),
    but the aggregate divergence from the f32 trajectory stays small
    (blockwise scales bound the per-bucket error)."""
    su, ss, mu, ms = rollout_pair(
        tiny_cfg(tie_embeddings=False),
        lambda: optax.adamw(1e-3),
        comm_cfg(wire_dtype=wire),
    )
    worst = 0.0
    for x, y in zip(
        jax.tree.leaves(su["params"]), jax.tree.leaves(ss["params"])
    ):
        x, y = np.asarray(x), np.asarray(y)
        worst = max(
            worst,
            float(
                np.sqrt(np.mean((x - y) ** 2) / (np.mean(x**2) + 1e-30))
            ),
        )
    assert worst < param_tol
    assert abs(float(mu["loss"]) - float(ms["loss"])) < loss_tol


@pytest.mark.slow
def test_block_fn_composes():
    """block_k>1 scans step_fn; the dispatch to the sharded step must
    survive the scan (state layout is the fixed point)."""
    cfg = tiny_cfg(tie_embeddings=False)
    mesh = dp_mesh()
    b = TrainStepBuilder(cfg, mesh, optax.adamw(1e-3), comm=comm_cfg())
    assert b.update_sharding
    state = init_train_state(
        jax.random.key(0), cfg, mesh, b.optimizer, comm=b.comm_resolved
    )
    bs = list(batches(2))
    block = {
        k: jnp.stack([b2[k] for b2 in bs]) for k in bs[0]
    }
    state, metrics = jax.jit(b.block_fn)(state, block)
    assert np.isfinite(np.asarray(metrics["loss"])).all()
