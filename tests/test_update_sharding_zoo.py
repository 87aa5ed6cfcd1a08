"""Update sharding across the mesh zoo: dp×fsdp / dp×tp, zero1/zero2.

The contract under test (train/train_step.py resolve_update_sharding on
hybrid meshes + parallel/sharding.py partial-manual exchange):

- On a dp×fsdp or dp×tp mesh the gradient exchange is manual over dp
  ONLY — fsdp/tp stay with the auto partitioner. The flat optimizer
  state is sharded over dp and replicated over the model axes, so the
  bucket collectives must be reduce-scatter/all-gather with replica
  groups of size dp, never spanning the model axis, and no
  full-gradient all-reduce may survive.
- ``zero2`` reduce-scatters every microbatch and accumulates the 1/dp
  shard — the per-microbatch scatter count in the HLO is the
  structural witness that no full-gradient accumulator crosses the
  grad-accum loop. ``zero1`` defers to one scatter per step.
- SGD one-step parity is the scaling guard: SGD is linear in the
  gradients, so a uniform wrong factor (the class of bug Adam's
  normalizer hides) shows up at full size.

Tolerances are pinned from measured runs on this backend: hybrid-mesh
rollouts are NOT bitwise (the auto partitioner fuses the model-axis
collectives differently than the replicated program — 1-ulp origins
that compound through Adam's low-bit amplification), but losses track
to ~1e-5 and one SGD step to ~1e-6.

Everything here builds multi-axis meshes over the 8 virtual devices
and compiles multiple SPMD programs — the whole module is slow-marked
(see test_marker_lint's mesh-zoo rule).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models.config import get_config
from dlrover_tpu.observability.runtime_timer import collective_stats
from dlrover_tpu.parallel import sharding as shd
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.train.optimizer import (
    make_optimizer,
    opt_state_bytes_per_replica,
)
from dlrover_tpu.train.train_step import TrainStepBuilder, init_train_state

P = jax.sharding.PartitionSpec

pytestmark = pytest.mark.slow


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


def zoo_mesh(axis, size=2):
    return build_mesh(MeshConfig(dp=-1, **{axis: size}))


def batches(n, batch=16, vocab=128, seed=0):
    rng = np.random.RandomState(seed)
    for _ in range(n):
        base = rng.randint(0, vocab, size=(batch, 33))
        yield {
            "tokens": jnp.asarray(base[:, :-1], jnp.int32),
            "targets": jnp.asarray(base[:, 1:], jnp.int32),
        }


def build_pair(cfg, mesh, opt_fn, mode, accum=1, **comm_kw):
    comm_kw.setdefault("bucket_mb", 0.05)
    comm = shd.CommConfig(update_sharding=mode, **comm_kw)
    bu = TrainStepBuilder(cfg, mesh, opt_fn(), grad_accum=accum)
    bs = TrainStepBuilder(cfg, mesh, opt_fn(), grad_accum=accum, comm=comm)
    assert bs.update_sharding, bs.update_sharding_reason
    su = init_train_state(jax.random.key(0), cfg, mesh, bu.optimizer)
    ss = init_train_state(
        jax.random.key(0), cfg, mesh, bs.optimizer, comm=bs.comm_resolved
    )
    return bu, bs, su, ss


# ---------------------------------------------------------------------------
# Numerics: SGD one-step scaling guard + adamw loss tracking
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "axis,mode,accum",
    [
        ("tp", "zero2", 2),
        ("tp", "zero1", 2),
        ("fsdp", "zero2", 2),
        ("fsdp", "zero1", 1),
    ],
)
def test_sgd_one_step_parity(axis, mode, accum):
    """One SGD step matches the replicated update to float rounding.

    SGD is linear in the gradient: a wrong uniform factor on the
    exchanged gradients (the bug class Adam's 1/sqrt(nu) normalizer
    conceals) would shift every parameter proportionally. Measured
    worst abs diff ~1.2e-7 on this backend."""
    cfg = tiny_cfg(tie_embeddings=False)
    mesh = zoo_mesh(axis)
    bu, bs, su, ss = build_pair(
        cfg, mesh, lambda: optax.sgd(1e-2), mode, accum=accum
    )
    batch = next(batches(1, batch=16 * accum))
    su, mu = jax.jit(bu.step_fn)(su, batch)
    ss, ms = jax.jit(bs.step_fn)(ss, batch)
    worst = max(
        float(jnp.max(jnp.abs(x - y)))
        for x, y in zip(
            jax.tree.leaves(su["params"]), jax.tree.leaves(ss["params"])
        )
    )
    assert worst < 1e-5, worst
    assert abs(float(mu["loss"]) - float(ms["loss"])) < 1e-6


@pytest.mark.parametrize("axis", ["tp", "fsdp"])
def test_adamw_rollout_losses_track(axis):
    """3-step adamw rollout: per-step losses agree with the replicated
    update. Params drift by low-bit amplification (Adam divides 1-ulp
    nu differences into the update), so the pin is on the losses."""
    cfg = tiny_cfg(tie_embeddings=False)
    mesh = zoo_mesh(axis)
    bu, bs, su, ss = build_pair(
        cfg, mesh, lambda: optax.adamw(1e-3), "zero2"
    )
    fu, fs = jax.jit(bu.step_fn), jax.jit(bs.step_fn)
    for b in batches(3):
        su, mu = fu(su, b)
        ss, ms = fs(ss, b)
        assert abs(float(mu["loss"]) - float(ms["loss"])) < 1e-5


@pytest.mark.parametrize(
    "state_dtype,tol",
    [("bfloat16", 5e-2), ("factored", 5e-2)],
)
def test_low_precision_state_shards(state_dtype, tol):
    """bf16 and row/col-factored optimizer state thread the flat view
    on a hybrid mesh: the builder must activate (not fall back) and the
    rollout must track the same-optimizer replicated run."""
    cfg = tiny_cfg(tie_embeddings=False)
    mesh = zoo_mesh("tp")
    opt_fn = lambda: make_optimizer(  # noqa: E731
        learning_rate=1e-3, warmup_steps=2, decay_steps=10,
        grad_clip=0.0, fused=True, state_dtype=state_dtype,
    )
    bu, bs, su, ss = build_pair(cfg, mesh, opt_fn, "zero1")
    fu, fs = jax.jit(bu.step_fn), jax.jit(bs.step_fn)
    for b in batches(3):
        su, mu = fu(su, b)
        ss, ms = fs(ss, b)
    assert abs(float(mu["loss"]) - float(ms["loss"])) < 1e-3
    worst = 0.0
    for x, y in zip(
        jax.tree.leaves(su["params"]), jax.tree.leaves(ss["params"])
    ):
        x, y = np.asarray(x), np.asarray(y)
        worst = max(
            worst,
            float(np.sqrt(np.mean((x - y) ** 2) / (np.mean(x**2) + 1e-30))),
        )
    assert worst < tol, worst


# ---------------------------------------------------------------------------
# HLO guards: dp-only collectives, no full-grad all-reduce, zero2 scatters
# ---------------------------------------------------------------------------


_COLL_RE = re.compile(
    r"(f32|bf16|s8|u8)\[([0-9,]*)\][^=]*"
    r"(reduce-scatter|all-gather|all-reduce|all-to-all|collective-permute)"
    r"\(.*?replica_groups=\{?\{([0-9,]+)\}"
)


def hlo_collectives(text):
    """(op, out_elems, group_size) for each collective in the HLO."""
    out = []
    for line in text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        elems = int(np.prod(dims)) if dims else 1
        group = len(m.group(4).split(","))
        out.append((m.group(3), elems, group))
    return out


@pytest.fixture(scope="module")
def compiled_dpxfsdp():
    cfg = tiny_cfg(tie_embeddings=False)
    mesh = zoo_mesh("fsdp")
    bu, bs, su, ss = build_pair(
        cfg, mesh, lambda: optax.adamw(1e-3), "zero1"
    )
    batch = next(batches(1))
    compiled = jax.jit(bs.step_fn).lower(ss, batch).compile()
    return mesh, bs, ss, compiled


def test_dpxfsdp_exchange_is_dp_only(compiled_dpxfsdp):
    """The bucket exchange lowers to reduce-scatter/all-gather with
    replica groups of exactly dp ranks — never the model axis, never
    the whole mesh — and no all-to-all sneaks in."""
    mesh, bs, _, compiled = compiled_dpxfsdp
    dp = mesh.shape["dp"]
    plan = bs._plan
    colls = hlo_collectives(compiled.as_text())
    assert colls, "no collectives parsed from HLO"
    # all-to-alls with fsdp-sized groups are the auto partitioner
    # resharding activations — fine. Over dp-sized groups they would
    # mean a quantized wire leaked into the hybrid region.
    assert not [c for c in colls if c[0] == "all-to-all" and c[2] == dp]
    shard_elems = plan.bucket_elems // dp
    rs_buckets = [
        c for c in colls if c[0] == "reduce-scatter"
        and c[1] % shard_elems == 0
    ]
    assert len(rs_buckets) >= plan.n_buckets, colls
    for op, elems, group in rs_buckets:
        assert group == dp, (op, elems, group)
    # the updated flat params come home through dp-group all-gathers of
    # bucket-stream shapes (fsdp-group gathers are the model's own
    # param gathers, not the exchange)
    ag_buckets = [
        c for c in colls if c[0] == "all-gather" and c[2] == dp
    ]
    assert ag_buckets, colls
    assert all(e % shard_elems == 0 for _, e, _ in ag_buckets), ag_buckets
    # and the ONLY dp-group traffic is the flat bucket stream: every
    # dp-group collective is stream-shaped, so no per-leaf gradient or
    # param payload crosses dp outside the exchange
    for op, elems, group in colls:
        if group == dp and op in ("reduce-scatter", "all-gather"):
            assert elems % shard_elems == 0, (op, elems, group)


def test_dpxfsdp_no_cross_axis_optimizer_collectives(compiled_dpxfsdp):
    """Optimizer state is elementwise on the flat dp shard: nothing
    moment-sized may cross the mesh at all, and no gradient-sized
    all-reduce may survive (scalars — loss, denom, grad-norm — are
    fine)."""
    _, bs, ss, compiled = compiled_dpxfsdp
    n_params = bs._plan.total
    moment_elems = {
        int(np.prod(np.shape(l)))
        for l in jax.tree.leaves(ss["opt_state"])
        if np.ndim(l) > 0 and int(np.prod(np.shape(l))) > 1
    }
    for op, elems, group in hlo_collectives(compiled.as_text()):
        if op == "all-reduce":
            assert elems < n_params // 2, (op, elems, group)
        assert elems not in moment_elems or op in (
            "reduce-scatter",
            "all-gather",
        ), ("optimizer-state-sized collective", op, elems, group)


def test_dpxfsdp_opt_state_bytes(compiled_dpxfsdp):
    mesh, bs, ss, _ = compiled_dpxfsdp
    cfg = tiny_cfg(tie_embeddings=False)
    dp = mesh.shape["dp"]
    full_state = init_train_state(
        jax.random.key(0), cfg, mesh, optax.adamw(1e-3)
    )
    full = opt_state_bytes_per_replica(full_state["opt_state"])
    rep = opt_state_bytes_per_replica(ss["opt_state"])
    assert rep <= full / dp + 3 * bs.comm_resolved.bucket_bytes, (rep, full)


def test_zero2_scatters_every_microbatch():
    """zero2's accumulator is the 1/dp shard: each microbatch pays its
    own bucket reduce-scatters (accum × n_buckets in the HLO), where
    zero1 defers to one exchange per step. The scatter-before-
    accumulate structure is what removes the full-gradient buffer from
    the accum loop."""
    cfg = tiny_cfg(tie_embeddings=False)
    mesh = zoo_mesh("tp")
    accum = 2

    def rs_count(mode):
        comm = shd.CommConfig(update_sharding=mode, bucket_mb=0.05)
        b = TrainStepBuilder(
            cfg, mesh, optax.adamw(1e-3), grad_accum=accum, comm=comm
        )
        assert b.update_sharding, b.update_sharding_reason
        state = init_train_state(
            jax.random.key(0), cfg, mesh, b.optimizer, comm=b.comm_resolved
        )
        batch = next(batches(1, batch=32))
        compiled = jax.jit(b.step_fn).lower(state, batch).compile()
        stats = collective_stats(compiled.as_text())
        return b._plan, stats["counts"].get("reduce-scatter", 0)

    plan1, n1 = rs_count("zero1")
    plan2, n2 = rs_count("zero2")
    assert n2 >= accum * plan2.n_buckets, (n2, plan2.n_buckets)
    assert n1 < n2
    assert n1 >= plan1.n_buckets


# ---------------------------------------------------------------------------
# PackPlan property: pack → exchange → unpack over model-sharded leaves
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(4))
def test_packplan_roundtrip_sharded_leaves(seed):
    """pack_flat → exchange_buckets → unpack_flat over fsdp-sharded
    leaf views reconstructs the dp-sum, for random shapes.

    Each dp rank holds a different local partial (leading ``[dp]``
    axis, sharded over dp); leaves also carry fsdp shardings so the
    pack runs over auto-axis-sharded views inside the partial-manual
    region — the exact provenance where a concatenate-based pack
    miscompiles on jax 0.4.x (values scaled by an unrelated mesh-axis
    size). The reference sum is computed in numpy from the replicated
    host values, never through the pack itself."""
    mesh = zoo_mesh("fsdp")
    dp, fsdp = mesh.shape["dp"], mesh.shape["fsdp"]
    rng = np.random.RandomState(seed)
    n_leaves = rng.randint(2, 6)
    tree = {}
    specs = {}
    for i in range(n_leaves):
        if rng.rand() < 0.5:
            shape = (int(rng.randint(1, 5)) * fsdp, int(rng.randint(1, 40)))
            spec = P(None, "fsdp") if rng.rand() < 0.5 else P("fsdp", None)
            if spec == P(None, "fsdp"):
                shape = (shape[0], int(rng.randint(1, 5)) * fsdp)
        else:
            shape = (int(rng.randint(1, 120)),)
            spec = P(None)
        tree[f"leaf{i}"] = np.asarray(
            rng.randn(dp, *shape), np.float32
        )
        specs[f"leaf{i}"] = P(*(("dp",) + tuple(spec)))

    abs_tree = {
        k: jax.ShapeDtypeStruct(v.shape[1:], jnp.float32)
        for k, v in tree.items()
    }
    plan = shd.build_pack_plan(abs_tree, dp, bucket_bytes=512, mesh_axes=("dp", "fsdp"))
    sharded = {
        k: jax.device_put(
            v, jax.sharding.NamedSharding(mesh, specs[k])
        )
        for k, v in tree.items()
    }

    def region(t):
        local = {k: v[0] for k, v in t.items()}  # this rank's partial
        flat = shd.pack_flat(local, plan)
        return shd.exchange_buckets(flat, plan, "float32")

    # in_specs may only name the manual axes ({"dp"}); the fsdp
    # shardings ride along on the values through the auto partitioner
    f = jax.jit(
        jax.shard_map(
            region,
            mesh=mesh,
            in_specs=({k: P("dp") for k in tree},),
            out_specs=P(None, "dp"),
            axis_names={"dp"},
        )
    )
    flat_sum = f(sharded)
    assert flat_sum.shape == (plan.n_buckets, plan.bucket_elems)
    got = shd.unpack_flat(flat_sum, abs_tree, plan)
    for k in tree:
        want = tree[k].sum(axis=0)
        np.testing.assert_allclose(
            np.asarray(got[k]), want, rtol=1e-5, atol=1e-5,
            err_msg=f"{k} seed={seed}",
        )
