"""fp8 delayed scaling THROUGH the ZeRO-1 sharded update (ISSUE 6).

The contract under test (train/train_step.py + parallel/sharding.py +
ops/fp8.py):

- The ``cfg.fp8`` gate on ``resolve_update_sharding`` is LIFTED for
  pure-dp meshes: the delayed-scaling state threads the shard_map
  manual region as an explicit argument, per-rank updated histories
  merge with ``lax.pmax`` over dp — the same all-reduce-max the
  replicated program runs, so the sharded rollout's fp8 state is
  BITWISE identical to the replicated one.
- Once-per-step semantics: every microbatch of a grad-accum step
  quantizes against the SAME step-start scales; the per-microbatch
  updated histories max-merge in the scan carry; each optimizer step
  advances every history by exactly ONE slot. Consequences pinned
  below: forward-operand histories (amax_x/amax_w) are bitwise
  IDENTICAL across grad_accum settings, and the gradient history's
  new slot scales exactly linearly with accum (the per-microbatch
  loss denominator is the microbatch token count, so cotangents are
  a× larger — the history tracks the actually-quantized magnitudes).
- HLO shape: gradients still leave the backward as bucketed
  reduce-scatters (never a full-gradient all-reduce), the module
  really quantizes (f8e4m3/f8e5m2 converts), and on pre-fp8 backends
  no DOT consumes f8 operands — the recipe runs through bf16 upcasts
  of the already-quantized values (identical numerics, ops/fp8.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models.config import get_config
from dlrover_tpu.parallel import sharding as shd
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.train import train_step as ts
from dlrover_tpu.train.train_step import (
    TrainStepBuilder,
    init_train_state,
    resolve_update_sharding,
)

DP = 8


def fp8_cfg(**kw):
    kw.setdefault("dtype", "float32")
    kw.setdefault("fp8", True)
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


def assert_trees_equal(a, b, msg=""):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), msg)


# ---------------------------------------------------------------------------
# Gate: fp8 composes with the sharded update on pure-dp meshes
# ---------------------------------------------------------------------------


def test_gate_lifted_on_pure_dp():
    active, reason, plan = resolve_update_sharding(
        fp8_cfg(), dp_mesh(), optax.adamw(1e-3), comm_cfg()
    )
    assert active and reason is None and plan is not None


def test_fallback_logged_once_per_config(monkeypatch):
    """A fallback reason warns ONCE per (reason, config) — the trainer
    rebuilds steps every cadence change, and re-warning buries real
    warnings; repeats ride update_sharding_reason instead. (Handler
    attached by hand: common.log loggers set propagate=False, so
    caplog's root-logger hook never sees them.)"""
    import logging

    monkeypatch.setattr(ts, "_LOGGED_FALLBACKS", set())
    records = []

    class Grab(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    grab = Grab()
    ts.logger.addHandler(grab)
    try:
        cfg = fp8_cfg(n_experts=2)  # MoE gate still refuses
        for _ in range(3):
            active, reason, _ = resolve_update_sharding(
                cfg, dp_mesh(), optax.adamw(1e-3), comm_cfg()
            )
    finally:
        ts.logger.removeHandler(grab)
    assert not active and "MoE" in reason
    hits = [m for m in records if "falling back" in m]
    assert len(hits) == 1, hits


# ---------------------------------------------------------------------------
# HLO guards (one compile, several assertions)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def compiled_fp8_sharded():
    cfg = fp8_cfg()
    mesh = dp_mesh()
    b = TrainStepBuilder(cfg, mesh, optax.adamw(1e-3), comm=comm_cfg())
    assert b.update_sharding, b.update_sharding_reason
    state = init_train_state(
        jax.random.key(0), cfg, mesh, b.optimizer, comm=b.comm_resolved
    )
    batch = next(batches(1))
    lowered = jax.jit(b.step_fn).lower(state, batch)
    return b, state, batch, lowered.as_text(), lowered.compile()


def test_hlo_quantizes_and_reduce_scatters(compiled_fp8_sharded):
    from dlrover_tpu.observability.runtime_timer import collective_stats

    _, _, _, lowered_text, compiled = compiled_fp8_sharded
    low = lowered_text.lower()
    assert "f8e4m3" in low, "forward operands never quantize to e4m3"
    assert "f8e5m2" in low, "gradients never quantize to e5m2"
    counts = collective_stats(compiled.as_text())["counts"]
    assert (
        counts.get("reduce-scatter", 0) + counts.get("all-to-all", 0) > 0
    ), counts
    assert counts.get("all-gather", 0) > 0, counts


def test_hlo_no_full_gradient_all_reduce(compiled_fp8_sharded):
    """Same guard as the bf16 suite, now with fp8 state in the carry:
    any surviving all-reduce must be scalar-ish (loss, denom) or
    amax-history-sized (the pmax merge) — never gradient-sized."""
    import re

    b, _, _, _, compiled = compiled_fp8_sharded
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


def test_cpu_dots_never_consume_f8(compiled_fp8_sharded):
    """On a pre-fp8 backend the OPTIMIZED module must upcast the
    quantized values before every dot — an f8-operand dot here means
    the bf16 fallback broke (XLA:CPU would either reject it or run a
    slow emulation)."""
    _, _, _, _, compiled = compiled_fp8_sharded
    for line in compiled.as_text().splitlines():
        low = line.lower()
        if "dot(" not in low and "dot-general" not in low:
            continue
        assert "f8e4m3" not in low and "f8e5m2" not in low, (
            f"f8-operand dot on a pre-fp8 backend: {line.strip()[:160]}"
        )


def test_native_lowering_feeds_f8_dots():
    """``native=True`` (what the capability table resolves on v6e+)
    lowers to dots whose OPERANDS are f8 — the MXU consumes the
    quantized values directly. Lower-only: pre-fp8 backends need not
    compile it."""
    from dlrover_tpu.ops import fp8

    x = jnp.ones((16, 32), jnp.bfloat16)
    w = jnp.ones((32, 8), jnp.bfloat16)
    st = fp8.init_fp8_state()
    text = (
        jax.jit(lambda x, w, st: fp8.fp8_dot(x, w, st, native=True))
        .lower(x, w, st)
        .as_text()
        .lower()
    )
    hit = False
    for line in text.splitlines():
        if "dot_general" in line or "dot(" in line:
            hit = hit or ("f8e4m3" in line)
    assert hit, "native=True never lowered an f8-operand dot"


# ---------------------------------------------------------------------------
# Once-per-step amax semantics (pinned against the unfused/unaccumulated
# paths) and parity rollouts
# ---------------------------------------------------------------------------


def _run(cfg, mesh, steps=1, accum=1, comm=None, seed=0, batch=16):
    b = TrainStepBuilder(
        cfg, mesh, optax.adamw(1e-3), grad_accum=accum, comm=comm
    )
    if comm is not None:
        assert b.update_sharding, b.update_sharding_reason
    state = init_train_state(
        jax.random.key(0), cfg, mesh, b.optimizer, comm=b.comm_resolved
    )
    step = jax.jit(b.step_fn)
    m = None
    for bt in batches(steps, batch=batch, seed=seed):
        state, m = step(state, bt)
    return state, m


@pytest.mark.slow
def test_amax_advances_once_per_step_under_accum():
    """grad_accum must NOT multiply history pushes. Pins: (a) one slot
    per optimizer step regardless of accum — the init-ones prefix
    shifts out one slot per step; (b) forward-operand histories are
    BITWISE independent of accum (same params, same step-start scales,
    same data ⇒ same amax, regardless of how the batch is split);
    (c) the gradient history's new slot is EXACTLY accum× the
    unaccumulated one (per-microbatch denom ⇒ a× cotangents; ×2 is
    exact in f32)."""
    cfg, mesh = fp8_cfg(), dp_mesh()
    s1, _ = _run(cfg, mesh, steps=1, accum=1)
    s2, _ = _run(cfg, mesh, steps=1, accum=2)
    for k in s1["fp8"]:
        h1, h2 = s1["fp8"][k], s2["fp8"][k]
        # (a) exactly one push: every slot but the last is still the
        # init value (ones), for both runs
        for h in (h1, h2):
            assert np.allclose(np.asarray(h["amax_x"])[..., :-1], 1.0)
            assert np.allclose(np.asarray(h["amax_g"])[..., :-1], 1.0)
        # (b) forward-operand amax is accum-invariant, bitwise
        np.testing.assert_array_equal(
            np.asarray(h1["amax_x"]), np.asarray(h2["amax_x"]), k
        )
        np.testing.assert_array_equal(
            np.asarray(h1["amax_w"]), np.asarray(h2["amax_w"]), k
        )
        # (c) gradient amax scales exactly with accum
        np.testing.assert_array_equal(
            2.0 * np.asarray(h1["amax_g"])[..., -1],
            np.asarray(h2["amax_g"])[..., -1],
            k,
        )


@pytest.mark.slow
def test_fused_block_matches_sequential_fp8():
    """The fused K-step block threads the fp8 state through its scan
    carry: a K=2 block walks the same trajectory as two separate
    step_fn dispatches. Pinned at ulp-scale tolerance, not bitwise —
    the scan body and the standalone step compile as different modules,
    so fusion boundaries differ by 1 ulp from step 2 on (same artifact
    class as test_update_sharding's documented ones); a state-threading
    BUG would show as a whole missing/doubled amax push, orders of
    magnitude above this bar."""
    cfg, mesh = fp8_cfg(), dp_mesh()
    b = TrainStepBuilder(cfg, mesh, optax.adamw(1e-3))
    seq_state = init_train_state(jax.random.key(0), cfg, mesh, b.optimizer)
    blk_state = init_train_state(jax.random.key(0), cfg, mesh, b.optimizer)
    bts = list(batches(2))
    step = jax.jit(b.step_fn)
    seq_losses = []
    for bt in bts:
        seq_state, m = step(seq_state, bt)
        seq_losses.append(float(m["loss"]))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *bts)
    blk_state, bm = b.build_block()(blk_state, stacked)
    np.testing.assert_allclose(
        np.asarray(jnp.ravel(bm["loss"]), np.float32),
        np.asarray(seq_losses, np.float32),
        rtol=1e-6,
    )
    for k in seq_state["fp8"]:
        for h in ("amax_x", "amax_w", "amax_g"):
            a = np.asarray(seq_state["fp8"][k][h])
            bb = np.asarray(blk_state["fp8"][k][h])
            # one push per step: exactly K slots moved off the init ones
            assert np.allclose(a[..., :-2], 1.0) and np.allclose(
                bb[..., :-2], 1.0
            ), (k, h)
            np.testing.assert_allclose(a, bb, rtol=1e-5, err_msg=f"{k}/{h}")


@pytest.mark.slow
def test_sharded_rollout_matches_replicated():
    """The acceptance bar: a 3-step fp8 rollout under ZeRO-1 update
    sharding reproduces the replicated update — losses agree, and the
    delayed-scaling state is BITWISE identical (the pmax merge is the
    replicated program's all-reduce-max). Params carry only the known
    tied-embedding 1-ulp fusion artifact (test_update_sharding's
    docstring: worst rel grows to ~2.5e-3 by step 6; ~3e-5 at step 3
    here), pinned at 1e-3."""
    cfg, mesh = fp8_cfg(), dp_mesh()
    sr = mr = ss = ms = None
    sr, mr = _run(cfg, mesh, steps=3)
    ss, ms = _run(cfg, mesh, steps=3, comm=comm_cfg())
    assert abs(float(mr["loss"]) - float(ms["loss"])) < 1e-6
    assert_trees_equal(sr["fp8"], ss["fp8"], "fp8 state diverged")
    for x, y in zip(
        jax.tree.leaves(sr["params"]), jax.tree.leaves(ss["params"])
    ):
        x, y = np.asarray(x), np.asarray(y)
        rel = np.max(np.abs(x - y) / np.maximum(np.abs(x), 1e-6))
        assert rel < 1e-3, rel


@pytest.mark.slow
def test_sharded_accum_matches_replicated():
    """fp8 + grad_accum + ZeRO-1 all at once: the scan carry's
    max-merge composes with the manual region's pmax merge."""
    cfg, mesh = fp8_cfg(), dp_mesh()
    sr, mr = _run(cfg, mesh, steps=2, accum=2)
    ss, ms = _run(cfg, mesh, steps=2, accum=2, comm=comm_cfg())
    assert abs(float(mr["loss"]) - float(ms["loss"])) < 2e-6
    assert_trees_equal(sr["fp8"], ss["fp8"], "fp8 state diverged")
