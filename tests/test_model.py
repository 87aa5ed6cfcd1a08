"""Model + sharded train step tests on the 8-device CPU mesh."""

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import decoder, get_config
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.parallel import sharding as shd
from dlrover_tpu.train import (
    TrainStepBuilder,
    batch_sharding,
    init_train_state,
    make_optimizer,
)


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))


def _batch(rng, b=8, s=32, vocab=1000):
    tokens = jax.random.randint(rng, (b, s), 0, vocab)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}


def test_forward_shapes():
    cfg = get_config("tiny")
    params = decoder.init(jax.random.key(0), cfg)
    logits = decoder.forward(
        params, jnp.zeros((2, 16), jnp.int32), cfg
    )
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


# the benchmark's configurations (read only) and the sequence length of
# the cell that runs each
BENCHMARK_CONFIGS = {
    "gpt2-xl": 1024,
    "gpt2-xl-zero1-dp4": 1024,
    "mistral-7b-l6": 8192,
    "olmoe-1b-7b-1chip": 4096,
    "glm-4.7-flash-ep8-1chip": 8192,
    "keye-vl-2.0-ep8-1chip": 8192,
    "nemotron-3-super-ep64-1chip": 8192,
    "trinity-mini-ep8-1chip": 16384,
    "jamba2-3b-l14": 8192,
    "minicpm-sala-l4": 16384,
    "qwen3-next-80b-a3b-ep16-1chip": 16384,
    "kimi-linear-48b-a3b-ep16-1chip": 16384,
    "mellum2-12b-a2.5b-ep4-1chip": 32768,
    "lfm2-8b-a1b-ep4-1chip": 4096,
}


@pytest.mark.parametrize("name", sorted(BENCHMARK_CONFIGS))
def test_flops_per_token_is_the_benchmarks_count(name):
    """One FLOP count: the MFU the trainer reports to the master and the
    benchmark's ``train_step.mfu`` divide the same numerator — multiplied
    parameters only, the head once, the causal and windowed mean span,
    ``routed_top_k`` experts and the router for a routed layer; and for
    an architecture whose layers differ, the count its reference module
    states (``flops.resolve``): latent attention's projections, the dense
    prefix, the held and shared experts, the prediction module, a
    selection of keys and its score-only indexer, layers of one part
    each with a state-space scan's recurrence among the multiplied, an
    attention kind per layer with each kind's own span, mixer + MLP
    layers of two parts each with a selective scan's, a selection of
    blocks with its pooled scorer past the length the model runs dense
    up to, a linear attention's recurrence, mixer + ROUTED layers with
    a gated delta rule's, and a delta rule by key channel's beside
    latent attention whose pairs count the mean of 192 score and 128
    value channels, a rope per layer kind, which counts nothing, and a
    gated short convolution's two matrices (its taps multiply nothing
    worth the name)."""
    from benchmarks.lib.flops import resolve

    configs = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    assert sorted(p.stem for p in configs.glob("*.json")) == sorted(
        BENCHMARK_CONFIGS
    ), "a configuration without a case here"
    config = json.loads((configs / f"{name}.json").read_text())
    cfg = get_config(
        config["program"]["model"], **config["program"]["overrides"]
    )
    for seq in (BENCHMARK_CONFIGS[name], 2 * BENCHMARK_CONFIGS[name], 512):
        assert cfg.flops_per_token(seq) == pytest.approx(
            resolve(config, seq), rel=1e-9
        )
    if cfg.selects_keys:
        # the selection is causal by construction; what it saves against
        # every visible key is the attention's pairs past index_topk
        dense = dataclasses.replace(cfg, index_topk=0)
        spared = 8192 / 2 + 0.5 - (2048 * 2049 / 2 + 6144 * 2048) / 8192
        assert dense.flops_per_token(8192) - cfg.flops_per_token(
            8192
        ) == pytest.approx(
            12.0 * cfg.n_layer * (
                cfg.n_head * cfg.head_dim * spared
                - cfg.index_n_heads * cfg.index_head_dim / 2 * 4096.5
            ) - 6.0 * cfg.n_layer * cfg.index_params
        )
        return
    if cfg.selects_blocks:
        # up to ``select_dense_len`` a sparse layer counts every visible
        # key; past it the keys of the blocks a query chose (3,560.5 at
        # 16,384) and half a pair-channel a pooled key it scored
        assert cfg.flops_per_token(16384) - cfg.flops_per_token(
            8192
        ) == pytest.approx(
            12.0 * cfg.layer_pattern.count("S") * cfg.n_head * cfg.head_dim
            * (3560.5 + 8192.5 / 16 / 2 - 4096.5)
        )
        return
    # bidirectional attention sees every key, a causal one half on average
    attention_layers = cfg.n_layer + cfg.n_mtp_module
    if cfg.layer_pattern:  # one part a layer: the attention layers alone
        attention_layers = (cfg.layer_pattern + cfg.mtp_pattern).count("*")
    # (``layer_types`` names causal kinds: one kind a model for this)
    both_ways = dataclasses.replace(
        cfg, causal=False, attn_window=0, layer_types=""
    )
    causal = dataclasses.replace(cfg, attn_window=0, layer_types="")
    assert both_ways.flops_per_token(1024) - causal.flops_per_token(
        1024
    ) == pytest.approx(
        # (a pair's two products: one over the score channels, one
        # over the value channels, narrower under Kimi-Linear's latent
        # attention alone)
        12.0 * attention_layers * cfg.n_head
        * (cfg.head_dim + cfg.value_dim) / 2 * (1024 - 512.5)
    )


@pytest.mark.slow  # tier-1 budget: three full model inits (~58s); the
# tree-structure property is exercised fast by every sharded HLO test
# that consumes logical_axes
def test_logical_axes_match_params():
    for name in ("tiny", "gpt2-124m", "tiny-moe"):
        cfg = get_config(name, n_layer=2)
        params = decoder.init(jax.random.key(0), cfg)
        axes = decoder.logical_axes(cfg)
        ps = jax.tree.structure(params)
        ax = jax.tree.structure(
            axes, is_leaf=lambda x: x is None or isinstance(x, tuple)
        )
        assert ps == ax, f"{name}: param/axes tree mismatch"
        # every axes tuple has the same rank as its param
        flat_p = jax.tree.leaves(params)
        flat_a = jax.tree.leaves(
            axes, is_leaf=lambda x: x is None or isinstance(x, tuple)
        )
        for p, a in zip(flat_p, flat_a):
            if a is not None:
                assert len(a) == p.ndim


@pytest.mark.slow  # tier-1 budget: sharded paths pinned fast by HLO tests
def test_sharded_init_and_step(mesh):
    cfg = get_config("tiny")
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=2, decay_steps=10)
    state = init_train_state(jax.random.key(0), cfg, mesh, opt)
    # embedding is sharded: vocab over tp, embed over fsdp
    emb = state["params"]["embed"]["tokens"]
    assert "tp" in str(emb.sharding.spec) or "fsdp" in str(emb.sharding.spec)

    step = TrainStepBuilder(cfg, mesh, opt).build()
    batch = jax.device_put(_batch(jax.random.key(1)), batch_sharding(mesh))
    state, metrics = step(state, batch)
    l1 = float(metrics["loss"])
    for _ in range(3):
        state, metrics = step(state, batch)
    assert float(metrics["loss"]) < l1, "loss should fall on a repeated batch"
    assert int(state["step"]) == 4


@pytest.mark.slow
def test_offloaded_opt_state_matches_resident(mesh):
    """Host-offloaded moments (CPU-offload-Adam parity): same numerics
    as HBM-resident state, and the moments actually live in pinned_host."""
    cfg = get_config("tiny")
    opt = make_optimizer(
        learning_rate=1e-3, warmup_steps=2, decay_steps=10
    )
    batch = jax.device_put(_batch(jax.random.key(1)), batch_sharding(mesh))

    state_res = init_train_state(jax.random.key(0), cfg, mesh, opt)
    state_off = init_train_state(
        jax.random.key(0), cfg, mesh, opt, offload_opt_state=True
    )
    if jax.default_backend() != "cpu":  # CPU: offload is a no-op
        kinds = {
            leaf.sharding.memory_kind
            for leaf in jax.tree.leaves(state_off["opt_state"])
            if hasattr(leaf, "sharding")
        }
        assert "pinned_host" in kinds, kinds

    s_res = TrainStepBuilder(cfg, mesh, opt).build()
    s_off = TrainStepBuilder(
        cfg, mesh, opt, offload_opt_state=True
    ).build()
    for _ in range(3):
        state_res, m_res = s_res(state_res, batch)
        state_off, m_off = s_off(state_off, batch)
    np.testing.assert_allclose(
        float(m_res["loss"]), float(m_off["loss"]), rtol=1e-5
    )
    pr = jax.tree.leaves(state_res["params"])[0]
    po = jax.tree.leaves(state_off["params"])[0]
    np.testing.assert_allclose(
        np.asarray(pr), np.asarray(po), rtol=1e-5, atol=1e-6
    )


def test_offload_opt_strategy_method():
    from dlrover_tpu.accelerate.strategy import apply_strategy

    plan = apply_strategy([("fsdp", {}), ("offload_opt", {})])
    assert plan.offload_opt_state is True
    # plan survives the JSON round trip
    from dlrover_tpu.accelerate.strategy import AccelerationPlan

    assert AccelerationPlan.from_json(plan.to_json()).offload_opt_state


@pytest.mark.slow
def test_grad_accum_matches_full_batch(mesh):
    cfg = get_config("tiny")
    opt = make_optimizer(
        learning_rate=1e-3, grad_clip=0, schedule="const", name="sgd"
    )
    batch = _batch(jax.random.key(2), b=8)
    state1 = init_train_state(jax.random.key(0), cfg, mesh, opt)
    state2 = jax.tree.map(jnp.copy, state1)

    s_full = TrainStepBuilder(cfg, mesh, opt, grad_accum=1).build()
    s_acc = TrainStepBuilder(cfg, mesh, opt, grad_accum=4).build()
    out1, _ = s_full(state1, batch)
    out2, _ = s_acc(state2, batch)
    p1 = jax.tree.leaves(out1["params"])[0]
    p2 = jax.tree.leaves(out2["params"])[0]
    # leaf 0 is the embedding table: its grad is a scatter-add of bf16
    # cotangents, and accum=4 vs accum=1 sums them in different orders.
    # The resulting param diff is O(lr · bf16 ulp · counts) ≈ 7e-5 and
    # shifts with XLA's CPU reduction partitioning (thread count), so
    # atol must sit above it; a broken accumulator (wrong scaling,
    # dropped microbatch) is off by O(lr · grad) ≈ 1e-3, far past this.
    np.testing.assert_allclose(
        np.asarray(p1), np.asarray(p2), rtol=2e-4, atol=2e-4
    )


@pytest.mark.slow  # tier-1 budget: sharded MoE forward compile
# (~18s); MoE numerics are pinned fast throughout test_moe.py
def test_moe_forward(mesh):
    cfg = get_config("tiny-moe")
    params = decoder.init(jax.random.key(0), cfg)
    logits = decoder.forward(
        params, jnp.zeros((8, 16), jnp.int32), cfg, mesh=mesh
    )
    assert logits.shape == (8, 16, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.slow  # tier-1 budget: double grad compile (~22s); remat
# parity siblings (offload, dtype-cast) already run on the slow tier
def test_remat_matches_no_remat():
    cfg = get_config("tiny")
    cfg_r = get_config("tiny", remat="full")
    params = decoder.init(jax.random.key(0), cfg)
    toks = jnp.zeros((2, 16), jnp.int32)
    batch = {"tokens": toks, "targets": toks}

    g1 = jax.grad(lambda p: decoder.loss_fn(p, batch, cfg)[0])(params)
    g2 = jax.grad(lambda p: decoder.loss_fn(p, batch, cfg_r)[0])(params)
    a = jax.tree.leaves(g1)[0]
    b = jax.tree.leaves(g2)[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


@pytest.mark.slow
def test_streamed_offload_adamw_matches_resident(mesh):
    """Per-leaf streamed host-offload (VERDICT r2 #8): same numerics as
    plain AdamW, no whole-tree device_put — the builder-level offload
    flag stays OFF and the optimizer owns placement."""
    cfg = get_config("tiny")
    opt_res = make_optimizer(learning_rate=1e-3, warmup_steps=2,
                             decay_steps=10)
    opt_str = make_optimizer(learning_rate=1e-3, warmup_steps=2,
                             decay_steps=10, offload_states=True)
    batch = jax.device_put(_batch(jax.random.key(1)), batch_sharding(mesh))

    state_res = init_train_state(jax.random.key(0), cfg, mesh, opt_res)
    state_str = init_train_state(
        jax.random.key(0), cfg, mesh, opt_str, offload_opt_state=True
    )
    s_res = TrainStepBuilder(cfg, mesh, opt_res).build()
    s_str = TrainStepBuilder(cfg, mesh, opt_str).build()
    for _ in range(3):
        state_res, m_res = s_res(state_res, batch)
        state_str, m_str = s_str(state_str, batch)
    # tolerance: the streamed path recomputes the bias-correction
    # powers/f32 chain in a different op order than optax's fused one
    np.testing.assert_allclose(
        float(m_res["loss"]), float(m_str["loss"]), rtol=1e-4
    )
    pr = jax.tree.leaves(state_res["params"])[0]
    ps = jax.tree.leaves(state_str["params"])[0]
    np.testing.assert_allclose(
        np.asarray(pr), np.asarray(ps), rtol=5e-4, atol=1e-6
    )


@pytest.mark.slow
def test_streamed_offload_serializes_leaf_transfers(mesh):
    """Structural proof of the working-set bound: the compiled step's
    HLO chains every moment leaf through opt-barriers, so leaf i+1's
    transfer depends on leaf i's update (XLA cannot batch them)."""
    cfg = get_config("tiny")
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=2,
                         decay_steps=10, offload_states=True)
    state = init_train_state(jax.random.key(0), cfg, mesh, opt)
    builder = TrainStepBuilder(cfg, mesh, opt)
    batch = jax.device_put(_batch(jax.random.key(1)), batch_sharding(mesh))
    import jax as _jax

    lowered = _jax.jit(builder.step_fn, donate_argnums=(0,)).lower(
        state, batch
    )
    txt = lowered.as_text()  # StableHLO
    n_leaves = len(_jax.tree.leaves(state["params"]))
    n_barriers = txt.count("optimization_barrier")
    assert n_barriers >= n_leaves, (n_barriers, n_leaves)


def test_analyser_offload_bound_is_leaf_sized():
    """analyse() budgets offloaded moments at the largest-leaf bound,
    not a fraction of the tree (closes the 0.5x assumption)."""
    from dlrover_tpu.accelerate.analyser import analyse
    from dlrover_tpu.accelerate.strategy import apply_strategy

    cfg = get_config("gpt2-1.5b")
    axes = {"dp": 1, "fsdp": 8, "tp": 1, "sp": 1, "pp": 1}
    plan_res = apply_strategy([("mixed_parallel", axes)])
    plan_off = apply_strategy(
        [("mixed_parallel", axes), ("offload_opt", {})]
    )
    res = analyse(cfg, plan_res, n_devices=8, batch_per_chip=1, seq=128)
    off = analyse(cfg, plan_off, n_devices=8, batch_per_chip=1, seq=128)
    assert off.opt_bytes_per_chip < res.opt_bytes_per_chip
    # bound = slack * slots * 4B * max(embed, stacked-mlp leaf) / shards
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
    max_leaf = max(v * d, cfg.n_layer * d * f)
    assert off.opt_bytes_per_chip == pytest.approx(
        2.0 * 2 * 4 * max_leaf / 8
    )


@pytest.mark.slow
def test_multi_slice_hybrid_mesh_trains():
    """num_slices>1 (the DCN layout: dp split across slices, model axes
    inside each slice) must build and train off multi-slice hardware —
    virtual CPU devices carry no slice_index attribute, so build_mesh
    falls back to contiguous-block slice emulation; the axis SHAPES and
    the collectives they imply are identical to the real hybrid mesh."""
    mesh2 = build_mesh(MeshConfig(dp=4, tp=2, num_slices=2))
    assert mesh2.shape["dp"] == 4 and mesh2.shape["tp"] == 2
    cfg = get_config("tiny")
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=2,
                         decay_steps=10)
    state = init_train_state(jax.random.key(0), cfg, mesh2, opt)
    step = TrainStepBuilder(cfg, mesh2, opt).build()
    toks = jnp.zeros((8, 32), jnp.int32)
    batch = jax.device_put(
        {"tokens": toks, "targets": toks}, batch_sharding(mesh2)
    )
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # dp must split evenly across slices
    with pytest.raises(ValueError, match="divisible by"):
        build_mesh(MeshConfig(dp=2, tp=4, num_slices=3))


# ---------------------------------------------------------------------------
# pins for the non-matmul rewrites: the strided-reshape rope and the
# single-pass layernorm replaced older formulations in-place, so the old
# formulas live on here as the reference the new code is held to.


def _old_rope(x, positions, theta):
    """The split+concatenate rotate-half this repo shipped before the
    strided-reshape rewrite — kept verbatim as the bitwise reference."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_rope_strided_rewrite_bitwise(dt):
    """The [..., 2, D/2] reshape pairs lane i with i+D/2 exactly like
    split(2, -1), and stack+reshape reproduces the concatenate layout —
    same f32 elementwise ops in the same order, so the rewrite must be
    BITWISE identical, not merely close."""
    b, s, h, d = 2, 16, 4, 64
    x = jax.random.normal(jax.random.key(0), (b, s, h, d)).astype(dt)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    theta = 10000.0
    rope = decoder._rope_tables(positions, d, theta)
    new = decoder._rope(x, rope)
    old = _old_rope(x, positions, theta)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
    # and with non-trivial positions (decode-style offsets)
    positions = positions + 37
    rope = decoder._rope_tables(positions, d, theta)
    np.testing.assert_array_equal(
        np.asarray(decoder._rope(x, rope)),
        np.asarray(_old_rope(x, positions, theta)),
    )


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_layernorm_single_pass_matches_two_pass(dt):
    """_norm's layernorm now computes var = E[x²] − E[x]² in the same
    f32 sweep as the mean (one read of the activation instead of two).
    Against the old mean-then-jnp.var formulation this is a reduction
    reassociation, not a semantics change: equal to f32 tolerance on
    activation scales well past anything the models produce."""
    d = 256
    x = (
        jax.random.normal(jax.random.key(1), (4, 32, d)) * 30.0
    ).astype(dt)
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.key(2), (d,))
    bias = 0.1 * jax.random.normal(jax.random.key(3), (d,))

    def two_pass(x, scale, bias):
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, -1, keepdims=True)
        var = jnp.var(x32, -1, keepdims=True)
        out = (x32 - mean) * jax.lax.rsqrt(var + 1e-5)
        out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
        return out.astype(x.dtype)

    new = decoder._norm(x, scale, bias, "layernorm")
    old = two_pass(x, scale, bias)
    np.testing.assert_allclose(
        np.asarray(new, np.float32),
        np.asarray(old, np.float32),
        rtol=2e-5 if dt == jnp.float32 else 2e-2,
        atol=2e-5 if dt == jnp.float32 else 2e-2,
    )


def test_layernorm_model_forward_matches_two_pass_family():
    """Model-level version of the layernorm pin: a layernorm-family
    config (neox: layernorm + parallel residual) forward under the
    current _norm agrees with a forward that routes every norm through
    the old two-pass formula, to f32 tolerance."""
    cfg = get_config("tiny-neox", dtype="float32", param_dtype="float32")
    assert cfg.norm == "layernorm"
    params = decoder.init(jax.random.key(0), cfg)
    batch = _batch(jax.random.key(1), b=2, s=16, vocab=cfg.vocab_size)

    loss_new = float(decoder.loss_fn(params, batch, cfg)[0])

    orig = decoder._norm

    def two_pass_norm(x, scale, bias, kind, eps=None):
        if kind != "layernorm":
            return orig(x, scale, bias, kind, eps)
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, -1, keepdims=True)
        var = jnp.var(x32, -1, keepdims=True)
        out = (x32 - mean) * jax.lax.rsqrt(var + 1e-5)
        out = out * scale.astype(jnp.float32)
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        return out.astype(x.dtype)

    decoder._norm = two_pass_norm
    try:
        loss_old = float(decoder.loss_fn(params, batch, cfg)[0])
    finally:
        decoder._norm = orig
    np.testing.assert_allclose(loss_new, loss_old, rtol=1e-5)


# ---- whole-projection QK-norm (OLMoE) ---------------------------------------


def _qk_norm_cfg(**kw):
    kw.setdefault("dtype", "float32")
    return get_config(
        "tiny", n_layer=2, d_model=64, d_ff=128, n_head=4, n_kv_head=2,
        vocab_size=128, max_seq=32, qk_norm=True, **kw,
    )


def _with_random_norm_scales(params, rng):
    """``init`` sets every norm scale to one, where a norm that forgot
    its scale would pass: draw the QK-norm scales around one."""
    attn = params["layers"]["attn"]
    for i, name in enumerate(("q_norm", "k_norm")):
        scale = attn[name]["scale"]
        attn[name]["scale"] = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(rng, i), scale.shape, scale.dtype
        )
    return params


@pytest.mark.parametrize("kind", ["rmsnorm", "layernorm"])
def test_qk_norm_matches_hand_computation(kind):
    """q and k are normed over ALL heads at once (one statistic per
    token over n_head·head_dim, resp. kv_heads·head_dim), with their own
    scales, before the split into heads and before rope; v is not."""
    cfg = _qk_norm_cfg(norm=kind)
    params = _with_random_norm_scales(
        decoder.init(jax.random.key(0), cfg), jax.random.key(5)
    )
    attn = params["layers"]["attn"]
    assert attn["q_norm"]["scale"].shape == (2, 64)
    assert attn["k_norm"]["scale"].shape == (2, 32)
    assert set(attn["q_norm"]) == {"scale"}  # scale only, also layernorm
    layer = jax.tree.map(lambda w: w[1], params["layers"])
    x = jax.random.normal(jax.random.key(1), (2, 8, 64))
    pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
    q, k, v = decoder._project_qkv(x, layer, cfg, pos)

    def norm(y, scale):
        if kind == "layernorm":
            y = y - y.mean(-1, keepdims=True)
            return y / jnp.sqrt((y * y).mean(-1, keepdims=True) + 1e-5) * scale
        return y / jnp.sqrt((y * y).mean(-1, keepdims=True) + 1e-6) * scale

    def rope(y):  # rotate-half, lane i pairs with lane i + 8
        inv = cfg.rope_theta ** (-jnp.arange(0, 16, 2) / 16)
        ang = jnp.arange(8)[:, None] * inv[None]
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        y1, y2 = y[..., :8], y[..., 8:]
        return jnp.concatenate([y1 * cos - y2 * sin, y2 * cos + y1 * sin], -1)

    a = layer["attn"]
    want_q = rope(norm(x @ a["wq"], a["q_norm"]["scale"]).reshape(2, 8, 4, 16))
    want_k = rope(norm(x @ a["wk"], a["k_norm"]["scale"]).reshape(2, 8, 2, 16))
    np.testing.assert_allclose(q, want_q, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(k, want_k, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        v, (x @ a["wv"]).reshape(2, 8, 2, 16), rtol=1e-6, atol=1e-6
    )
    # a per-head norm is another function
    per_head = rope(
        norm((x @ a["wq"]).reshape(2, 8, 4, 16),
             a["q_norm"]["scale"].reshape(4, 16))
    )
    assert float(jnp.max(jnp.abs(per_head - q))) > 1e-2
    assert decoder.logical_axes(cfg)["layers"]["attn"]["q_norm"] == {
        "scale": ("layers", "norm")
    }


def test_qk_norm_refuses_a_tp_mesh(mesh):
    cfg = _qk_norm_cfg()
    params = decoder.init(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="tp shards the heads axis"):
        decoder.forward(params, jnp.zeros((8, 16), jnp.int32), cfg, mesh=mesh)


def test_qk_norm_prefill_and_decode_match_forward():
    """A tiny routed model with QK-norm: the prompt through ``prefill``
    and the rest token by token through ``decode_step`` give the logits
    of the full forward — the cache paths make q and k in the same one
    place."""
    cfg = get_config(
        "olmoe-1b-7b", n_layer=2, d_model=64, d_ff=32, n_head=4,
        n_kv_head=4, vocab_size=128, max_seq=32, n_experts=8,
        expert_top_k=2, dtype="float32",
    )
    params = _with_random_norm_scales(
        decoder.init(jax.random.key(0), cfg), jax.random.key(5)
    )
    toks = jax.random.randint(jax.random.key(1), (2, 12), 0, 128)
    full = decoder.forward(params, toks, cfg)
    logits, cache = decoder.prefill(params, toks[:, :8], cfg, max_len=16)
    np.testing.assert_allclose(logits, full[:, :8], rtol=2e-4, atol=2e-4)
    for pos in range(8, 12):
        step, cache = decoder.decode_step(
            params, toks[:, pos], cache, jnp.int32(pos), cfg, prefilled=True
        )
        np.testing.assert_allclose(
            step, full[:, pos], rtol=2e-4, atol=2e-4
        )
    # the norm is live in what was compared
    no_norm = decoder.forward(
        params, toks, dataclasses.replace(cfg, qk_norm=False)
    )
    assert float(jnp.max(jnp.abs(no_norm - full))) > 1e-2


def test_qk_norm_tree_round_trips_checkpoint_and_pack_plan(tmp_path):
    """What walks the parameter tree takes the two new leaves as they
    come: a checkpoint saved and restored, and ZeRO's pack plan (every
    leaf in the stream, packed and unpacked bit for bit)."""
    from dlrover_tpu.checkpoint import Checkpointer, StorageType
    from dlrover_tpu.checkpoint.checkpointer import state_template

    cfg = _qk_norm_cfg()
    dp = build_mesh(MeshConfig(dp=-1))
    opt = make_optimizer(learning_rate=1e-3)
    builder = TrainStepBuilder(
        cfg, dp, opt, comm=shd.CommConfig(update_sharding=True, bucket_mb=0.05)
    )
    assert builder.update_sharding, builder.update_sharding_reason
    state = init_train_state(
        jax.random.key(0), cfg, dp, opt, comm=builder.comm_resolved
    )
    params = _with_random_norm_scales(state["params"], jax.random.key(5))
    paths = {
        jax.tree_util.keystr(p)
        for p, _ in jax.tree_util.tree_leaves_with_path(params)
    }
    assert "['layers']['attn']['q_norm']['scale']" in paths
    assert "['layers']['attn']['k_norm']['scale']" in paths

    plan = builder._plan
    assert plan.total == sum(x.size for x in jax.tree.leaves(params))
    back = shd.unpack_flat(shd.pack_flat(params, plan), params, plan)
    for x, y in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    ckpt = Checkpointer(str(tmp_path / "ckpt"), use_agent=False)
    saved = {"params": params, "step": jnp.asarray(3)}
    assert ckpt.save_checkpoint(3, saved, StorageType.DISK)
    ckpt.wait_for_persist()
    out = ckpt.load_checkpoint(state_template(saved))
    for x, y in zip(jax.tree.leaves(saved), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
