"""Trinity-Mini's architecture (``trinity-mini``: an attention kind per
layer — sliding-window layers with rope, full layers without positions
—, a sigmoid gate on the attention's output, four norms a layer, a
scaled embedding, sigmoid-scored experts of which a part is held beside
a shared one, behind a dense layer) against the benchmark's plain
reference, at a tiny size on the CPU with seeded weights: the
comparison the chip's cell is judged by (``benchmarks/lib/routed.py``),
one defect per new part, what each kind of layer sees, the shares of an
expert-parallel layer adding up to the uncut layer, the gradient, the
period scan, the counters and the paths that refuse the model."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from reference_suite import LOGITS, REFUSALS, Suite, seeded

from benchmarks.references import trinity_afmoe_plain as plain
from benchmarks.tests import trinity_defects
from dlrover_tpu.models import decoder, get_config
from dlrover_tpu.observability import tracing
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.train import TrainStepBuilder, make_optimizer
from dlrover_tpu.train.train_step import abstract_train_state

# 1 dense + 4 routed layers: a window layer, then one period SSSF
TINY = dict(
    n_layer=5, n_dense_layer=1, layer_types="SSSSF", d_model=64, n_head=4,
    n_kv_head=2, d_head=16, d_ff=128, vocab_size=256, max_seq=64,
    attn_window=8, d_expert=32, n_experts=8, expert_top_k=2,
    n_experts_held=4, expert_offset=0, remat="full", dtype="float32",
)
SIZE_KEYS = (
    "n_layer", "n_dense_layer", "layer_types", "d_model", "n_head",
    "n_kv_head", "head_dim", "d_ff", "vocab_size", "attn_window",
    "rope_theta", "norm_eps", "scale_embedding", "d_expert", "n_experts",
    "n_experts_held", "expert_offset", "expert_top_k", "n_shared_experts",
    "routed_scaling_factor", "moe_renorm_topk", "moe_aux_coef",
)
CHECKS = [
    "choices_valid", "routing_regret", "logits_vs_reference",
    "logits_rms_vs_reference", "loss_vs_reference",
    "moe_lb_loss_vs_reference", "loss_vs_free_reference",
]
# every norm scale and per-head scale drawn around 1 (at 1 a scale left
# out could not show) and a head that reads the token table
SUITE = Suite(
    "trinity-mini", plain, TINY, SIZE_KEYS, seq=32, q_block=16, norm_eps=None,
    make=lambda cfg, seed: seeded(
        cfg, seed, scales=jax.random.key(seed + 100)
    ),
)
_cfg, _sizes, _batch = SUITE.cfg, SUITE.sizes, SUITE.batch
_seeded = SUITE.weights


@pytest.fixture(scope="module")
def model():
    return SUITE.model()


def test_program_matches_the_plain_reference(model):
    cfg, params = model
    checks, record = SUITE.compare(cfg, params)
    assert list(checks) == CHECKS
    assert all(ok for ok, _ in checks.values()), checks
    assert checks["routing_regret"][1] == 0.0
    assert checks["logits_vs_reference"][1] < 1e-5
    assert checks["moe_lb_loss_vs_reference"][1] < 1e-5
    # one row of choices per routed layer, none for the dense one
    assert len(record["moved_by_layer"]) == cfg.n_routed_layer == 4
    assert set(record["reference_terms"]) == {"moe_lb_loss"}


# ---- one defect per new part ----------------------------------------------


# defect -> what differs in the program; every one has to fail the
# teacher-forced logits
DEFECTS = {
    # the five the chip's cell was held to (PERF.md section 6, PR 47) ...
    **{name: lambda patch, cfg, plant=plant: plant(patch)
       for name, plant in trinity_defects.PLANT.items()},
    # ... and others a configuration can state
    "window_on_full_layers": dict(layer_types="SSSSS"),
    "epsilon_of_another_model": dict(norm_eps=1e-2),
    "scaling_factor_left_out": dict(routed_scaling_factor=1.0),
}


@pytest.mark.parametrize("defect", sorted(DEFECTS))
def test_comparison_catches(monkeypatch, model, defect):
    SUITE.catches(monkeypatch, model, DEFECTS[defect], LOGITS)


# ---- what each kind of layer sees -----------------------------------------


def _one_kind(kind):
    """Two routed layers of one kind behind no dense layer."""
    cfg = _cfg(n_layer=2, n_dense_layer=0, layer_types=kind * 2)
    return cfg, _seeded(cfg, 2)


@pytest.mark.parametrize("kind,sees", [("S", False), ("F", True)])
def test_a_window_layer_ignores_a_key_eight_back(kind, sees):
    """Token 0 changed: query 8 of a window layer (window 8: keys 1-8)
    is unmoved in the first layer's output, a full layer's is not."""
    cfg, params = _one_kind(kind)
    cfg = dataclasses.replace(cfg, n_layer=1, layer_types=kind)
    params = dict(params, layers=jax.tree.map(lambda t: t[:1], params["layers"]))
    tokens = _batch()["tokens"]
    other = tokens.at[:, 0].set((tokens[:, 0] + 1) % cfg.vocab_size)
    a = decoder.forward(params, tokens, cfg, features_only=True)
    b = decoder.forward(params, other, cfg, features_only=True)
    moved = np.abs(np.asarray(a - b)).max(-1)  # [B, S]
    assert (moved[:, :8] > 0).all()  # queries 0-7 see key 0
    assert (moved[:, 8:] > 1e-6).all() == sees
    if not sees:
        assert (moved[:, 8:] == 0).all()


@pytest.mark.parametrize("kind", ["S", "F"])
def test_positions_shifted_move_no_layer(kind):
    """Every position id + 1000: rope is relative and a full layer has
    no positions, so neither kind's output moves (program and reference
    alike)."""
    cfg, params = _one_kind(kind)
    tokens = _batch()["tokens"]
    base = jnp.broadcast_to(jnp.arange(32), tokens.shape)
    a = decoder.forward(params, tokens, cfg, positions=base)
    b = decoder.forward(params, tokens, cfg, positions=base + 1000)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)
    with jax.default_matmul_precision("highest"):
        ra, _ = plain.forward(params, tokens, _sizes(cfg), 16, positions=base)
        rb, _ = plain.forward(
            params, tokens, _sizes(cfg), 16, positions=base + 1000
        )
    np.testing.assert_allclose(np.asarray(ra), np.asarray(rb), atol=2e-3)
    if kind == "F":
        # no positional term AT ALL: bit for bit
        assert (np.asarray(a) == np.asarray(b)).all()


def test_a_full_layer_has_no_rope():
    """Positions stretched (0, 3, 6, ...): a window layer's output
    moves, a full layer's does not by a bit; and the same weights with
    rope swapped onto the full layers give another model."""
    tokens = _batch()["tokens"]
    base = jnp.broadcast_to(jnp.arange(32), tokens.shape)
    for kind, moves in (("S", True), ("F", False)):
        cfg, params = _one_kind(kind)
        a = decoder.forward(params, tokens, cfg, positions=base)
        b = decoder.forward(params, tokens, cfg, positions=3 * base)
        assert (np.abs(np.asarray(a - b)).max() > 1e-3) == moves
        if not moves:
            assert (np.asarray(a) == np.asarray(b)).all()
    cfg, params = _one_kind("F")
    # one kind a model, rope, no window: full attention WITH positions
    roped = dataclasses.replace(cfg, layer_types="", attn_window=0)
    a = decoder.forward(params, tokens, cfg)
    b = decoder.forward(params, tokens, roped)
    assert np.abs(np.asarray(a - b)).max() > 1e-2


# ---- the shares add up ----------------------------------------------------


def test_shares_of_the_expert_parallel_layer_add_up():
    """Eight chips hold experts 0-1 ... 14-15 of one routed layer."""
    SUITE.shares_add_up(8, 2, n_experts=16, expert_top_k=4)


# ---- the gradient ---------------------------------------------------------


def test_gradient_of_every_leaf_is_the_references(model):
    """d(ce_loss + moe_lb_loss)/d(params) through the dense window
    layer, the routed stack scanned a period at a time under ``remat:
    full``, the gate and the four norms, against ``jax.grad`` of the
    plain reference sent to the same experts."""
    SUITE.gradients_match(model, terms=("moe_lb_loss",))


# ---- the period scan ------------------------------------------------------


@pytest.mark.parametrize(
    "kinds,period",
    [("SSSFSSSF", 4), ("S", 1), ("SSSS", 1), ("SSSF", 4), ("SFSFSF", 2),
     ("SSSFSSS", 7)],
)
def test_period_of_a_stack(kinds, period):
    assert decoder._period(kinds) == period


def test_two_periods_scanned_are_the_layers_one_by_one():
    """1 dense + 8 routed layers (SSSF SSSF, the cell's depth): the
    scan over two periods gives what the reference's nine layers give
    one after another, and the expert ids come out in trunk order."""
    cfg = _cfg(n_layer=9, layer_types="S" + "SSSF" * 2)
    checks, record = SUITE.compare(cfg, _seeded(cfg, 5))
    assert all(ok for ok, _ in checks.values()), checks
    assert checks["logits_vs_reference"][1] < 1e-5
    assert len(record["moved_by_layer"]) == 8


# ---- counters, counts and refusals ----------------------------------------


def test_step_counts_its_layers_by_kind(monkeypatch):
    """A traced train step carries ``attn.window_layers``,
    ``attn.full_layers`` and ``attn.output_kept``, set by
    ``decoder.forward`` as it decides: on the CPU (no flash
    kernel) nothing is kept; where the attention runs the kernels each
    KIND's output is kept where its forward kernel executes 2,048 keys
    a query or more — the published window's band does, a narrower
    one's does not beside full layers that do."""
    cfg = _cfg(n_layer=9, layer_types="S" + "SSSF" * 2, dtype="bfloat16")
    mesh = build_mesh(MeshConfig(dp=-1), devices=jax.devices()[:1])
    opt = make_optimizer(learning_rate=1e-4, warmup_steps=2, decay_steps=10)
    builder = TrainStepBuilder(cfg, mesh, opt)
    state = abstract_train_state(cfg, mesh, opt, comm=builder.comm_resolved)
    batch = {
        k: jax.ShapeDtypeStruct((1, 64), jnp.int32)
        for k in ("tokens", "targets")
    }
    tracing._counters.clear()
    jax.eval_shape(builder.step_fn, state, batch)
    counters = tracing.counters()
    assert counters["attn.window_layers"] == 7
    assert counters["attn.full_layers"] == 2
    assert counters["attn.output_kept"] == 0
    # by kind, where the kernels run: the published window at 16,384
    wide = _cfg(
        n_layer=9, layer_types="S" + "SSSF" * 2, attn_window=2048,
        max_seq=16384,
    )
    # — a band of three key tiles of 1,024, 2,880 keys a query executed
    # for 1,920 attended to (PR 61: the rule reads what the kernel runs)
    assert decoder.keeps_attention_output(wide, 16384, "flash", kind="S")
    assert decoder.keeps_attention_output(wide, 16384, "flash", kind="F")
    assert decoder.kept_attention_layers(wide, 16384, "flash") == 9
    # the line can still fall INSIDE a step: a window of 1,024 keys is a
    # band of two tiles, 1,984 keys executed — remade — beside 8,704
    narrow = dataclasses.replace(wide, attn_window=1024)
    assert not decoder.keeps_attention_output(
        narrow, 16384, "flash", kind="S"
    )
    assert decoder.keeps_attention_output(narrow, 16384, "flash", kind="F")
    assert decoder.kept_attention_layers(narrow, 16384, "flash") == 2
    assert decoder.kept_attention_layers(wide, 2048, "flash") == 0
    assert decoder.kept_attention_layers(wide, 16384, "reference") == 0
    # a model of one kind counts all its layers or none
    keye = get_config("keye-vl-2.0", n_layer=12, remat="full")
    assert decoder.kept_attention_layers(keye, 8192, "flash") == 12
    assert decoder.kept_attention_layers(keye, 2048, "flash") == 0


def test_counts_by_kind():
    """``flops_per_token`` and ``executed_span`` by kind at the cell's
    sizes, against the benchmark's own count."""
    cfg = get_config(
        "trinity-mini", n_layer=9, n_dense_layer=1,
        layer_types="SSSSFSSSF", n_experts_held=16, vocab_size=25024,
    )
    assert cfg.executed_span(16384, "S") == 1920.0625
    assert cfg.executed_span(16384, "F") == 8192.5
    sizes = dict(_sizes(cfg))
    terms = plain.required_terms(sizes, 16384)
    assert terms["multiplied_params"] == 437_125_120
    assert cfg.flops_per_token(16384) == (
        6.0 * terms["multiplied_params"]
        + 12.0 * terms["attention_pair_channels"]
    )
    # what init makes is what num_params counts
    shapes = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    n = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert n == cfg.num_params()  # the per-head scales among them
    assert round(n / 1e6, 1) == 1243.4


@pytest.mark.parametrize(
    "over,why",
    [
        (dict(layer_types="SSSF"), "names each of the n_layer layers"),
        (dict(layer_types="SSSSX"), "names each of the n_layer layers"),
        (dict(attn_window=0), "layer_types is for causal"),
        (dict(pos="learned"), "layer_types is for causal"),
        (dict(layer_types="", parallel_residual=True), "attn_gate and post"),
    ],
)
def test_config_refuses(over, why):
    with pytest.raises(ValueError, match=why):
        _cfg(**over)


@pytest.mark.parametrize("path", sorted(REFUSALS))
def test_cache_and_generate_paths_refuse_the_model(model, path):
    SUITE.refuses(model, path, "trinity-mini: a trunk whose")


def test_a_gated_layer_of_one_kind_is_refused_by_the_cache_paths():
    cfg = _cfg(n_layer=2, n_dense_layer=0, layer_types="")
    with pytest.raises(ValueError, match="gated, twice-normed"):
        decoder.init_kv_cache(cfg, 2, 64)


@pytest.mark.parametrize("name", ["gpt2-1.5b", "mistral-7b", "keye-vl-2.0"])
def test_parameter_trees_of_the_other_models_are_unchanged(name):
    """No gate, no output norm in a model that does not ask for them."""
    cfg = get_config(name, n_layer=1, vocab_size=256, max_seq=64)
    shapes = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    layer = shapes["layers"]
    assert "wg" not in layer["attn"]
    assert "ln1_post" not in layer and "ln2_post" not in layer
    assert cfg.norm_eps is None and not cfg.layer_types
