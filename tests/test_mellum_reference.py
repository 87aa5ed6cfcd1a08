"""Mellum2's architecture (``mellum2``: a rope per layer kind — window
layers under the plain table, full layers under YaRN's with its
amplitude —, softmax top-k renormalised over experts of which a part is
held, no shared expert, no dense layer) against the benchmark's plain
reference, at a tiny size on the CPU with seeded weights: the
comparison the chip's cell is judged by (``benchmarks/lib/routed.py``),
one defect per thing the configuration states, the scaled table at the
published numbers, the shares of an expert-parallel layer adding up to
the uncut layer, the gradient, the counts, the counters and the paths
that refuse the model."""

import dataclasses
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from reference_suite import REFUSALS, Suite, seeded

from benchmarks.references import mellum_plain as plain
from benchmarks.runners.train import _program_config
from benchmarks.tests import mellum_defects
from dlrover_tpu.models import decoder, get_config
from dlrover_tpu.observability import tracing
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.train import TrainStepBuilder, make_optimizer
from dlrover_tpu.train.train_step import abstract_train_state

# one period SSSY; YaRN x 4 over an original 16 positions of 64 run,
# betas 2 and 1/4 at theta 100: of a head's 8 pairs, pair 0 keeps its
# frequency, pairs 1-4 are blended and 5-7 turn four times slower, and
# three quarters of a sequence lies past the original length
TINY = dict(
    n_layer=4, layer_types="SSSY", d_model=64, n_head=4, n_kv_head=2,
    d_head=16, d_ff=128, vocab_size=256, max_seq=64, attn_window=8,
    rope_theta=100.0, rope_factor=4.0, rope_original_max=16,
    rope_beta_fast=2.0, rope_beta_slow=0.25, rope_attn_factor=0.0,
    d_expert=32, n_experts=8, expert_top_k=2, n_experts_held=4,
    expert_offset=0, remat="full", dtype="float32",
)
SIZE_KEYS = (
    "n_layer", "n_dense_layer", "layer_types", "d_model", "n_head",
    "n_kv_head", "head_dim", "vocab_size", "attn_window", "rope_theta",
    "rope_factor", "rope_original_max", "rope_beta_fast", "rope_beta_slow",
    "rope_attn_factor", "norm_eps", "d_expert", "n_experts",
    "n_experts_held", "expert_offset", "expert_top_k", "moe_renorm_topk",
    "moe_aux_coef",
)
CHECKS = [
    "choices_valid", "routing_regret", "logits_vs_reference",
    "logits_rms_vs_reference", "loss_vs_reference",
    "moe_lb_loss_vs_reference", "loss_vs_free_reference",
]
CELL = (
    pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    / "mellum2-12b-a2.5b-ep4-1chip.json"
)


# every norm scale and per-head scale drawn around 1 (at 1 a scale left
# out could not show) and a head that reads the token table
SUITE = Suite(
    "mellum2", plain, TINY, SIZE_KEYS, seq=64, q_block=16, norm_eps=None,
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
    assert len(record["moved_by_layer"]) == cfg.n_routed_layer == 4
    assert set(record["reference_terms"]) == {"moe_lb_loss"}


def test_two_periods_scanned_are_the_layers_one_by_one():
    """SSSY SSSY: the scan over two periods, each body handed its own
    table, gives what the reference's eight layers give one after
    another."""
    cfg = _cfg(n_layer=8, layer_types="SSSY" * 2)
    checks, record = SUITE.compare(cfg, _seeded(cfg, 5))
    assert all(ok for ok, _ in checks.values()), checks
    assert checks["logits_vs_reference"][1] < 1e-5
    assert len(record["moved_by_layer"]) == 8


# ---- one defect per thing the configuration states ------------------------


DEFECTS = {
    # the eight the chip's cell is held to (PERF.md section 6, PR 70) ...
    **{name: lambda patch, cfg, plant=plant: plant(patch)
       for name, plant in mellum_defects.PLANT.items()},
    # ... and others a configuration can state
    "no_positions_on_full_layers": dict(layer_types="SSSF"),
    "factor_of_another_model": dict(rope_factor=2.0),
    "original_length_of_another_model": dict(rope_original_max=32),
}


@pytest.mark.parametrize("defect", sorted(DEFECTS))
def test_comparison_catches(monkeypatch, model, defect):
    caught_by = mellum_defects.CAUGHT_BY.get(defect, mellum_defects.LOGITS)
    SUITE.catches(monkeypatch, model, DEFECTS[defect], caught_by)


# ---- the scaled table -----------------------------------------------------


def test_scaled_table_at_the_published_numbers():
    """hd 128, theta 5e5, x 16 over 8,192, betas 32 and 1: pairs 0-18
    keep their frequency, 35-63 turn sixteen times slower, and the table
    is the direct evaluation of the formula to the last float32 bit."""
    cfg = get_config("mellum2")
    factor, original, fast, slow, m = cfg.rope_scaling
    assert (factor, original, fast, slow) == (16.0, 8192, 32.0, 1.0)
    assert m == 1.2772588722239782
    assert dataclasses.replace(
        cfg, rope_attn_factor=0.0
    ).rope_scaling[4] == pytest.approx(0.1 * math.log(16) + 1, rel=1e-15)

    def pair(beta):
        return 128 * math.log(8192 / (2 * math.pi * beta)) / (
            2 * math.log(5e5)
        )

    assert (math.floor(pair(32)), math.ceil(pair(1))) == (18, 35)
    assert plain.yarn_range(json.loads(CELL.read_text())["sizes"]) == (18, 35)
    i = np.arange(64, dtype=np.float32)
    plain_f = jnp.asarray(5e5, jnp.float32) ** (
        -jnp.arange(0, 128, 2, dtype=jnp.float32) / 128
    )
    ramp = jnp.clip((jnp.asarray(i) - 18) / 17, 0.0, 1.0)
    want = plain_f * (1.0 - ramp) + plain_f / 16.0 * ramp
    got = decoder._rope_frequencies(128, 5e5, cfg.rope_scaling)
    assert (np.asarray(got) == np.asarray(want)).all()
    assert (np.asarray(got[:19]) == np.asarray(plain_f[:19])).all()
    assert float(got[0]) == 1.0
    assert (np.asarray(got[35:]) == np.asarray(plain_f[35:] / 16.0)).all()
    assert float(got[63]) == float(plain_f[63] / 16.0)
    blended = np.asarray(got[19:35] / plain_f[19:35])
    assert ((blended < 1) & (blended > 1 / 16)).all()
    assert (np.diff(blended) < 0).all()
    # the tables: cos and sin of position x frequency, times m
    positions = jnp.asarray([[0, 1, 8191, 8192, 32767]])
    cos, sin = decoder._rope_tables(positions, 128, 5e5, cfg.rope_scaling)
    angles = positions[..., None].astype(jnp.float32) * want
    m32 = np.float32(m)
    assert (np.asarray(cos[:, :, 0]) == np.asarray(jnp.cos(angles) * m32)).all()
    assert (np.asarray(sin[:, :, 0]) == np.asarray(jnp.sin(angles) * m32)).all()
    assert float(cos[0, 0, 0, 0]) == float(m32)
    # the reference's, from its own formula
    r_cos, r_sin = plain.rope_table(
        json.loads(CELL.read_text())["sizes"], "Y", positions
    )
    np.testing.assert_allclose(
        np.asarray(r_cos), np.asarray(cos), rtol=0, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(r_sin), np.asarray(sin), rtol=0, atol=2e-4
    )
    # and the plain table is what it was
    p_cos, _ = decoder._rope_tables(positions, 128, 5e5)
    assert (
        np.asarray(p_cos[:, :, 0])
        == np.asarray(jnp.cos(positions[..., None] * plain_f))
    ).all()


def test_tiny_table_blends_and_runs_past_its_original_length():
    cfg = _cfg()
    assert plain.yarn_range(_sizes(cfg)) == (0, 5)
    got = np.asarray(
        decoder._rope_frequencies(16, cfg.rope_theta, cfg.rope_scaling)
    )
    kept = np.asarray(decoder._rope_frequencies(16, cfg.rope_theta))
    ratio = got / kept
    assert ratio[0] == 1.0 and (ratio[5:] == 0.25).all()
    assert ((ratio[1:5] < 1) & (ratio[1:5] > 0.25)).all()
    assert cfg.max_seq > cfg.rope_original_max
    assert cfg.rope_scaling[4] == pytest.approx(0.1 * math.log(4) + 1)


def test_a_scaled_rope_without_layer_types_turns_every_layer():
    """The fields by themselves: every layer full and under the scaled
    table, which is what a stack of ``Y`` layers is to the reference."""
    cfg = _cfg(n_layer=2, layer_types="", attn_window=0)
    assert cfg.rope_kinds == ("scaled",) and cfg.kind_rope() == "scaled"
    params = _seeded(cfg, 3)
    sizes = dict(_sizes(cfg), layer_types="YY")
    checks, _ = SUITE.compare(cfg, params, sizes=sizes)
    assert all(ok for ok, _ in checks.values()), checks
    assert checks["logits_vs_reference"][1] < 1e-5
    assert tracing.counters()["attn.rope_tables"] == 1
    # the same weights as Y layers of a ``layer_types`` model
    kinds = dataclasses.replace(cfg, layer_types="YY")
    tokens = _batch()["tokens"]
    np.testing.assert_array_equal(
        np.asarray(decoder.forward(params, tokens, cfg)),
        np.asarray(decoder.forward(params, tokens, kinds)),
    )
    with pytest.raises(ValueError, match="mellum2: a scaled rope"):
        decoder.init_kv_cache(cfg, 2, 64)


# ---- the shares add up ----------------------------------------------------


def test_shares_of_the_expert_parallel_layer_add_up():
    """Four chips hold experts 0-1 ... 6-7 of one routed layer
    (``expert_offset`` 0, E/4, 2E/4, 3E/4); the reference's share is
    the program's, share by share."""
    SUITE.shares_add_up(4, 2, each=True, n_experts=8, expert_top_k=4)


# ---- the gradient ---------------------------------------------------------


def test_gradient_of_every_leaf_is_the_references(model):
    """d(ce_loss + moe_lb_loss)/d(params) through the stack scanned a
    period at a time under ``remat: full``, each kind under its own
    table, against ``jax.grad`` of the plain reference sent to the same
    experts."""
    SUITE.gradients_match(model, terms=("moe_lb_loss",))


# ---- counts, counters and refusals ----------------------------------------


def test_counts_are_the_files_arithmetic():
    """The published model's parameters, the cell's against the file's
    table, and the required FLOPs by hand at 32,768 tokens."""
    assert get_config("mellum2").num_params() == 12_149_923_072
    config = json.loads(CELL.read_text())
    cfg = _program_config(config)  # refuses a size the file misstates
    assert cfg.num_params() == 595_154_176
    assert "= 595,154,176 =" in config["parameters"]["total"]
    shapes = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    assert sum(
        int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)
    ) == 595_154_176
    terms = plain.required_terms(config["sizes"], 32768)
    assert terms["multiplied_params"] == 191_692_800
    assert terms["attention_pair_channels"] == 79_497_408
    assert cfg.executed_span(32768, "S") == 1008.015625
    assert cfg.executed_span(32768, "Y") == 16384.5
    assert cfg.flops_per_token(32768) == 2_104_125_696 == (
        6 * 191_692_800 + 12 * 79_497_408
    )
    # the published widths, and the four cuts
    assert (cfg.d_model, cfg.n_head, cfg.kv_heads, cfg.head_dim) == (
        2304, 32, 4, 128
    )
    assert (cfg.expert_width, cfg.n_experts, cfg.expert_top_k) == (896, 64, 8)
    assert sorted(config["reduced"]) == [
        "max_position_embeddings", "num_experts", "num_hidden_layers",
        "vocab_size",
    ]
    assert config["program"]["optimizer"]["warmup_steps"] == 100
    # what keeps the held rows still by seed (PERF.md section 6, PR 70)
    assert cfg.embed_init_std == 1.0
    assert "embed_init_std" in config["assumed"]["weights"]


def _counters_of(cfg, seq=64):
    mesh = build_mesh(MeshConfig(dp=-1), devices=jax.devices()[:1])
    opt = make_optimizer(learning_rate=1e-4, warmup_steps=2, decay_steps=10)
    builder = TrainStepBuilder(cfg, mesh, opt)
    state = abstract_train_state(cfg, mesh, opt, comm=builder.comm_resolved)
    batch = {
        k: jax.ShapeDtypeStruct((1, seq), jnp.int32)
        for k in ("tokens", "targets")
    }
    tracing._counters.clear()
    jax.eval_shape(builder.step_fn, state, batch)
    return dict(tracing.counters())


def test_step_counts_its_tables_and_layers_by_kind():
    counters = _counters_of(
        _cfg(n_layer=8, layer_types="SSSY" * 2, dtype="bfloat16")
    )
    assert counters["attn.rope_tables"] == 2
    assert counters["attn.scaled_rope_layers"] == 2
    assert counters["attn.window_layers"] == 6
    assert counters["attn.full_layers"] == 2


@pytest.mark.parametrize(
    "name,over",
    [
        ("trinity-mini", dict(n_layer=5, n_dense_layer=1,
                              layer_types="SSSSF", attn_window=8)),
        ("olmoe-1b-7b", dict(n_layer=2, n_kv_head=4)),
        ("mistral-7b", dict(n_layer=2, n_kv_head=2, attn_window=8)),
    ],
)
def test_a_model_of_one_rope_builds_one_table(name, over):
    cfg = get_config(
        name, d_model=64, n_head=4, d_ff=128, vocab_size=256, max_seq=64,
        **over,
    )
    assert cfg.rope_kinds == ("plain",) and cfg.rope_scaling is None
    counters = _counters_of(cfg)
    assert counters["attn.rope_tables"] == 1
    assert counters.get("attn.scaled_rope_layers", 0) == 0


def test_kinds_are_one_table():
    from dlrover_tpu.models.config import ATTN_KINDS

    cfg = _cfg()
    assert set(ATTN_KINDS) == {"S", "F", "Y"}
    assert [cfg.kind_window(k) for k in "SFY"] == [8, 0, 0]
    assert [cfg.kind_rope(k) for k in "SFY"] == ["plain", "", "scaled"]
    assert cfg.rope_kinds == ("plain", "scaled")
    assert decoder.attention_kinds(cfg) == ("S", "Y")
    assert get_config("gpt2-1.5b").rope_kinds == ()


@pytest.mark.parametrize(
    "over,why",
    [
        (dict(layer_types="SSSYS"), "names each of the n_layer layers"),
        (dict(layer_types="SSSX"), r"Y \(full, scaled rope\)"),
        (dict(rope_factor=0.0), "rope_factor where one is turned"),
        (dict(attn_window=0), "attn_window set where a kind has"),
        (dict(rope_original_max=0), "a scaled rope needs"),
        (dict(rope_factor=1.0), "a scaled rope needs"),
        (dict(rope_beta_slow=64.0), "a scaled rope needs"),
        (dict(layer_types="", pos="learned"), "a scaled rope needs"),
    ],
)
def test_config_refuses(over, why):
    with pytest.raises(ValueError, match=why):
        _cfg(**over)


@pytest.mark.parametrize("std", [0.02, 1.0])
def test_embeddings_are_drawn_at_the_configured_std(std):
    """``embed_init_std`` scales the embeddings' draw and nothing else:
    0.02, the default, is the init every other model has, bit for bit,
    and the cell's 1.0 is the same draw fifty times larger."""
    key = jax.random.key(7)
    base = decoder.init(key, _cfg())
    got = decoder.init(key, _cfg(embed_init_std=std))
    want = jax.random.normal(jax.random.split(key, 16)[0], (256, 64)) * std
    np.testing.assert_array_equal(got["embed"]["tokens"], want)
    same = jax.tree.map(
        lambda a, b: bool((a == b).all()),
        dict(got, embed=base["embed"]), base,
    )
    assert jax.tree.all(same)
    assert get_config("mistral-7b").embed_init_std == 0.02
    assert _cfg().embed_init_std == 0.02  # the preset's; the cell sets 1.0
    with pytest.raises(ValueError, match="embed_init_std"):
        _cfg(embed_init_std=0.0)


@pytest.mark.parametrize("path", sorted(REFUSALS))
def test_cache_and_generate_paths_refuse_the_model(model, path):
    SUITE.refuses(model, path, "mellum2: a trunk whose")
