"""LFM2's architecture (``lfm2-8b-a1b``: a gated short convolution as a
``layer_pattern`` part, two leading conv + dense-MLP layers, a
grouped-query attention with per-head norms and rope in one layer of
four, sigmoid top-k renormalised over experts of which a part is held)
against the benchmark's plain reference, at a tiny size on the CPU with
seeded weights: the comparison the chip's cell is judged by
(``benchmarks/lib/routed.py``), one defect per thing the configuration
states, the shares of an expert-parallel layer adding up to the uncut
layer, the gradient, the counts, the counters and the paths that refuse
the model."""

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from reference_suite import REFUSALS, Suite, seeded

from benchmarks.references import lfm2_moe_plain as plain
from benchmarks.runners.train import _program_config
from benchmarks.tests import lfm2_defects
from dlrover_tpu.models import decoder, get_config
from dlrover_tpu.observability import tracing
from dlrover_tpu.ops import pallas_attention, ssd

# two conv + dense layers (a scanned run of two), an attention + routed
# layer and a conv + routed one
TINY = dict(
    n_layer=4, layer_pattern="C-C-*eCe", d_model=64, n_head=4, n_kv_head=2,
    d_ff=128, vocab_size=256, max_seq=64, rope_theta=100.0, d_expert=32,
    n_experts=8, expert_top_k=2, n_experts_held=4, expert_offset=0,
    remat="full", dtype="float32",
)
SIZE_KEYS = (
    "n_layer", "layer_pattern", "d_model", "n_head", "n_kv_head", "head_dim",
    "vocab_size", "rope_theta", "norm_eps", "conv_kernel", "d_ff", "d_expert",
    "n_experts", "n_experts_held", "expert_offset", "expert_top_k",
    "moe_renorm_topk", "routed_scaling_factor", "remat",
)
# float32 on both sides: far inside the chip's limits (4e-2, 2.5e-2,
# 2e-4), so that a defect shows by orders of magnitude. What is left is
# the order of float32 sums (the stacked experts against one at a time,
# the flash blocks against whole rows) and the renormalisation's guard,
# ``max(sum, 1e-9)`` against the published ``sum + 1e-6``: 1e-5 relative
# on a routed part's output at the most
TOLERANCES = (1e-3, 1e-3, 1e-4)
CHECKS = [
    "choices_valid", "routing_regret", "logits_vs_reference",
    "logits_rms_vs_reference", "loss_vs_reference", "loss_vs_free_reference",
]
CELL = (
    pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    / "lfm2-8b-a1b-ep4-1chip.json"
)


# every norm scale and per-head scale drawn around 1: at 1 a scale left
# out could not show. The head is the token table's own (tied), which
# predicts the doubled tokens
SUITE = Suite(
    "lfm2-8b-a1b", plain, TINY, SIZE_KEYS, seq=64, q_block=16,
    tolerances=TOLERANCES, norm_eps=None,
    make=lambda cfg, seed: seeded(
        cfg, seed, scales=jax.random.key(seed + 100), head=False
    ),
)
_cfg = SUITE.cfg
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
    assert len(record["moved_by_layer"]) == cfg.n_routed_layer == 2
    assert record["reference_terms"] == {}  # no term beside the loss


def test_the_cells_six_layers_are_the_layers_one_by_one():
    """``C-C-*eCeCeCe``: the scanned pair of dense layers (stack
    ``conv``), then the routed layers unrolled (stack ``conv.1``): what
    the reference's twelve parts give one after another."""
    cfg = _cfg(n_layer=6, layer_pattern="C-C-*eCeCeCe")
    assert decoder._pattern_runs(cfg.layer_pattern) == [
        ("C-", 2), ("*e", 1), ("Ce", 1), ("Ce", 1), ("Ce", 1)
    ]
    assert [s[0] for s in decoder._pattern_stacks(cfg.layer_pattern)] == [
        "attention", "mlp", "conv", "conv.1", "experts",
    ]
    params = _seeded(cfg, 5)
    assert params["layers"]["conv"]["conv"]["w_in"].shape == (2, 64, 192)
    assert params["layers"]["conv.1"]["conv"]["conv_w"].shape == (3, 3, 64)
    checks, record = SUITE.compare(cfg, params)
    assert all(ok for ok, _ in checks.values()), checks
    assert checks["logits_vs_reference"][1] < 1e-5
    assert len(record["moved_by_layer"]) == 4


def test_a_reference_side_in_bf16_does_not_pass(model):
    """The reference-side path in the nearest precision below: the
    program's own bf16 forward held to the float32 reference at THESE
    limits fails by the logits (and passes at the chip's, which are set
    for it)."""
    cfg, params = model
    low = dataclasses.replace(cfg, dtype="bfloat16")
    checks, _ = SUITE.compare(low, params)
    assert not checks["logits_rms_vs_reference"][0], checks
    from benchmarks.runners import train

    chip = (train.LOGIT_TOL, train.LOGIT_RMS_TOL, train.LOSS_TOL)
    checks, _ = SUITE.compare(low, params, tolerances=chip)
    assert checks["logits_rms_vs_reference"][0], checks


# ---- one defect per thing the configuration states ------------------------


DEFECTS = {
    # the eight the chip's cell is held to ...
    **{name: lambda patch, cfg, plant=plant: plant(patch)
       for name, plant in lfm2_defects.PLANT.items()},
    # ... and others a configuration can state
    "four_taps": dict(conv_kernel=4),
    "theta_of_another_model": dict(rope_theta=10000.0),
    "scaled_weights": dict(routed_scaling_factor=2.0),
}


@pytest.mark.parametrize("defect", sorted(DEFECTS))
def test_comparison_catches(monkeypatch, model, defect):
    plant, program_params = DEFECTS[defect], None
    if isinstance(plant, dict) and "conv_kernel" in plant:
        # a fourth tap, the oldest, on every conv

        def wider(path, leaf):
            if path[-1].key != "conv_w":
                return leaf
            return jnp.concatenate([leaf[:, :1], leaf], axis=1)

        program_params = jax.tree_util.tree_map_with_path(wider, model[1])
    caught_by = lfm2_defects.CAUGHT_BY.get(defect, lfm2_defects.LOGITS)
    SUITE.catches(monkeypatch, model, plant, caught_by, program_params)


# ---- the gated conv, both bodies, as the mixer calls it -------------------


def test_gated_conv_is_the_definition_token_by_token():
    """Another algorithm, in float64 by hand: z = B * x, ``c_t = Σ_j
    w_j z_{t-K+1+j}``, y = C * c; nothing before a sequence's first
    token, each row of the batch by itself."""
    k = jax.random.split(jax.random.key(3), 2)
    proj = jax.random.normal(k[0], (2, 16, 3 * 8))
    w = jax.random.normal(k[1], (3, 8))
    got = np.asarray(ssd.gated_conv(proj, w), np.float64)
    p, taps = np.asarray(proj, np.float64), np.asarray(w, np.float64)
    gate_in, gate_out, x = p[..., :8], p[..., 8:16], p[..., 16:]
    z = gate_in * x
    want = np.zeros_like(z)
    for t in range(16):
        for j in range(3):
            if 0 <= (src := t - 2 + j):
                want[:, t] += taps[j] * z[:, src]
    np.testing.assert_allclose(got, gate_out * want, rtol=0, atol=2e-6)


@pytest.mark.parametrize("interpreted", [False, True])
def test_mixer_goes_through_the_one_door(monkeypatch, interpreted):
    """The benchmark plants its defects by standing in for
    ``ssd.gated_conv(proj, weight, mesh=None)``: the mixer calls it with
    the whole in-projection and the taps, and nothing else, whichever
    body is behind it; the counters say which."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", interpreted)
    seq = 512 if interpreted else 64
    cfg = _cfg(d_model=128, max_seq=seq, n_head=4, n_kv_head=2)
    seen = []
    sound = ssd.gated_conv

    def stand_in(proj, weight, mesh=None):
        seen.append((proj.shape, weight.shape, mesh))
        return sound(proj, weight, mesh=mesh)

    monkeypatch.setattr(ssd, "gated_conv", stand_in)
    params = jax.eval_shape(lambda k: decoder.init(k, cfg), jax.random.key(0))
    tracing._counters.clear()
    jax.eval_shape(
        lambda p, t: decoder.forward(p, t, cfg), params,
        jax.ShapeDtypeStruct((2, seq), jnp.int32),
    )
    assert seen and all(
        args == ((2, seq, 384), (3, 128), None) for args in seen
    )
    counters = tracing.counters()
    assert counters["conv.layers"] == 3
    assert counters["conv.kernel_layers"] == 3 * int(interpreted)
    assert "ssm.conv_in_kernel" not in counters


# ---- the shares add up ----------------------------------------------------


def test_shares_of_the_expert_parallel_layer_add_up():
    """Four chips hold experts 0-1 ... 6-7 of one routed layer
    (``expert_offset`` 0, E/4, 2E/4, 3E/4): a token's sigmoid weights
    are renormalised over all it chose, and the reference's share is
    the program's, share by share."""
    SUITE.shares_add_up(4, 2, each=True, n_experts=8, expert_top_k=4)


# ---- the gradient ---------------------------------------------------------


def test_gradient_of_every_leaf_is_the_references(model):
    """d(loss)/d(params) through the scanned pair and the unrolled
    layers under ``remat: full`` against ``jax.grad`` of the plain
    reference sent to the same experts: every leaf, the taps and the
    tied table among them, to 2e-4 of the leaf's largest entry (float32
    sums in another order)."""
    SUITE.gradients_match(model)


# ---- counts, counters and refusals ----------------------------------------


def test_counts_are_the_files_arithmetic():
    """The published model's parameters, the cell's against the file's
    table, and the required FLOPs by hand at 4,096 tokens."""
    full = get_config("lfm2-8b-a1b")
    assert full.num_params() == 8_339_929_856
    assert full.layer_pattern[:12] == "C-C-*eCeCeCe"
    assert (full.layer_pattern.count("C"), full.layer_pattern.count("*")) == (
        18, 6
    )
    config = json.loads(CELL.read_text())
    kinds = "".join(
        {"conv": "C", "full_attention": "*"}[k] for k in config["layer_types"]
    )
    assert kinds == full.layer_pattern[::2]
    cfg = _program_config(config)  # refuses a size the file misstates
    assert cfg.num_params() == 568_647_808
    assert "= 568,647,808 =" in config["parameters"]["total"]
    shapes = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    assert sum(
        int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)
    ) == 568_647_808
    terms = plain.required_terms(config["sizes"], 4096)
    assert terms["multiplied_params"] == 260_308_992
    assert terms["attention_pair_channels"] == 4_195_328
    assert cfg.flops_per_token(4096) == 1_612_197_888 == (
        6 * 260_308_992 + 12 * 4_195_328
    )
    # the published widths, and the four cuts
    assert (cfg.d_model, cfg.n_head, cfg.kv_heads, cfg.head_dim) == (
        2048, 32, 8, 64
    )
    assert (cfg.d_ff, cfg.expert_width, cfg.n_experts, cfg.expert_top_k) == (
        7168, 1792, 32, 4
    )
    assert (cfg.conv_kernel, cfg.rope_theta, cfg.norm_eps) == (3, 1e6, 1e-5)
    assert sorted(config["reduced"]) == [
        "max_position_embeddings", "num_experts", "num_hidden_layers",
        "vocab_size",
    ]
    assert config["program"]["optimizer"]["warmup_steps"] == 100
    assert cfg.embed_init_std == 1.0 and cfg.tie_embeddings
    assert "embed_init_std" in config["assumed"]["weights"]


def test_conv_part_counts_its_matrices_and_taps():
    cfg = _cfg()
    held, met = cfg._part_counts()["C"]
    assert held == 4 * 64 * 64 + 3 * 64 + 64 and met == 4 * 64 * 64
    shapes = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    assert sum(
        int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)
    ) == cfg.num_params()
    axes = decoder.logical_axes(cfg)
    assert axes["layers"]["conv"]["conv"] == {
        "w_in": ("layers", "embed", "mlp"),
        "conv_w": ("layers", None, "mlp"),
        "w_out": ("layers", "mlp", "embed"),
    }


@pytest.mark.parametrize(
    "over,why",
    [
        (dict(conv_kernel=0), "a C part needs conv_kernel"),
        (dict(conv_kernel=1), "a C part needs conv_kernel"),
        (dict(layer_pattern="C-C-*eCx"), "gated short convolution"),
        (dict(n_dense_layer=1), "no dense prefix"),
    ],
)
def test_config_refuses(over, why):
    with pytest.raises(ValueError, match=why):
        _cfg(**over)


CACHE_PATHS = sorted(set(REFUSALS) - {"prefill_chunk", "verify_chunk"})


@pytest.mark.parametrize("path", CACHE_PATHS)
def test_cache_and_generate_paths_refuse_the_model(model, path):
    SUITE.refuses(
        model, path, r"lfm2-8b-a1b: gated-short-convolution \(C\) layers"
    )
