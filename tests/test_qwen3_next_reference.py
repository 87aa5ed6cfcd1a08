"""Qwen3-Next's architecture (``qwen3-next``: layers of a mixer and a
ROUTED part by a pattern — a gated-delta-rule mixer through the chunked
rule in three of four, a gated attention with partial rope in the
fourth, zero-centred norms, softmax top-k experts beside a gated shared
one, a part of them held) against the benchmark's plain reference, at a
tiny size on the CPU with seeded weights whose norm offsets are not
zero: the comparison the chip's cell is judged by
(``benchmarks/lib/routed.py``), the read-out's mean square, the shares
of an expert-parallel layer adding up to the uncut layer, the defects
the comparison has to catch, the gradient, the parameter count and the
FLOPs, and the paths that refuse the model."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from reference_suite import LOGITS, Suite, seeded

from benchmarks.lib import flops as flopslib
from benchmarks.references import qwen3_next_plain as plain
from benchmarks.tests import qwen3next_defects as defects
from dlrover_tpu.models import decoder, get_config
from dlrover_tpu.models.config import pattern_layers, pattern_parts
from dlrover_tpu.parallel import moe

# two periods; 2 key heads shared by 4 value heads of 8 (a sequence of
# 72 is a chunk of 64 and a padded one); heads of 16 with rope on their
# first 4 channels; top-2 of 8 experts with 4 held
TINY = dict(
    n_layer=8, layer_pattern="GeGeGe*e" * 2, d_model=64, n_head=4,
    n_kv_head=2, d_head=16, vocab_size=256, max_seq=128, gdn_key_heads=2,
    gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8,
    n_experts=8, expert_top_k=2, d_expert=32, d_shared_expert=32,
    n_experts_held=4, expert_offset=0, remat="full", dtype="float32",
)
SIZE_KEYS = (
    "n_layer", "layer_pattern", "d_model", "n_head", "n_kv_head", "d_head",
    "vocab_size", "partial_rotary_factor", "rope_theta", "gdn_key_heads",
    "gdn_value_heads", "gdn_key_dim", "gdn_value_dim", "conv_kernel",
    "n_experts", "n_experts_held", "expert_offset", "expert_top_k",
    "d_expert", "d_shared_expert", "moe_renorm_topk",
)
SEQ = 72
# one period: every layer kind the model has. The defect and gradient
# cases run on it; what two periods add (stacking, one row of choices a
# layer of eight) is ``test_program_matches_the_plain_reference``'s
PERIOD = dict(n_layer=4, layer_pattern="GeGeGe*e")
# float32 on both sides (the suite's tolerances): far inside the chip's
# limits, so that a defect shows by orders of magnitude. Norm offsets
# and the mixer's output-norm scale are off their initial 0 and 1; A_log,
# dt_bias and the conv's taps are drawn, not constants, by
# ``decoder.init`` itself
SUITE = Suite(
    "qwen3-next", plain, TINY, SIZE_KEYS, seq=SEQ, q_block=8,
    make=lambda cfg, seed: seeded(
        cfg, seed, scales=jax.random.key(seed + 1), by_index=True
    ),
)
_cfg, _sizes, _batch = SUITE.cfg, SUITE.sizes, SUITE.batch


@pytest.fixture(scope="module")
def model():
    return SUITE.model()


@pytest.fixture(scope="module")
def period():
    return SUITE.model(**PERIOD)


def test_program_matches_the_plain_reference(model):
    cfg, params = model
    checks, record = SUITE.compare(cfg, params)
    assert list(checks) == [
        "choices_valid", "routing_regret", "logits_vs_reference",
        "logits_rms_vs_reference", "loss_vs_reference",
        "gdn_readout_ms_vs_reference", "loss_vs_free_reference",
    ]
    assert all(ok for ok, _ in checks.values()), checks
    assert checks["routing_regret"][1] == 0.0
    assert checks["logits_vs_reference"][1] < 1e-4
    assert checks["gdn_readout_ms_vs_reference"][1] < 1e-5
    # one row of choices per routed block: every layer has one
    assert len(record["moved_by_layer"]) == cfg.n_routed_layer == 8


def test_forward_hands_over_every_choice_and_the_readout(model):
    cfg, params = model
    ids = np.asarray(SUITE.forward(cfg, params)[1])
    assert ids.dtype == np.int32
    assert ids.shape == (8, 2, SEQ, cfg.expert_top_k)
    assert ids.max() >= cfg.n_experts_held and ids.max() < cfg.n_experts
    metrics = SUITE.losses(cfg, params)
    assert metrics["moe_held_rows"] == pytest.approx(
        (ids < cfg.n_experts_held).sum() / 8
    )
    assert set(metrics) >= {"loss", "gdn_readout_ms", "moe_held_rows"}
    with jax.default_matmul_precision("highest"):
        _, _, readout = jax.jit(
            lambda p, t: plain.forward(p, t, _sizes(cfg), 8)
        )(params, _batch()["tokens"])
    assert metrics["gdn_readout_ms"] == pytest.approx(
        float(readout), rel=1e-5
    )


def test_a_layer_is_a_mixer_and_a_routed_part():
    """``e`` is a layer's second part as ``-`` is; ``E`` stays a layer
    by itself; one pattern has one of the two."""
    assert pattern_parts("GeGe*e") == ["Ge", "Ge", "*e"]
    assert pattern_layers("GeGeGe*e" * 12) == 48
    assert pattern_parts("MEM*E") == ["M", "E", "M", "*", "E"]
    assert pattern_parts("m-*-") == ["m-", "*-"]
    cfg = get_config("qwen3-next")
    assert cfg.n_layer == 48 and cfg.n_routed_layer == 48
    assert cfg.layer_pattern.count("G") == 36
    assert [i for i in range(48) if cfg.layer_pattern[2 * i] == "*"] == [
        i for i in range(48) if (i + 1) % 4 == 0
    ]
    with pytest.raises(ValueError, match="E .* or by e"):
        _cfg(layer_pattern="GeGeGe*EGeGeGeGe", n_layer=9)
    # no unit with a routed part is scanned: its choices ride out
    assert all(n == 1 for _, n in decoder._pattern_runs(cfg.layer_pattern))


def test_rope_turns_a_quarter_of_a_head():
    cfg = get_config("qwen3-next")
    assert cfg.head_dim == 256 and cfg.rope_dim == 64
    assert get_config("keye-vl-2.0").rope_dim == 128
    assert get_config("glm-4.7-flash").rope_dim == 64
    x = jax.random.normal(jax.random.key(0), (1, 6, 2, 16))
    positions = jnp.arange(6)[None]
    rope = decoder._rope_tables(positions, 4, 1e4)
    got = decoder._rope(x, rope)
    np.testing.assert_array_equal(
        np.asarray(got[..., 4:]), np.asarray(x[..., 4:])
    )
    np.testing.assert_allclose(
        np.asarray(got[..., :4]), np.asarray(decoder._rope(x[..., :4], rope)),
        rtol=1e-6,
    )
    assert not np.allclose(np.asarray(got[:, 1:, :, :4]),
                           np.asarray(x[:, 1:, :, :4]))
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        _cfg(partial_rotary_factor=0.3)


def test_norm_offsets_start_at_zero_and_the_mixers_norm_at_one():
    cfg = _cfg()
    init = jax.jit(decoder.init, static_argnums=1)
    params = init(jax.random.key(0), cfg)
    layers = params["layers"]
    for scale in (
        params["final_norm"]["scale"], layers["gdn"]["ln"]["scale"],
        layers["experts"]["ln"]["scale"], layers["attention"]["ln"]["scale"],
        layers["attention"]["attn"]["q_norm"]["scale"],
        layers["attention"]["attn"]["k_norm"]["scale"],
    ):
        np.testing.assert_array_equal(np.asarray(scale), 0.0)
    norm = layers["gdn"]["gdn"]["norm"]["scale"]
    assert norm.shape == (6, cfg.gdn_value_dim)
    np.testing.assert_array_equal(np.asarray(norm), 1.0)
    a = np.exp(np.asarray(layers["gdn"]["gdn"]["a_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 0
    # a model that is not zero-centred keeps its ones
    other = init(
        jax.random.key(0),
        get_config("jamba2-3b", n_layer=2, layer_pattern="m-*-", d_model=64,
                   n_head=4, d_head=16, d_ff=64, vocab_size=64,
                   mamba_dt_rank=4),
    )
    np.testing.assert_array_equal(
        np.asarray(other["final_norm"]["scale"]), 1.0
    )


# ---- defects the comparison has to catch ---------------------------------


def _conv_looks_ahead(patch, cfg):
    from dlrover_tpu.ops import ssd

    conv = ssd.causal_conv
    patch(
        ssd, "causal_conv",
        lambda x, w, b: jnp.roll(conv(x, w, b), -1, axis=1),
    )


def _held_only_weights(patch, cfg):
    """Combine weights normalised over the chosen experts that are HERE."""

    def weights(probs, k, renormalize):
        vals, idx = jax.lax.top_k(probs, k)
        here = idx < cfg.n_experts_held
        total = jnp.sum(jnp.where(here, vals, 0.0), -1, keepdims=True)
        return vals / jnp.maximum(total, 1e-9), idx

    patch(moe, "_topk_weights", weights)


DEFECTS = {
    **{
        name: (lambda patch, cfg, plant=plant: plant(patch),
               defects.CAUGHT_BY[name])
        for name, plant in defects.PLANT.items()
    },
    "conv_looks_ahead": (_conv_looks_ahead, LOGITS),
    "weights_over_held_experts_only": (_held_only_weights, LOGITS),
    "sigmoid_for_softmax": (dict(moe_score="sigmoid"), LOGITS),
    "weights_not_renormalised": (dict(moe_renorm_topk=False), LOGITS),
    "attention_gate_left_out": (dict(attn_gate=False), LOGITS),
}


@pytest.mark.parametrize("defect", sorted(DEFECTS))
def test_comparison_catches(monkeypatch, period, defect):
    SUITE.catches(monkeypatch, period, *DEFECTS[defect])


def test_a_scale_of_the_readout_shows_in_its_mean_square(monkeypatch, period):
    """Why ``gdn_readout_ms`` is a term: q's 1 / sqrt(channels) left
    out makes every read-out sqrt(Dk) times too large, which the norm a
    head behind it takes out again but for its eps; the mean square
    reads Dk times the reference's."""
    cfg, params = period
    defects.PLANT["query_scale_left_out"](monkeypatch.setattr)
    checks, _ = SUITE.compare(cfg, params, planted=True)
    ok, value = checks["gdn_readout_ms_vs_reference"]
    assert not ok
    assert value == pytest.approx(cfg.gdn_key_dim - 1, rel=0.1)


# ---- the shares add up ----------------------------------------------------


def test_shares_of_the_expert_parallel_layer_add_up():
    """Two chips hold experts 0-3 and 4-7 of one routed block; the
    GATED shared expert is added once."""
    whole, full = SUITE.shares_add_up(2, 4)
    assert full["shared"]["w_own_gate"].shape == (whole.d_model, 1)


# ---- the gradient -----------------------------------------------------------


def test_gradient_of_every_kind_of_parameter_is_the_references(period):
    """d(loss)/d(params) through the mixers' chunked rule and its
    hand-written inverse derivative, the gated attention, the held
    experts' cut dispatch and combine and the shared expert's gate,
    against ``jax.grad`` of the plain reference sent to the same
    experts (float32 on both sides; eight parts amplify its
    rounding)."""
    SUITE.gradients_match(period, atol=2e-3)


# ---- the parameters and the FLOPs -------------------------------------------


def _cell_cfg():
    return get_config(
        "qwen3-next", n_layer=4, layer_pattern="GeGeGe*e",
        n_experts_held=32, vocab_size=18992, max_seq=16384,
    )


def test_num_params_is_the_tables_count():
    """ISSUE 63's table, part by part: the mixer, the attention, the
    routed block as held, the embedding and the head's slice."""
    cfg = _cell_cfg()
    held, _ = zip(*(cfg._part_counts()[c] for c in "G*e"))
    d = cfg.d_model
    assert held[0] - d == 33_718_464
    assert held[1] - d == 27_263_488
    assert held[2] - d == 104_859_648
    # one period; ISSUE 63's two are 1,173,540,992
    assert cfg.num_params() == 625_667_136
    two = dataclasses.replace(
        cfg, n_layer=8, layer_pattern=cfg.layer_pattern * 2
    )
    assert two.num_params() == 1_173_540_992
    params = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    assert sum(
        int(np.prod(t.shape)) for t in jax.tree.leaves(params)
    ) == cfg.num_params()
    tiny = _cfg()
    counted = jax.eval_shape(lambda: decoder.init(jax.random.key(0), tiny))
    assert sum(
        int(np.prod(t.shape)) for t in jax.tree.leaves(counted)
    ) == tiny.num_params()


@pytest.mark.parametrize("size", ["tiny", "cell"])
def test_flops_per_token_is_the_references_required_terms(size):
    cfg, seq = (_cfg(), SEQ) if size == "tiny" else (_cell_cfg(), 16384)
    terms = SUITE.flops_terms(cfg, seq)
    if size == "cell":
        rule = 3 * plain.gdn_multiply_adds(_sizes(cfg))
        assert rule == 3 * 1_835_008
        assert terms["multiplied_params"] - rule == 191_864_832
        assert flopslib.flops_of(terms) == 1_586_896_896


# ---- the published sizes, and the paths that refuse the model --------------


def test_the_published_pattern_traces_whole():
    """48 layers, 512 experts, the full vocabulary: shapes only."""
    cfg = get_config("qwen3-next", remat="full")
    params = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    layers = params["layers"]
    assert layers["gdn"]["gdn"]["w_qkvz"].shape == (36, 2048, 12288)
    assert layers["gdn"]["gdn"]["w_ba"].shape == (36, 2048, 64)
    assert layers["gdn"]["gdn"]["conv_w"].shape == (36, 4, 8192)
    assert layers["attention"]["attn"]["wq"].shape == (12, 2048, 4096)
    assert layers["attention"]["attn"]["wg"].shape == (12, 2048, 4096)
    assert layers["attention"]["attn"]["wk"].shape == (12, 2048, 512)
    assert layers["experts"]["moe"]["w_up"].shape == (48, 512, 2048, 512)
    assert layers["experts"]["moe"]["shared"]["w_own_gate"].shape == (
        48, 2048, 1
    )
    batch = {
        k: jax.ShapeDtypeStruct((1, 128), jnp.int32)
        for k in ("tokens", "targets")
    }
    loss, metrics = jax.eval_shape(
        lambda p, b: decoder.loss_fn(p, b, cfg), params, batch
    )
    assert loss.shape == () and "gdn_readout_ms" in metrics
    aux = jax.eval_shape(
        lambda p, t: decoder.forward(p, t, cfg, return_aux=True)[1],
        params, batch["tokens"],
    )
    assert aux["moe_choices"].shape == (48, 1, 128, 10)


def test_cache_paths_refuse_the_model_by_name(model):
    assert "gated-delta-rule" in model[0].train_only
    for path in ("prefill", "sample"):
        SUITE.refuses(model, path, "gated-delta-rule")


@pytest.mark.parametrize(
    "widths,interpret,kernel_layers",
    [(128, True, 3), (128, False, 0), (8, True, 0)],
    ids=["kernels", "off-the-chip", "other-widths"],
)
def test_counters_say_which_body_the_linear_layers_took(
    monkeypatch, widths, interpret, kernel_layers
):
    """``gdn.layers`` counts the pattern's linear layers and
    ``gdn.kernel_layers`` those whose rule runs the Pallas kernels: at
    the cell's pattern all 3 with key and value channels of 128 where a
    TPU (here the interpreter) would run them, none off the chip or at
    widths off the 128 lanes — where the trace holds no kernel of the
    rule's."""
    from dlrover_tpu.observability import tracing
    from dlrover_tpu.ops import pallas_attention

    monkeypatch.setattr(pallas_attention, "INTERPRET", interpret)
    cfg = _cfg(
        n_layer=4, layer_pattern="GeGeGe*e", gdn_key_heads=1,
        gdn_value_heads=2, gdn_key_dim=widths, gdn_value_dim=widths,
    )
    params = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    tracing._counters.clear()
    text = str(jax.make_jaxpr(
        lambda p, t: decoder.forward(p, t, cfg)
    )(params, _batch()["tokens"]))
    counters = tracing.counters()
    assert counters["gdn.layers"] == 3
    assert counters["gdn.kernel_layers"] == kernel_layers
    assert ("name=gdn_fwd" in text) == bool(kernel_layers)
