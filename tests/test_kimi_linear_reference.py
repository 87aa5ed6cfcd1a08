"""Kimi-Linear's architecture (``kimi-linear``: a first layer of a KDA
mixer and a dense MLP, then layers of a mixer and a ROUTED part by a
pattern — a delta rule with a decay a key channel through the chunked
rule in three of four, latent attention without positions, q at full
rank and values narrower than the scores in the fourth, sigmoid top-k
experts renormalised and scaled beside a shared one, a part of them
held) against the benchmark's plain reference, at a tiny size on the CPU
with seeded weights whose norm scales are not one: the comparison the
chip's cell is judged by (``benchmarks/lib/routed.py``), the read-out's
mean square, the shares of an expert-parallel layer adding up to the
uncut layer, the defects the comparison has to catch, the gradient, the
parameter count and the FLOPs against the configuration file's
arithmetic, latent attention's new shapes against ``mha_reference``, and
GLM's preset building what it built."""

import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from reference_suite import LOGITS, Suite, seeded

from benchmarks.lib import flops as flopslib
from benchmarks.references import kimi_linear_plain as plain
from benchmarks.tests import kimilinear_defects as defects
from dlrover_tpu.models import decoder, get_config
from dlrover_tpu.models.config import pattern_layers, pattern_parts
from dlrover_tpu.ops.attention import mha_reference

# the cell's five layers; 4 heads of 8 key and 8 value channels in the
# mixer (a sequence of 72 is a chunk of 64 and a padded one: sub-blocks
# and their matmuls both run), gates through a rank of 8; 4 latent heads
# scoring over 8 + 4 channels with values of 8 through a latent of 16;
# top-2 of 8 experts with 4 held
TINY = dict(
    n_layer=5, layer_pattern="K-KeKe*eKe", d_model=64, d_ff=96, n_head=4,
    n_kv_head=4, vocab_size=256, max_seq=128, kda_heads=4, kda_head_dim=8,
    kda_gate_rank=8, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, n_experts=8, expert_top_k=2,
    d_expert=32, n_experts_held=4, expert_offset=0, remat="full",
    dtype="float32",
)
SIZE_KEYS = (
    "n_layer", "layer_pattern", "d_model", "d_ff", "n_head", "vocab_size",
    "kda_heads", "kda_head_dim", "kda_gate_rank", "conv_kernel",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "n_experts", "n_experts_held", "expert_offset",
    "expert_top_k", "d_expert", "n_shared_experts", "moe_renorm_topk",
    "routed_scaling_factor", "norm_eps",
)
# (max, rms, loss) with float32 on both sides. A sound program reads
# 2e-6 / 1e-6 / 1e-7 here (the chunked rule against the recurrence, the
# sorted dispatch against a loop over experts): held 100 times over. A
# mixer whose values BETWEEN its matmuls are rounded to bf16 (its
# projection's output, q, k and v into the rule, the read-out, the gated
# norm's output — what ``_kda_block`` keeps float32, PR 63's finding)
# reads 6e-3 by rms at these five layers and fails both logit limits
# (``test_bf16_between_the_mixers_matmuls_fails``)
TOLERANCES = (1e-3, 2e-4, 1e-4)
SEQ = 72
# norm scales off their initial 1: at 1 a program that norms after the
# gate, or leaves a norm out, could not be told from a sound one. A_log,
# dt_bias and the conv's taps are drawn, not constants, by
# ``decoder.init`` itself
SUITE = Suite(
    "kimi-linear", plain, TINY, SIZE_KEYS, seq=SEQ, q_block=8,
    tolerances=TOLERANCES, norm_eps=None,
    make=lambda cfg, seed: seeded(
        cfg, seed, scales=jax.random.key(seed + 1), by_index=True
    ),
)
_cfg, _sizes, _batch = SUITE.cfg, SUITE.sizes, SUITE.batch


@pytest.fixture(scope="module")
def model():
    return SUITE.model()


def test_program_matches_the_plain_reference(model):
    cfg, params = model
    checks, record = SUITE.compare(cfg, params)
    assert list(checks) == [
        "choices_valid", "routing_regret", "logits_vs_reference",
        "logits_rms_vs_reference", "loss_vs_reference",
        "kda_readout_ms_vs_reference", "loss_vs_free_reference",
    ]
    assert all(ok for ok, _ in checks.values()), checks
    assert checks["routing_regret"][1] == 0.0
    assert checks["logits_vs_reference"][1] < 1e-4
    assert checks["kda_readout_ms_vs_reference"][1] < 1e-5
    # one row of choices per routed block: every layer but the first
    assert len(record["moved_by_layer"]) == cfg.n_routed_layer == 4


def test_the_reference_does_not_import_the_program():
    text = pathlib.Path(plain.__file__).read_text()
    assert "dlrover_tpu" not in text.replace(
        "no import from the\nprogram", ""
    )


def test_forward_hands_over_every_choice_and_the_readout(model):
    cfg, params = model
    ids = np.asarray(SUITE.forward(cfg, params)[1])
    assert ids.dtype == np.int32
    assert ids.shape == (4, 2, SEQ, cfg.expert_top_k)
    assert ids.max() >= cfg.n_experts_held and ids.max() < cfg.n_experts
    metrics = SUITE.losses(cfg, params)
    assert metrics["moe_held_rows"] == pytest.approx(
        (ids < cfg.n_experts_held).sum() / 4
    )
    assert set(metrics) >= {"loss", "kda_readout_ms", "moe_held_rows"}
    with jax.default_matmul_precision("highest"):
        _, scores, readout = jax.jit(
            lambda p, t: plain.forward(p, t, _sizes(cfg), 8)
        )(params, _batch()["tokens"])
    assert metrics["kda_readout_ms"] == pytest.approx(
        float(readout), rel=1e-5
    )
    # the router's scores: the program's choices are the top-2 of the
    # reference's own logits, layer by layer
    want = np.sort(np.asarray(jax.lax.top_k(scores, 2)[1]), -1)
    np.testing.assert_array_equal(np.sort(ids, -1), want)


def test_the_first_layer_is_a_mixer_and_a_dense_mlp():
    """``K-`` beside ``Ke`` in one pattern: the published
    ``first_k_dense_replace`` 1 as a layer_pattern spells it."""
    assert pattern_parts("K-KeKe*eKe") == ["K-", "Ke", "Ke", "*e", "Ke"]
    cfg = get_config("kimi-linear")
    assert pattern_layers(cfg.layer_pattern) == cfg.n_layer == 27
    layers = pattern_parts(cfg.layer_pattern)
    assert layers[0] == "K-" and all(p[1] == "e" for p in layers[1:])
    full = [i + 1 for i, p in enumerate(layers) if p[0] == "*"]
    assert full == [4, 8, 12, 16, 20, 24, 27]  # full_attn_layers
    assert cfg.n_routed_layer == 26
    assert (cfg.head_dim, cfg.value_dim, cfg.rope_dim) == (192, 128, 64)
    with pytest.raises(ValueError, match="K-"):
        _cfg(n_dense_layer=1)
    with pytest.raises(ValueError, match="kda_gate_rank"):
        _cfg(kda_gate_rank=0)
    with pytest.raises(ValueError, match="no wider than its scores"):
        _cfg(v_head_dim=16)
    with pytest.raises(ValueError, match="K parts are the trunk's"):
        _cfg(kv_lora_rank=0, mtp_pattern="K", n_mtp_module=1)


def test_norm_scales_and_the_decays_parameters_start_as_drawn():
    cfg = _cfg()
    kda = jax.jit(decoder.init, static_argnums=1)(
        jax.random.key(0), cfg
    )["layers"]["kda"]["kda"]
    inner = cfg.kda_heads * cfg.kda_head_dim
    assert kda["w_qkv"].shape == (4, 64, 3 * inner)
    assert kda["w_gates"].shape == (4, 64, 2 * 8 + 4)
    assert kda["dt_bias"].shape == (4, inner)
    assert kda["norm"]["scale"].shape == (4, cfg.kda_head_dim)
    np.testing.assert_array_equal(np.asarray(kda["norm"]["scale"]), 1.0)
    a = np.exp(np.asarray(kda["a_log"]))
    assert a.shape == (4, 4) and 1.0 <= a.min() and a.max() <= 16.0
    step = np.asarray(jax.nn.softplus(kda["dt_bias"]))
    assert 1e-4 <= step.min() and step.max() <= 0.1 + 1e-6 and step.std() > 0


# ---- defects the comparison has to catch ---------------------------------


def _bf16_between_the_matmuls(patch):
    """What ``_kda_block`` keeps float32, rounded to bf16: the conv's
    output, q, k and v into the rule and its read-out, the gated norm's
    output."""
    from dlrover_tpu.ops import gated_delta, ssd

    def rounded(t):
        return t.astype(jnp.bfloat16).astype(t.dtype)

    conv, rule, norm = (
        ssd.causal_conv, gated_delta.gated_delta_rule, ssd.gated_group_norm
    )
    patch(ssd, "causal_conv", lambda *a, **kw: rounded(conv(*a, **kw)))
    patch(
        gated_delta, "gated_delta_rule",
        lambda q, k, v, g, beta, **kw: rounded(
            rule(rounded(q), rounded(k), rounded(v), g, beta, **kw)
        ),
    )
    patch(ssd, "gated_group_norm", lambda *a, **kw: rounded(norm(*a, **kw)))


def _conv_looks_ahead(patch, cfg):
    from dlrover_tpu.ops import ssd

    conv = ssd.causal_conv
    patch(
        ssd, "causal_conv",
        lambda x, w, b: jnp.roll(conv(x, w, b), -1, axis=1),
    )


DEFECTS = {
    **{
        name: (lambda patch, cfg, plant=plant: plant(patch),
               defects.CAUGHT_BY[name])
        for name, plant in defects.PLANT.items()
    },
    "conv_looks_ahead": (_conv_looks_ahead, LOGITS),
    "softmax_for_sigmoid": (dict(moe_score="softmax"), LOGITS),
    "weights_not_renormalised": (dict(moe_renorm_topk=False), LOGITS),
}


@pytest.mark.parametrize("defect", sorted(DEFECTS))
def test_comparison_catches(monkeypatch, model, defect):
    SUITE.catches(monkeypatch, model, *DEFECTS[defect])


def test_bf16_between_the_mixers_matmuls_fails(monkeypatch, model):
    """The tolerances' reason: both logit limits sit between a sound
    program and one with bf16 between the mixer's matmuls."""
    cfg, params = model
    _bf16_between_the_matmuls(monkeypatch.setattr)
    checks, _ = SUITE.compare(cfg, params, planted=True, read=LOGITS)
    assert not checks["logits_rms_vs_reference"][0], checks
    assert checks["logits_rms_vs_reference"][1] > 5 * TOLERANCES[1]


def test_a_scale_of_the_readout_shows_in_its_mean_square(monkeypatch, model):
    """Why ``kda_readout_ms`` is a term: q's 1 / sqrt(channels) left out
    makes every read-out sqrt(Dk) times too large, which the norm a head
    behind it takes out again but for its eps; the mean square reads Dk
    times the reference's."""
    cfg, params = model
    defects.PLANT["query_scale_left_out"](monkeypatch.setattr)
    checks, _ = SUITE.compare(cfg, params, planted=True)
    ok, value = checks["kda_readout_ms_vs_reference"]
    assert not ok
    assert value == pytest.approx(cfg.kda_head_dim - 1, rel=0.1)


# ---- the shares add up ----------------------------------------------------


def test_sixteen_shares_of_the_expert_parallel_layer_add_up():
    """Sixteen chips hold one expert each of one routed block's
    sixteen; a token's weights are over all it chose, times the scaling
    factor."""
    SUITE.shares_add_up(16, 1, n_experts=16, expert_top_k=4)


# ---- the gradient -----------------------------------------------------------


def test_gradient_of_every_kind_of_parameter_is_the_references(model):
    """d(loss)/d(params) through the mixers' chunked vector rule and its
    hand-written inverse derivative, the latent attention with its
    padded values, the held experts' cut dispatch and combine, against
    ``jax.grad`` of the plain reference sent to the same experts:
    element by element (float32 on both sides; ten parts amplify its
    rounding), and so the norms."""
    got, want = SUITE.gradients_match(model, atol=2e-3)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)
    ):
        assert float(jnp.linalg.norm(a)) == pytest.approx(
            float(jnp.linalg.norm(b)), rel=2e-3
        ), jax.tree_util.keystr(path)


# ---- the parameters and the FLOPs -------------------------------------------


def _file():
    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    return json.loads(
        (path / "kimi-linear-48b-a3b-ep16-1chip.json").read_text()
    )


def _cell_cfg():
    config = _file()
    return get_config(config["program"]["model"], **config["program"]["overrides"])


def _stated(text, what=-1):
    """The numbers of one line of the file's ``parameters``; the last is
    its sum."""
    import re

    return [int(n.replace(",", "")) for n in re.findall(
        r"(?<![\d.])\d{1,3}(?:,\d{3})+(?![\d.])", text
    )][what]


def test_num_params_is_the_files_arithmetic():
    """The configuration file's ``parameters``, part by part, against
    ``ModelConfig``: the mixer, the latent attention, the dense MLP, the
    routed block as held, the embedding and the head's slice."""
    cfg, stated = _cell_cfg(), _file()["parameters"]
    held = {c: n for c, (n, _) in cfg._part_counts().items()}
    d = cfg.d_model
    assert held["K"] - d == _stated(stated["kda_mixer"]) == 39_514_272
    assert held["*"] - d == _stated(stated["latent_attention"]) == 29_114_880
    assert held["-"] - d == _stated(stated["dense_mlp"]) == 63_700_992
    assert held["e"] - d == 120_913_920
    assert "120,913,920" in stated["routed_block_here"]
    assert held["K"] + held["-"] == _stated(stated["first_layer"])
    assert held["K"] + held["e"] == _stated(stated["routed_kda_layer"])
    assert held["*"] + held["e"] == _stated(stated["routed_latent_layer"])
    assert 2 * cfg.vocab_size * d + d == _stated(
        stated["embedding_head_slice_and_final_norm"]
    )
    assert cfg.num_params() == 828_925_824
    assert "= 828,925,824 =" in stated["total"]
    params = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    assert sum(
        int(np.prod(t.shape)) for t in jax.tree.leaves(params)
    ) == cfg.num_params()
    tiny = _cfg()
    counted = jax.eval_shape(lambda: decoder.init(jax.random.key(0), tiny))
    assert sum(
        int(np.prod(t.shape)) for t in jax.tree.leaves(counted)
    ) == tiny.num_params()
    # the published model whole: 48B, 3B of them met by a token
    full = get_config("kimi-linear")
    assert round(full.num_params() / 1e9, 1) == 49.1


@pytest.mark.parametrize("size", ["tiny", "cell"])
def test_flops_per_token_is_the_references_required_terms(size):
    cfg, seq = (_cfg(), SEQ) if size == "tiny" else (_cell_cfg(), 16384)
    terms = SUITE.flops_terms(cfg, seq)
    if size == "cell":
        rule = 4 * plain.kda_multiply_adds(_sizes(cfg))
        assert rule == 4 * 1_835_008
        assert terms["multiplied_params"] == 350_011_392
        # 32 heads x (192 + 128) / 2 channels x 8,192.5 keys
        assert terms["attention_pair_channels"] == 32 * 160 * 8192.5
        assert flopslib.flops_of(terms) == 2_603_415_552


# ---- latent attention's new shapes --------------------------------------------


@pytest.mark.parametrize(
    "rank,pos,vd", [(0, "none", 8), (0, "none", 12), (6, "rope", 8)],
    ids=["full-rank-q-narrow-v", "full-rank-q-equal-v", "ranked-q-rope"],
)
def test_latent_attention_block_is_mha_over_its_own_q_k_v(rank, pos, vd):
    """``_attention_block`` on a latent layer — q at full rank or
    through a rank, rotated or not, values narrower than the scores
    (padded for the kernels' one width and cut again) or as wide —
    against ``mha_reference`` on the unpadded q, k and v it is made of."""
    cfg = _cfg(q_lora_rank=rank, pos=pos, v_head_dim=vd)
    stack, ones = decoder._stackers(cfg, ())
    attn = decoder._init_attention(
        jax.random.split(jax.random.key(2), 16), cfg, stack, ones
    )
    assert ("wq" in attn) == (rank == 0) and ("wq_a" in attn) == (rank > 0)
    assert attn["wkv_b"].shape == (16, 4 * (8 + vd))
    assert attn["wo"].shape == (4 * vd, 64)
    x = jax.random.normal(jax.random.key(5), (2, 24, 64))
    positions = jnp.broadcast_to(jnp.arange(24), (2, 24))
    seen = {}

    def attn_fn(q, k, v):
        seen["shapes"] = (q.shape, k.shape, v.shape)
        return mha_reference(q, k, v, causal=True)

    got = decoder._attention_block(
        x, {"attn": attn}, cfg, None, positions, attn_fn
    )
    # one width into the kernels: 8 + 4 score channels
    assert seen["shapes"] == ((2, 24, 4, 12),) * 3
    q, k, v = decoder._latent_qkv(x, attn, cfg, positions)
    assert v.shape == (2, 24, 4, vd)
    if pos == "none":
        # no rotation: the shared channels are x W_kva's as they are
        kv = x @ attn["wkv_a"]
        np.testing.assert_allclose(
            np.asarray(k[:, :, 2, 8:]), np.asarray(kv[..., 16:]), rtol=1e-6
        )
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 12 ** -0.5
    mask = jnp.tril(jnp.ones((24, 24), bool))
    prob = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    want = jnp.einsum("bhqk,bkhe->bqhe", prob, v).reshape(2, 24, 4 * vd)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want @ attn["wo"]), rtol=2e-5, atol=2e-5
    )


def test_glm_preset_builds_what_it_built():
    """The parent commit's numbers (PR 64's tree): the published model's
    and the cell's parameter and FLOP counts, and a tiny GLM's drawn
    parameters and loss to the bit."""
    full = get_config("glm-4.7-flash")
    cell = get_config(
        "glm-4.7-flash", n_layer=9, n_experts_held=8, vocab_size=19360,
        max_seq=8192,
    )
    assert (full.num_params(), cell.num_params()) == (
        30_587_097_088, 1_133_834_752
    )
    assert (full.flops_per_token(8192), cell.flops_per_token(8192)) == (
        35_924_901_888.0, 5_497_466_880.0
    )
    tiny = get_config(
        "glm-4.7-flash", n_layer=3, d_model=64, d_ff=128, n_head=4,
        n_kv_head=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=16, n_experts=8, expert_top_k=2,
        d_expert=32, vocab_size=128, max_seq=64, dtype="float32",
    )
    params = decoder.init(jax.random.key(0), tiny)
    flat = jax.tree_util.tree_leaves_with_path(params)
    digest = hashlib.sha256()
    for path, leaf in flat:
        digest.update(jax.tree_util.keystr(path).encode())
        digest.update(np.asarray(leaf, np.float32).tobytes())
    assert len(flat) == 51
    assert digest.hexdigest() == (
        "b72bcca5d20560dd24e2fd1294e0f6043fc36811d8cfdfaab56a6b8b85d3e40e"
    )
    assert set(params["layers"]["attn"]) == {
        "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo",
    }
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, 128)
    loss = decoder.loss_fn(
        params, {"tokens": tokens, "targets": tokens}, cfg=tiny
    )[0]
    assert float(loss) == 6.743052959442139


# ---- the published sizes, and the paths that refuse the model --------------


def test_the_published_pattern_traces_whole():
    """27 layers, 256 experts, the full vocabulary: shapes only."""
    cfg = get_config("kimi-linear", remat="full")
    params = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    layers = params["layers"]
    assert layers["kda"]["kda"]["w_qkv"].shape == (20, 2304, 12288)
    assert layers["kda"]["kda"]["w_fb"].shape == (20, 128, 4096)
    assert layers["kda"]["kda"]["conv_w"].shape == (20, 4, 12288)
    assert layers["attention"]["attn"]["wq"].shape == (7, 2304, 32 * 192)
    assert layers["attention"]["attn"]["wkv_a"].shape == (7, 2304, 576)
    assert layers["attention"]["attn"]["wkv_b"].shape == (7, 512, 32 * 256)
    assert layers["attention"]["attn"]["wo"].shape == (7, 4096, 2304)
    assert layers["mlp"]["mlp"]["w_up"].shape == (1, 2304, 9216)
    assert layers["experts"]["moe"]["w_up"].shape == (26, 256, 2304, 1024)
    assert layers["experts"]["moe"]["shared"]["w_up"].shape == (
        26, 2304, 1024
    )
    batch = {
        k: jax.ShapeDtypeStruct((1, 128), jnp.int32)
        for k in ("tokens", "targets")
    }
    loss, metrics = jax.eval_shape(
        lambda p, b: decoder.loss_fn(p, b, cfg), params, batch
    )
    assert loss.shape == () and "kda_readout_ms" in metrics
    aux = jax.eval_shape(
        lambda p, t: decoder.forward(p, t, cfg, return_aux=True)[1],
        params, batch["tokens"],
    )
    assert aux["moe_choices"].shape == (26, 1, 128, 8)


def test_cache_paths_refuse_the_model_by_name(model):
    assert "decay a key channel (K)" in model[0].train_only
    for path in ("prefill", "sample"):
        SUITE.refuses(model, path, "key channel")


@pytest.mark.parametrize(
    "widths,interpret,kernel_layers",
    [(128, True, 4), (128, False, 0), (8, True, 0)],
    ids=["kernels", "off-the-chip", "other-widths"],
)
def test_counters_and_scopes_are_what_the_readers_read(
    monkeypatch, widths, interpret, kernel_layers
):
    """``kda.layers`` counts the pattern's KDA layers and
    ``kda.kernel_layers`` those whose rule runs the Pallas kernels
    (``ops/pallas_kda.py``): all 4 with heads of 128 where a TPU (here
    the interpreter) would run them, none off the chip or at widths off
    the 128 lanes — where the lowered text holds no kernel of the
    rule's; the scopes ``kda.conv``, ``kda.rule`` and ``kda.gate`` stand
    under the part's scope ``kda`` whichever body runs."""
    from dlrover_tpu.observability import tracing
    from dlrover_tpu.ops import pallas_attention

    monkeypatch.setattr(pallas_attention, "INTERPRET", interpret)
    cfg = _cfg(kda_heads=2, kda_head_dim=widths)
    params = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    tracing._counters.clear()
    lowered = jax.jit(
        lambda p, t: decoder.forward(p, t, cfg)
    ).lower(params, _batch()["tokens"])
    counters = tracing.counters()
    assert counters["kda.layers"] == 4
    assert counters["kda.kernel_layers"] == kernel_layers
    assert "gdn.layers" not in counters
    text = lowered.as_text(debug_info=True)
    assert ("kda_fwd" in text) == bool(kernel_layers)
    assert "gdn_fwd" not in text
    for scope in ("kda/kda.conv/ssm.conv", "kda/kda.rule", "kda/kda.gate",
                  "attn/attn.latent"):
        assert scope in text, scope
