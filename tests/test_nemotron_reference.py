"""Nemotron-3-Super's architecture (``nemotron-3-super``: layers of ONE
part each by a pattern: Mamba-2 mixers through a chunked scan, an
attention without rope, relu² experts in a latent beside a shared one,
a part of them held; a prediction module of two such layers) against
the benchmark's plain reference, at a tiny size on the CPU with seeded
weights: the comparison the chip's cell is judged by
(``benchmarks/lib/routed.py``), the chunked scan against the sequential
recurrence, the shares of an expert-parallel layer adding up to the
uncut layer, the static row bound of the held experts, the pattern
trunk against its layers one by one, the gradient, the FLOPs, and the
paths that refuse the model."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from reference_suite import LOGITS, REFUSALS, Suite, seeded

from benchmarks.lib import flops as flopslib
from benchmarks.references import nemotron_h_plain as plain
from benchmarks.tests import nemotron_defects as shared_defects
from dlrover_tpu.models import decoder, get_config
from dlrover_tpu.ops import ssd
from dlrover_tpu.parallel import moe

# heads 8 x 8 in 2 groups, state 16, chunks of 16 (a sequence of 40 is
# two chunks and a half); top-6 of 16 experts with 4 held, so the row
# bound (k > held) is live
TINY = dict(
    n_layer=5, layer_pattern="MEM*E", d_model=64, n_head=4, n_kv_head=2,
    d_head=16, vocab_size=256, max_seq=64, mamba_num_heads=8,
    mamba_head_dim=8, ssm_state_size=16, n_groups=2, ssm_chunk=16,
    ssm_head_block=4, n_experts=16, expert_top_k=6, d_expert=48,
    moe_latent_size=32, d_shared_expert=96, n_experts_held=4,
    expert_offset=0, remat="full", dtype="float32",
)
SIZE_KEYS = (
    "n_layer", "layer_pattern", "mtp_pattern", "d_model", "n_head",
    "n_kv_head", "d_head", "vocab_size", "mamba_num_heads", "mamba_head_dim",
    "ssm_state_size", "n_groups", "conv_kernel", "ssm_chunk", "ssm_norm_eps",
    "n_experts", "n_experts_held", "expert_offset", "expert_top_k",
    "d_expert", "moe_latent_size", "d_shared_expert", "n_shared_experts",
    "n_mtp_module", "mtp_loss_coef", "routed_scaling_factor",
    "moe_renorm_topk",
)
SEQ = 40
# a head that reads the token table and a module projection that passes
# the next token's embedding through
SUITE = Suite(
    "nemotron-3-super", plain, TINY, SIZE_KEYS, seq=SEQ, q_block=8,
    make=lambda cfg, seed: seeded(cfg, seed, module=True),
)
_cfg, _batch = SUITE.cfg, SUITE.batch


@pytest.fixture(scope="module")
def model():
    return SUITE.model()


def test_program_matches_the_plain_reference(model):
    cfg, params = model
    checks, record = SUITE.compare(cfg, params)
    assert list(checks) == [
        "choices_valid", "routing_regret", "logits_vs_reference",
        "logits_rms_vs_reference", "loss_vs_reference",
        "mtp_loss_vs_reference", "loss_vs_free_reference",
    ]
    assert all(ok for ok, _ in checks.values()), checks
    assert checks["routing_regret"][1] == 0.0
    assert checks["logits_vs_reference"][1] < 1e-5
    assert checks["mtp_loss_vs_reference"][1] < 1e-5
    # one row of choices per routed layer, the module's last
    assert len(record["moved_by_layer"]) == cfg.n_routed_layer + 1 == 3


def test_forward_hands_over_every_choice_of_every_routed_layer(model):
    cfg, params = model
    ids = np.asarray(SUITE.forward(cfg, params)[1])
    assert ids.dtype == np.int32
    assert ids.shape == (3, 2, SEQ, cfg.expert_top_k)
    assert ids.max() >= cfg.n_experts_held and ids.max() < cfg.n_experts
    metrics = SUITE.losses(cfg, params)
    assert metrics["moe_held_rows"] == pytest.approx(
        (ids < cfg.n_experts_held).sum() / 3
    )
    assert set(metrics) >= {"loss", "mtp_loss", "moe_held_rows"}


# ---- the chunked scan is the recurrence -----------------------------------


def _scan_inputs(s, h=8, p=4, g=2, n=16, b=2):
    k = jax.random.split(jax.random.key(11), 5)
    return (
        jax.random.normal(k[0], (b, s, h, p)),
        jax.nn.softplus(jax.random.normal(k[1], (b, s, h))),
        -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.7)),
        jax.random.normal(k[3], (b, s, g, n)),
        jax.random.normal(k[4], (b, s, g, n)),
    )


def _recurrence(x, dt, a, b_mat, c_mat):
    """S_t = a_t S_{t-1} + Δ_t x_t B_tᵀ; y_t = S_t C_t, token by token."""
    rep = x.shape[2] // b_mat.shape[2]
    b_mat, c_mat = jnp.repeat(b_mat, rep, 2), jnp.repeat(c_mat, rep, 2)

    def token(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = jnp.exp(a * dt_t)[..., None, None] * state + (
            (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        )
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    start = jnp.zeros(x.shape[:1] + x.shape[2:] + b_mat.shape[-1:])
    _, y = jax.lax.scan(
        token, start,
        jax.tree.map(lambda t: jnp.moveaxis(t, 1, 0), (x, dt, b_mat, c_mat)),
    )
    return jnp.moveaxis(y, 0, 1)


@pytest.mark.parametrize("head_block", [0, 2, 4, 8],
                         ids=["whole", "half-group", "group", "two-groups"])
@pytest.mark.parametrize("seq,chunk", [(16, 16), (64, 16), (50, 16)],
                         ids=["one-chunk", "four-chunks", "padded"])
def test_chunked_scan_is_the_sequential_recurrence(seq, chunk, head_block):
    """At one chunk, at several, and at a length that is no multiple of
    the chunk (padded with steps of Δ = 0, cut off again), with the
    heads whole and in blocks."""
    args = _scan_inputs(seq)
    got = ssd.ssd_scan(*args, chunk, head_block)
    want = _recurrence(*args)
    assert got.shape == want.shape
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_chunked_scan_gradient_is_the_recurrences():
    args = _scan_inputs(50)
    w = jax.random.normal(jax.random.key(12), args[0].shape)
    got = jax.grad(
        lambda *a: (ssd.ssd_scan(*a, 16, 4) * w).sum(), range(5)
    )(*args)
    want = jax.grad(lambda *a: (_recurrence(*a) * w).sum(), range(5))(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
        )


def test_head_block_that_fits_no_group_is_refused_by_name():
    with pytest.raises(ValueError, match="block of 3 heads"):
        ssd.ssd_scan(*_scan_inputs(16), 16, 3)


def test_mixer_is_initialised_as_published():
    """A in [1, 16], the time step in [time_step_min, time_step_max]
    through the inverse softplus, D = 1."""
    cfg = _cfg(mamba_num_heads=64, n_groups=2, ssm_head_block=0)
    ssm = decoder._init_mamba(jax.random.key(2), cfg, (3,))
    a = np.exp(np.asarray(ssm["a_log"]))
    step = np.asarray(jax.nn.softplus(ssm["dt_bias"]))
    assert 1.0 <= a.min() < 3.0 and 12.0 < a.max() <= 16.0
    assert cfg.time_step_min * 0.999 <= step.min() < 0.003
    assert 0.03 < step.max() <= cfg.time_step_max * 1.001
    np.testing.assert_array_equal(np.asarray(ssm["d_skip"]), 1.0)


# ---- defects the comparison has to catch ---------------------------------


def _no_skip(patch, cfg):
    scan = ssd.ssd_scan
    patch(
        ssd, "ssd_scan",
        lambda x, *a: scan(x, *a) - x,  # D = 1 at initialisation
    )


def _conv_looks_ahead(patch, cfg):
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


def _no_shared_expert(patch, cfg):
    patch(moe, "_shared_expert", lambda x, shared, mesh: jnp.zeros_like(x))


def _rows_cut_short(patch, cfg):
    """A row bound one token too small: a capacity, not a bound."""
    bound = moe._held_row_bound
    patch(
        moe, "_held_row_bound",
        lambda t, k, e, some: (
            None if bound(t, k, e, some) is None else t * e // 4
        ),
    )


def _shared(name):
    """A defect the benchmark's own tests plant too
    (``benchmarks/tests/nemotron_defects.py``)."""
    return lambda patch, cfg: shared_defects.INJECT[name](patch)


DEFECTS = {
    "decays_summed_in_bf16": (_shared("bf16_decays"), LOGITS),
    "gate_after_the_group_norm": (_shared("gate_after_the_norm"), LOGITS),
    "skip_left_out": (_no_skip, LOGITS),
    "chunk_state_dropped": (_shared("chunk_state_dropped"), LOGITS),
    "conv_looks_ahead": (_conv_looks_ahead, LOGITS),
    "rope_on": (_shared("rope_on"), LOGITS),
    "softmax_for_sigmoid": (dict(moe_score="softmax"), LOGITS),
    "scaling_factor_left_out": (dict(routed_scaling_factor=1.0), LOGITS),
    "weights_over_held_experts_only": (_held_only_weights, LOGITS),
    "shared_expert_left_out": (_no_shared_expert, LOGITS),
    "held_rows_cut_short": (_rows_cut_short, LOGITS),
    "module_loss_weight_off": (
        dict(mtp_loss_coef=0.303), ("mtp_loss_vs_reference",)
    ),
}


@pytest.mark.parametrize("defect", sorted(DEFECTS))
def test_comparison_catches(monkeypatch, model, defect):
    SUITE.catches(monkeypatch, model, *DEFECTS[defect])


# ---- the shares add up ----------------------------------------------------


def test_shares_of_the_expert_parallel_layer_add_up():
    """Four chips hold experts 0-3 ... 12-15 of one routed layer, each
    through W_up, which is linear."""
    SUITE.shares_add_up(4, 4, cut=("w_up", "w_down"))


# ---- the row bound ----------------------------------------------------------


def test_row_bound_loses_no_row_when_every_token_fills_it():
    """A routing that sends EVERY token to all the held experts puts
    t · held pairs here: exactly the bound, and none is dropped."""
    cfg = _cfg()
    t, k, held = 64, cfg.expert_top_k, cfg.n_experts_held
    assert moe._held_row_bound(t, k, held, True) == t * held
    part = moe.init_moe_params(jax.random.key(3), cfg, lead=())
    rows = jax.random.normal(jax.random.key(4), (t, cfg.moe_latent_size))
    # the held experts first, then two elsewhere (ids local to the share)
    ids = jnp.tile(jnp.asarray([0, 1, 2, 3, 7, 9], jnp.int32), (t, 1))
    weights = jax.random.uniform(jax.random.key(5), (t, k)) + 0.5

    def program(rows, weights):
        return moe._ragged_ffn(rows, part, ids, weights, jnp.float32)

    def every_held_expert(rows, weights):
        return sum(
            weights[:, e:e + 1] * plain._relu2(
                rows, part["w_up"][e], part["w_down"][e]
            )
            for e in range(held)
        )

    out, counts = program(rows, weights)
    np.testing.assert_array_equal(np.asarray(counts), t)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(every_held_expert(rows, weights)),
        rtol=2e-5, atol=2e-5,
    )
    got = jax.grad(lambda *a: (program(*a)[0] ** 2).sum(), (0, 1))(
        rows, weights
    )
    want = jax.grad(lambda *a: (every_held_expert(*a) ** 2).sum(), (0, 1))(
        rows, weights
    )
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
        )


@pytest.mark.parametrize(
    "name,k,held",
    [("glm-4.7-flash", 4, 8), ("keye-vl-2.0", 8, 16), ("olmoe-1b-7b", 8, 64)],
)
def test_row_bound_leaves_the_other_models_shapes(name, k, held):
    """k ≤ held: every sorted row can hold a held pair; nothing is cut."""
    cfg = get_config(name)
    assert cfg.expert_top_k == k
    assert moe._held_row_bound(16384, k, held, held < cfg.n_experts) is None
    assert moe._held_row_bound(8192, 22, 8, True) == 65536


# ---- a pattern trunk is its layers one by one ------------------------------


def test_pattern_trunk_is_its_layers_one_by_one(model):
    cfg, params = model
    cfg = dataclasses.replace(cfg, remat="none")
    tokens = _batch()["tokens"]
    positions = jnp.broadcast_to(jnp.arange(SEQ, dtype=jnp.int32), (2, SEQ))
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)

    def attn_fn(q, k, v):
        from dlrover_tpu.ops.attention import mha_reference

        return mha_reference(q, k, v, causal=True)

    got, aux = decoder.run_trunk(
        x, params["layers"], positions, cfg, attn_fn=attn_fn
    )
    want, seen, choices = x, {"M": 0, "*": 0, "E": 0}, []
    for letter in cfg.layer_pattern:
        layer = jax.tree.map(
            lambda t: t[seen[letter]],
            params["layers"][decoder.PARTS[letter].stack],
        )
        seen[letter] += 1
        want, layer_aux = decoder._part_body(
            want, layer, positions, letter=letter, cfg=cfg, mesh=None,
            attn_fn=attn_fn,
        )
        if layer_aux:
            choices.append(layer_aux["moe_choices"])
    assert seen == {"M": 2, "*": 1, "E": 2}
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_array_equal(
        np.asarray(aux["moe_choices"]), np.asarray(jnp.stack(choices))
    )


def test_the_published_pattern_traces_whole():
    """88 layers, 512 experts, the full vocabulary: shapes only."""
    cfg = get_config("nemotron-3-super", remat="full")
    assert cfg.layer_pattern.count("M") == cfg.layer_pattern.count("E") == 40
    assert cfg.layer_pattern[27:38] == "MEMEMEMEM*E"
    params = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    assert params["layers"]["mamba"]["ssm"]["w_in"].shape == (40, 4096, 18560)
    assert params["layers"]["experts"]["moe"]["w_up"].shape == (
        40, 512, 1024, 2688
    )
    batch = {
        k: jax.ShapeDtypeStruct((1, 256), jnp.int32)
        for k in ("tokens", "targets")
    }
    loss, metrics = jax.eval_shape(
        lambda p, b: decoder.loss_fn(p, b, cfg), params, batch
    )
    assert loss.shape == () and "mtp_loss" in metrics
    aux = jax.eval_shape(
        lambda p, t: decoder.forward(p, t, cfg, return_aux=True)[1],
        params, batch["tokens"],
    )
    assert aux["moe_choices"].shape == (41, 1, 256, 22)


# ---- the gradient -----------------------------------------------------------


def test_gradient_of_every_kind_of_parameter_is_the_references(model):
    """d(ce_loss + mtp_loss)/d(params) through the mixers' chunked scan
    under its own checkpoint, the attention, the held experts' cut
    dispatch and combine and the module, against ``jax.grad`` of the
    plain reference sent to the same experts."""
    SUITE.gradients_match(model, terms=("mtp_loss",))


# ---- the FLOPs ---------------------------------------------------------------


@pytest.mark.parametrize("size", ["tiny", "cell"])
def test_flops_per_token_is_the_references_required_terms(size):
    if size == "tiny":
        cfg, seq = _cfg(), SEQ
    else:
        cfg, seq = get_config(
            "nemotron-3-super", n_layer=11, layer_pattern="MEMEMEMEM*E",
            n_experts_held=8, vocab_size=16384, max_seq=8192,
        ), 8192
    terms = SUITE.flops_terms(cfg, seq)
    if size == "cell":
        # the matrices, and 5 x 2.10 M of the recurrence entered as such
        scan = 5 * 2 * 128 * 64 * 128
        assert terms["multiplied_params"] - scan == pytest.approx(
            1.1255e9, rel=1e-4
        )
        assert flopslib.flops_of(terms) == pytest.approx(7.22e9, rel=1e-3)
        assert cfg.num_params() == pytest.approx(1.3787e9, rel=1e-4)


# ---- the paths that cannot run it say so ----------------------------------

@pytest.mark.parametrize("path", sorted(REFUSALS))
def test_cache_and_generate_paths_refuse_the_model(model, path):
    SUITE.refuses(model, path, "nemotron-3-super: state-space layers")


def test_pipeline_refuses_the_model():
    from dlrover_tpu.parallel import MeshConfig, build_mesh

    cfg = _cfg()
    mesh = build_mesh(MeshConfig(pp=2, dp=-1))
    params = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    tokens = jax.ShapeDtypeStruct((8, 32), jnp.int32)
    with pytest.raises(ValueError, match="state-space layers"):
        jax.eval_shape(
            lambda p, t: decoder.forward(p, t, cfg, mesh=mesh), params, tokens
        )


@pytest.mark.parametrize(
    "over,why",
    [
        (dict(layer_pattern="MEM*"), "names 4 layers"),
        (dict(layer_pattern="MEMxE"), "made of M"),
        (dict(layer_pattern="ME-*E"), "names 4 layers"),
        (dict(layer_pattern="MEM*E-"), "dense MLP of d_ff"),
        (dict(mtp_pattern=""), "both or neither"),
        (dict(moe_impl="dense"), "ragged"),
        (dict(mamba_num_heads=0), "Mamba-2 layer needs"),
        (dict(pos="learned"), "position table"),
    ],
    ids=["count", "letter", "mlp-in-a-layer", "mlp-act", "module", "lowering",
         "mixer", "positions"],
)
def test_config_refuses_a_pattern_it_cannot_run(over, why):
    with pytest.raises(ValueError, match=why):
        _cfg(**over)


def test_the_models_parts_belong_to_a_pattern_model():
    with pytest.raises(ValueError, match="layer_pattern model"):
        get_config("tiny", act="relu2")
    with pytest.raises(ValueError, match="layer_pattern model"):
        get_config("tiny-moe", moe_latent_size=16)
