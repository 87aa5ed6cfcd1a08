"""GLM-4.7-Flash's architecture (``glm-4.7-flash``: latent attention, a
dense prefix, sigmoid-scored experts of which a part is held beside a
shared one, a prediction module) against the benchmark's plain
reference, at a tiny size on the CPU with seeded weights: the
comparison the chip's cell is judged by (``benchmarks/lib/routed.py``),
defects it has to catch, the shares of an expert-parallel layer adding
up to the uncut layer, the expanded attention against plain attention,
the gradient, and the paths that refuse the model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from reference_suite import LOGITS, REFUSALS, Suite, seeded

from benchmarks.references import decoder_plain
from benchmarks.references import glm_moe_lite_plain as plain
from dlrover_tpu.models import decoder, get_config
from dlrover_tpu.ops import pallas_attention
from dlrover_tpu.parallel import moe

TINY = dict(
    n_layer=3, d_model=64, n_head=2, n_kv_head=2, d_ff=128, vocab_size=256,
    max_seq=64, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=24,
    qk_rope_head_dim=8, v_head_dim=32, d_expert=48, n_experts=8,
    expert_top_k=2, n_experts_held=4, expert_offset=0, remat="full",
    dtype="float32",
)
SIZE_KEYS = (
    "n_layer", "n_dense_layer", "d_model", "n_head", "n_kv_head", "d_ff",
    "vocab_size", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "d_expert", "n_experts",
    "n_experts_held", "expert_offset", "expert_top_k", "n_shared_experts",
    "n_mtp_module", "mtp_loss_coef", "routed_scaling_factor",
    "moe_renorm_topk", "rope_theta", "attn_window",
)
# a head that reads the token table and a module projection that passes
# the next token's embedding through (``reference_suite.seeded`` says why)
SUITE = Suite(
    "glm-4.7-flash", plain, TINY, SIZE_KEYS, seq=32, q_block=16,
    make=lambda cfg, seed: seeded(cfg, seed, module=True),
)
_cfg = SUITE.cfg


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
    # one row of choices per routed block, the module's last
    assert len(record["moved_by_layer"]) == cfg.n_routed_layer + 1


def test_forward_hands_over_every_choice_of_every_routed_block(model):
    cfg, params = model
    ids = np.asarray(SUITE.forward(cfg, params)[1])
    assert ids.shape == (cfg.n_routed_layer + 1, 2, 32, cfg.expert_top_k)
    # all of a token's choices, the experts held elsewhere among them
    assert ids.max() >= cfg.n_experts_held and ids.max() < cfg.n_experts
    metrics = SUITE.losses(cfg, params)
    assert metrics["moe_held_rows"] == pytest.approx(
        (ids < cfg.n_experts_held).sum() / (cfg.n_routed_layer + 1)
    )
    assert set(metrics) >= {"loss", "mtp_loss", "moe_held_rows"}


# ---- defects the comparison has to catch ---------------------------------


def _wrong_rope(patch, cfg):
    """Each rope frequency on its neighbour's channels."""
    rope = decoder._rope
    patch(
        decoder, "_rope",
        lambda x, tables: jnp.roll(rope(jnp.roll(x, 1, -1), tables), -1, -1),
    )


def _no_rank_norm(patch, cfg):
    """The norm of the kv latent left out."""
    norm = decoder._norm_block

    def skipping(x, ln, cfg_, residual=None):
        if residual is None and x.shape[-1] == cfg.kv_lora_rank:
            return x
        return norm(x, ln, cfg_, residual=residual)

    patch(decoder, "_norm_block", skipping)


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


def _in_the_loss_head(patch, name, replacement):
    """``decoder.<name>`` replaced while ``_loss_from_head`` runs: there
    it serves the prediction module alone."""
    head, real = decoder._loss_from_head, getattr(decoder, name)

    def patched(*args, **kwargs):
        setattr(decoder, name, replacement(real))
        try:
            return head(*args, **kwargs)
        finally:
            setattr(decoder, name, real)

    patch(decoder, "_loss_from_head", patched)


def _module_targets_shifted(patch, cfg):
    """The module's targets one place further on (t_{i+3})."""
    _in_the_loss_head(
        patch, "next_tokens",
        lambda real: lambda t: (
            real(real(t)) if jnp.issubdtype(t.dtype, jnp.integer) else real(t)
        ),
    )


def _no_module_norm(patch, cfg):
    """The module's own norm before the shared head left out."""
    _in_the_loss_head(
        patch, "_norm_block",
        lambda real: lambda x, ln, cfg_, residual=None: x,
    )


# defect -> (what differs in the program, the checks of which one fails)
DEFECTS = {
    "rope_on_the_wrong_channels": (_wrong_rope, LOGITS),
    "rank_norm_left_out": (_no_rank_norm, LOGITS),
    "softmax_for_sigmoid": (dict(moe_score="softmax"), LOGITS),
    "scaling_factor_left_out": (dict(routed_scaling_factor=1.0), LOGITS),
    "weights_over_held_experts_only": (_held_only_weights, LOGITS),
    "shared_expert_left_out": (_no_shared_expert, LOGITS),
    "module_targets_shifted": (
        _module_targets_shifted, ("mtp_loss_vs_reference",)
    ),
    "module_loss_weight_off": (
        dict(mtp_loss_coef=0.303), ("mtp_loss_vs_reference",)
    ),
    "module_norm_left_out": (_no_module_norm, ("mtp_loss_vs_reference",)),
}


@pytest.mark.parametrize("defect", sorted(DEFECTS))
def test_comparison_catches(monkeypatch, model, defect):
    plant, caught_by = DEFECTS[defect]
    checks = SUITE.catches(monkeypatch, model, plant, caught_by)
    if caught_by != LOGITS:
        # the module is beside the trunk: its defects leave the logits
        assert checks["logits_vs_reference"][0], checks


# ---- the shares add up ----------------------------------------------------


def test_shares_of_the_expert_parallel_layer_add_up():
    """Eight chips hold experts 0-1 ... 14-15 of one routed layer."""
    SUITE.shares_add_up(
        8, 2, n_experts=16, expert_top_k=4, n_mtp_module=0
    )


# ---- the expanded form is plain attention ---------------------------------


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_expanded_latent_attention_is_plain_attention(monkeypatch, grad):
    """q, k and v of latent attention, expanded, go through the unpacked
    flash kernels at head size 256 (interpreted here) as plain MHA."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    cfg = _cfg(
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        max_seq=256,
    )
    attn = jax.tree.map(
        lambda w: w[0], decoder.init(jax.random.key(5), cfg)["layers"]["attn"]
    )
    x = jax.random.normal(jax.random.key(6), (1, 256, cfg.d_model))
    positions = jnp.arange(256, dtype=jnp.int32)[None]
    q, k, v = decoder._latent_qkv(x, attn, cfg, positions)
    assert q.shape == k.shape == v.shape == (1, 256, cfg.n_head, 256)
    # every head's key ends in the same rope channels
    np.testing.assert_array_equal(
        np.asarray(k[:, :, 0, 192:]), np.asarray(k[:, :, 1, 192:])
    )

    def flash(q, k, v):
        return pallas_attention.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128
        ).reshape(1, 256, -1)

    def reference(q, k, v):
        return decoder_plain._attention(q, k, v, 0, 64)

    if not grad:
        np.testing.assert_allclose(
            np.asarray(flash(q, k, v)), np.asarray(reference(q, k, v)),
            rtol=2e-3, atol=2e-3,
        )
        return
    w = jax.random.normal(jax.random.key(8), (1, 256, cfg.n_head * 256))
    got = jax.grad(lambda *a: (flash(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (reference(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3
        )


# ---- the gradient -----------------------------------------------------------


def test_gradient_of_the_mixed_trunk_is_the_references(model):
    """d(ce_loss + mtp_loss)/d(params) through the dense prefix, the
    scanned routed layers, the held experts' hand-written dispatch and
    combine derivatives and the module, against ``jax.grad`` of the
    plain reference sent to the same experts."""
    SUITE.gradients_match(model, terms=("mtp_loss",))


# ---- the paths that cannot run it say so ----------------------------------

@pytest.mark.parametrize("path", sorted(REFUSALS))
def test_cache_and_generate_paths_refuse_the_model(model, path):
    SUITE.refuses(model, path, "glm-4.7-flash: latent attention")


@pytest.mark.parametrize(
    "over,why",
    [
        (dict(), "latent attention"),
        (dict(q_lora_rank=0, kv_lora_rank=0, qk_nope_head_dim=0,
              qk_rope_head_dim=0, v_head_dim=0), "layers differ"),
    ],
    ids=["latent", "mixed-trunk"],
)
def test_pipeline_refuses_the_model(over, why):
    from dlrover_tpu.parallel import MeshConfig, build_mesh

    cfg = _cfg(n_layer=5, **over)
    mesh = build_mesh(MeshConfig(pp=2, dp=-1))
    params = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    tokens = jax.ShapeDtypeStruct((8, 32), jnp.int32)
    with pytest.raises(ValueError, match=why):
        jax.eval_shape(
            lambda p, t: decoder.forward(p, t, cfg, mesh=mesh), params, tokens
        )


# ---- what the other models keep ---------------------------------------------


def _shapes(tree):
    return {
        jax.tree_util.keystr(path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def _plain_tree(cfg, routed_layers):
    """The tree ``decoder.init`` gave before this architecture came, by
    hand: one stack of layers, q/k/v/o, two norms, an MLP — but for the
    routed layers' dense ``mlp``, which a routed layer no longer holds."""
    L, d, f, v = cfg.n_layer, cfg.d_model, cfg.d_ff, cfg.vocab_size
    hd, nh, nkv = d // cfg.n_head, cfg.n_head, cfg.kv_heads
    t = {
        "['embed']['tokens']": (v, d),
        "['final_norm']['scale']": (d,),
        "['layers']['attn']['wq']": (L, d, nh * hd),
        "['layers']['attn']['wk']": (L, d, nkv * hd),
        "['layers']['attn']['wv']": (L, d, nkv * hd),
        "['layers']['attn']['wo']": (L, nh * hd, d),
        "['layers']['ln1']['scale']": (L, d),
        "['layers']['ln2']['scale']": (L, d),
    }
    if routed_layers:
        e = cfg.n_experts
        t.update({
            "['layers']['moe']['w_gate']": (L, d, e),
            "['layers']['moe']['w_up']": (L, e, d, f),
            "['layers']['moe']['w_gate_proj']": (L, e, d, f),
            "['layers']['moe']['w_down']": (L, e, f, d),
        })
    else:
        t["['layers']['mlp']['w_up']"] = (L, d, f)
        t["['layers']['mlp']['w_down']"] = (L, f, d)
        if cfg.act == "swiglu":
            t["['layers']['mlp']['w_gate']"] = (L, d, f)
    if cfg.norm == "layernorm":
        t["['final_norm']['bias']"] = (d,)
        t["['layers']['ln1']['bias']"] = (L, d)
        t["['layers']['ln2']['bias']"] = (L, d)
    if cfg.pos == "learned":
        t["['pos_embed']['table']"] = (cfg.max_seq, d)
    if not cfg.tie_embeddings:
        t["['lm_head']['w']"] = (d, v)
    return t


@pytest.mark.parametrize("name", ["gpt2-1.5b", "mistral-7b", "tiny-moe"])
def test_parameter_trees_of_the_other_models_are_unchanged(name):
    cfg = get_config(name)
    tree = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    assert _shapes(tree) == _plain_tree(cfg, routed_layers=name == "tiny-moe")
    axes = decoder.logical_axes(cfg)
    assert jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple))
    ) == jax.tree.structure(tree)


def test_published_sizes_through_init_and_axes():
    """``get_config("glm-4.7-flash")`` at the published sizes: every
    parameter has its logical axes, and the counts are the source's."""
    cfg = get_config("glm-4.7-flash")
    assert (cfg.n_layer, cfg.n_routed_layer, cfg.head_dim, cfg.rope_dim) == (
        47, 46, 256, 64
    )
    tree = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    axes = decoder.logical_axes(cfg)
    is_axes = lambda a: isinstance(a, tuple)  # noqa: E731
    for leaf, ax in zip(
        jax.tree.leaves(tree), jax.tree.leaves(axes, is_leaf=is_axes)
    ):
        assert leaf.ndim == len(ax)
    n = sum(leaf.size for leaf in jax.tree.leaves(tree))
    # 30B-A3B: 46 routed layers of 65 experts of 9.437 M and the rest
    assert n == cfg.num_params() == 30_587_097_088
    assert tree["layers"]["attn"]["wkv_b"].shape == (46, 512, 20 * 448)
    assert tree["dense_layers"]["mlp"]["w_up"].shape == (1, 2048, 10240)
    assert "mlp" not in tree["layers"]
