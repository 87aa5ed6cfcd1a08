"""GLM-4.7-Flash's architecture (``glm-4.7-flash``: latent attention, a
dense prefix, sigmoid-scored experts of which a part is held beside a
shared one, a prediction module) against the benchmark's plain
reference, at a tiny size on the CPU with seeded weights: the
comparison the chip's cell is judged by (``benchmarks/lib/routed.py``),
defects it has to catch, the shares of an expert-parallel layer adding
up to the uncut layer, the expanded attention against plain attention,
the gradient, and the paths that refuse the model."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import routed
from benchmarks.references import decoder_plain
from benchmarks.references import glm_moe_lite_plain as plain
from dlrover_tpu.models import decoder, generate, get_config
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
# float32 on both sides: far inside the chip's limits, so that a defect
# shows by orders of magnitude
TOLERANCES = (1e-3, 1e-3, 1e-4)


def _cfg(**over):
    return get_config("glm-4.7-flash", **{**TINY, **over})


def _sizes(cfg):
    return dict({k: getattr(cfg, k) for k in SIZE_KEYS}, norm_eps=1e-6)


def _batch(seq=32, rows=2, vocab=256):
    """Every token twice in a row (a a b b c c ...): the next token is
    the present one half of the time, the one after that never."""
    half = np.random.default_rng(7).integers(0, vocab, (rows, seq // 2 + 1))
    data = jnp.asarray(np.repeat(half, 2, axis=1)[:, : seq + 1], jnp.int32)
    return {"tokens": data[:, :-1], "targets": data[:, 1:]}


@pytest.fixture(scope="module")
def model():
    """Seeded weights, but for a head that reads the token table and a
    module projection that passes the next token's embedding through: a
    model whose predictions lean towards the token it was just given.
    With predictions that know nothing of the targets (seeded weights,
    uniform tokens) a loss asked for the wrong tokens reads the same as
    the right one, and a shifted target could not show."""
    cfg = _cfg()
    params = decoder.init(jax.random.key(0), cfg)
    d = cfg.d_model
    params["lm_head"]["w"] = params["embed"]["tokens"].T / (0.02 * d ** 0.5)
    params["mtp"]["eh_proj"] = jnp.concatenate(
        [jnp.eye(d), 0.25 * params["mtp"]["eh_proj"][d:]]
    )
    return cfg, params


def _compare(cfg, params, batch, sizes=None):
    """The cell's comparison, teacher-forced and free-running."""
    sizes = sizes or _sizes(cfg)
    logits, choices = routed.program_logits_and_choices(
        params, batch["tokens"], cfg
    )
    program = routed.program_losses(params, batch, cfg)
    results, record = routed.compare(
        plain, params, batch, sizes, 16, logits, choices, program, TOLERANCES
    )
    with jax.default_matmul_precision("highest"):
        free_loss, _ = plain.loss_and_logits(params, batch, sizes, 16)
    err = abs(program["loss"] - float(free_loss)) / float(free_loss)
    results.append(
        ("loss_vs_free_reference", err <= routed.FREE_LOSS_TOL, err,
         routed.FREE_LOSS_TOL)
    )
    return {name: (ok, value) for name, ok, value, _ in results}, record


def test_program_matches_the_plain_reference(model):
    cfg, params = model
    checks, record = _compare(cfg, params, _batch())
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
    batch = _batch()
    _, aux = decoder.forward(params, batch["tokens"], cfg, return_aux=True)
    ids = np.asarray(aux["moe_choices"])
    assert ids.shape == (cfg.n_routed_layer + 1, 2, 32, cfg.expert_top_k)
    # all of a token's choices, the experts held elsewhere among them
    assert ids.max() >= cfg.n_experts_held and ids.max() < cfg.n_experts
    metrics = decoder.loss_fn(params, batch, cfg)[1]
    held = float(metrics["moe_held_rows"])
    assert held == pytest.approx(
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
LOGITS = ("logits_vs_reference", "logits_rms_vs_reference")
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
    cfg, params = model
    plant, caught_by = DEFECTS[defect]
    program_cfg = cfg
    if isinstance(plant, dict):
        program_cfg = dataclasses.replace(cfg, **plant)
    else:
        plant(monkeypatch.setattr, cfg)
    # the reference keeps the sound sizes; only the program is defective
    checks, _ = _compare(program_cfg, params, _batch(), sizes=_sizes(cfg))
    failed = {name for name, (ok, _) in checks.items() if not ok}
    assert failed & set(caught_by), (defect, checks)
    if caught_by != LOGITS:
        # the module is beside the trunk: its defects leave the logits
        assert checks["logits_vs_reference"][0], checks


# ---- the shares add up ----------------------------------------------------


def test_shares_of_the_expert_parallel_layer_add_up():
    """Eight chips hold experts 0-1 ... 14-15 of one routed layer. Their
    routed parts, and the shared expert ONCE, add up to what the uncut
    reference gives for the whole layer: nothing is lost or counted
    twice at the seams, and a token's weights are over all it chose."""
    shares, held = 8, 2
    whole = _cfg(
        n_experts=shares * held, n_experts_held=0, expert_top_k=4,
        n_mtp_module=0,
    )
    full = moe.init_moe_params(jax.random.key(3), whole, lead=())
    g = jax.random.normal(jax.random.key(4), (2, 32, whole.d_model))
    sizes = dict(
        _sizes(whole), n_experts_held=shares * held, expert_offset=0
    )
    with jax.default_matmul_precision("highest"):
        want, _ = plain._routed(g.reshape(64, -1), full, sizes, None)
        total = moe._shared_expert(g, full["shared"], None)
        rows = 0.0
        for rank in range(shares):
            cfg = dataclasses.replace(
                whole, n_experts_held=held, expert_offset=rank * held
            )
            here = slice(rank * held, (rank + 1) * held)
            part = dict(
                full, **{k: full[k][here]
                         for k in ("w_up", "w_gate_proj", "w_down")}
            )
            out, aux = moe._moe_block_ragged(g, part, cfg)
            total = total + out
            rows += float(aux["moe_held_rows"])
    np.testing.assert_allclose(
        np.asarray(total).reshape(64, -1), np.asarray(want),
        rtol=2e-5, atol=2e-5,
    )
    # every (token, choice) row went to exactly one share
    assert rows == 2 * 32 * whole.expert_top_k


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
    cfg, params = model
    batch = _batch()
    sizes = _sizes(cfg)
    _, choices = routed.program_logits_and_choices(
        params, batch["tokens"], cfg
    )

    def objective(p):
        ce, _, terms = plain.loss_and_logits_routed(
            p, batch, sizes, 16, choices
        )
        return ce + terms["mtp_loss"]

    got = jax.grad(lambda p: decoder.loss_fn(p, batch, cfg)[0])(params)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(objective)(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, a), b in zip(flat_got, flat_want):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-4,
            err_msg=jax.tree_util.keystr(path),
        )
        assert float(jnp.max(jnp.abs(b))) > 0, jax.tree_util.keystr(path)


# ---- the paths that cannot run it say so ----------------------------------

REFUSALS = {
    "init_kv_cache": lambda cfg, p, t: decoder.init_kv_cache(cfg, 2, 64),
    "prefill": lambda cfg, p, t: decoder.prefill(p, t, cfg, 64),
    "decode_step": lambda cfg, p, t: decoder.decode_step(
        p, t[:, 0], {}, 0, cfg
    ),
    "prefill_chunk": lambda cfg, p, t: decoder.prefill_chunk(
        p, t, {}, 0, cfg
    ),
    "decode_step_paged": lambda cfg, p, t: decoder.decode_step_paged(
        p, t[:, 0], {}, None, jnp.zeros(2, jnp.int32), None, cfg
    ),
    "verify_chunk": lambda cfg, p, t: decoder.verify_chunk(p, t, {}, 0, cfg),
    "sample": lambda cfg, p, t: generate.sample(
        p, cfg, t, 4, jax.random.key(0)
    ),
}


@pytest.mark.parametrize("path", sorted(REFUSALS))
def test_cache_and_generate_paths_refuse_the_model(model, path):
    cfg, params = model
    with pytest.raises(ValueError, match="glm-4.7-flash: latent attention"):
        REFUSALS[path](cfg, params, _batch()["tokens"])


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
