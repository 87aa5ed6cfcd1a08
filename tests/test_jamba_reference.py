"""Jamba2-3B's architecture (``jamba2-3b``: mixer + MLP layers as two
``layer_pattern`` parts each, Mamba-1 mixers through a selective scan
with a hand-written derivative, one multi-query attention without rope,
a tied head, runs of like layers scanned) against the benchmark's plain
reference, at a tiny size on the CPU with seeded weights in float32: the
comparison the chip's cell is judged by (``dense``), every parameter's
gradient, the scan against the token-by-token recurrence, two parts
against one published layer, scanned runs against unrolled ones, the
counters, and that a Mamba-2 pattern is left as it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from reference_suite import Suite

from benchmarks.references import jamba_plain as plain
from dlrover_tpu.models import decoder, get_config
from dlrover_tpu.models.config import pattern_layers, pattern_parts
from dlrover_tpu.observability import tracing
from dlrover_tpu.ops import selective_scan as sscan

# 128 channels of 4 states, Δ through a rank of 8; two Mamba-1 layers,
# the attention layer (4 query heads on one key/value head), two more
TINY = dict(
    n_layer=5, layer_pattern="m-m-*-m-m-", d_model=64, n_head=4,
    n_kv_head=1, d_head=16, d_ff=128, vocab_size=256, max_seq=64,
    mamba_dt_rank=8, ssm_state_size=4, remat="full", dtype="float32",
)
SIZE_KEYS = (
    "n_layer", "layer_pattern", "d_model", "n_head", "n_kv_head", "d_head",
    "d_ff", "vocab_size", "mamba_expand", "mamba_dt_rank", "ssm_state_size",
    "conv_kernel", "tie_embeddings",
)
SEQ = 40


def _made(cfg, seed):
    params = decoder.init(jax.random.key(seed), cfg)
    # a head that reads the token table at a size where logits differ
    params["embed"]["tokens"] = params["embed"]["tokens"] * 20.0
    return params


SUITE = Suite(
    "jamba2-3b", plain, TINY, SIZE_KEYS, seq=SEQ, q_block=8, make=_made,
    doubled=False,
)
_cfg, _sizes, _batch = SUITE.cfg, SUITE.sizes, SUITE.batch


@pytest.fixture(scope="module")
def model():
    return SUITE.model()


def _reference(params, batch, cfg):
    with jax.default_matmul_precision("highest"):
        return plain.loss_and_logits(params, batch, _sizes(cfg), 8)


def test_program_matches_the_plain_reference(model):
    cfg, params = model
    batch = _batch()
    ref_loss, ref_logits = _reference(params, batch, cfg)
    logits = decoder.forward(params, batch["tokens"], cfg)
    loss = decoder.loss_fn(params, batch, cfg=cfg)[1]["loss"]
    scale = float(jnp.max(jnp.abs(ref_logits)))
    assert scale > 1.0
    assert float(jnp.max(jnp.abs(logits - ref_logits))) / scale < 1e-5
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < 1e-6


def test_every_parameters_gradient_matches_the_reference(model):
    """The hand-written derivative of the scan inside the whole model,
    through remat and the scanned runs, against autodiff of the
    reference's token-by-token recurrence."""
    SUITE.gradients_match(model, forced=False)


# ---- the selective scan -----------------------------------------------------


def _sequential(u, delta, a, b, c):
    """The recurrence as written, token by token, state [B, C, N]."""

    def token(s, inp):
        u_t, d_t, b_t, c_t = inp
        s = jnp.exp(d_t[..., None] * a) * s + (
            (d_t * u_t)[..., None] * b_t[:, None, :]
        )
        return s, jnp.sum(s * c_t[:, None, :], -1)

    _, y = jax.lax.scan(
        token, jnp.zeros(u.shape[:1] + a.shape),
        tuple(jnp.moveaxis(t, 1, 0) for t in (u, delta, b, c)),
    )
    return jnp.moveaxis(y, 0, 1)


def _operands(seq=37, rows=2, channels=24, states=4):
    k = jax.random.split(jax.random.key(3), 6)
    return (
        jax.random.normal(k[0], (rows, seq, channels)),
        jax.nn.softplus(jax.random.normal(k[1], (rows, seq, channels)) - 1.0),
        -jnp.exp(jax.random.normal(k[2], (channels, states))),
        jax.random.normal(k[3], (rows, seq, states)),
        jax.random.normal(k[4], (rows, seq, states)),
    ), jax.random.normal(k[5], (rows, seq, channels))


# 37 tokens: no chunk but 1 and 37 divides them
@pytest.mark.parametrize("chunk", [1, 8, 16, 37, 64])
def test_scan_matches_the_recurrence_value_and_derivative(chunk):
    operands, weight = _operands()
    got, got_grads = jax.value_and_grad(
        lambda *x: jnp.sum(sscan.selective_scan(*x, chunk=chunk) * weight),
        argnums=(0, 1, 2, 3, 4),
    )(*operands)
    want, want_grads = jax.value_and_grad(
        lambda *x: jnp.sum(_sequential(*x) * weight), argnums=(0, 1, 2, 3, 4)
    )(*operands)
    assert abs(float(got - want)) < 1e-4 * abs(float(want))
    for g, w in zip(got_grads, want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert float(jnp.max(jnp.abs(g - w))) < 1e-5 * float(
            jnp.max(jnp.abs(w))
        )
    assert tracing.counters()["ssm1.scan_chunk"] == min(chunk, 37)


def test_scan_keeps_chunk_starts_only():
    """The residuals of the hand-written derivative: the operands and
    one state a chunk, never a state a token."""
    operands, _ = _operands(seq=64)
    _, residuals = sscan._scan_fwd(*operands, 16)
    *kept, starts = residuals
    assert [t.shape for t in kept] == [t.shape for t in operands]
    assert starts.shape == (4, 2, 4, 24)  # [chunks, B, N, C]
    text = jax.jit(jax.grad(
        lambda *x: sscan.selective_scan(*x, chunk=16).sum(),
        argnums=(0, 1, 2, 3, 4),
    )).lower(*operands).as_text()
    assert "64x2x4x24" not in text and "2x64x24x4" not in text
    assert "16x2x4x24" in text  # one chunk's states, in the backward


def test_scan_in_bf16_operands_keeps_float32_inside():
    operands, _ = _operands()
    u, delta, a, b, c = operands
    got = sscan.selective_scan(
        u.astype(jnp.bfloat16), delta, a, b.astype(jnp.bfloat16),
        c.astype(jnp.bfloat16), chunk=8,
    )
    assert got.dtype == jnp.bfloat16
    want = _sequential(*operands)
    assert float(jnp.max(jnp.abs(got - want))) < 3e-2 * float(
        jnp.max(jnp.abs(want))
    )


# ---- parts, layers, runs ----------------------------------------------------


def test_two_parts_are_one_published_layer(model):
    """``m-`` is the reference's one Mamba layer, ``*-`` its attention
    layer: the program run part by part against the reference's layer
    equations on the same input."""
    cfg, params = model
    x = jax.random.normal(jax.random.key(5), (2, SEQ, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
    sizes = _sizes(cfg)

    def attn_fn(q, k, v, **_):
        from dlrover_tpu.ops.attention import mha_reference

        return mha_reference(q, k, v, causal=True)

    for unit, name in (("m-", "mamba1"), ("*-", "attention")):
        got = x
        for letter in unit:
            stack = params["layers"][
                "mlp.1" if unit == "*-" and letter == "-"
                else decoder.PARTS[letter].stack
            ]
            got, _ = decoder._part_body(
                got, jax.tree.map(lambda t: t[0], stack), positions,
                letter=letter, cfg=cfg, mesh=None, attn_fn=attn_fn,
            )
        f32 = lambda tree: jax.tree.map(lambda t: t[0].astype(plain.F32), tree)
        # the first Mamba-1 layer's MLP is the first of ``mlp``, the
        # attention layer's the one of ``mlp.1``
        mlp_stack = "mlp" if unit == "m-" else "mlp.1"
        mixer, mlp = f32(params["layers"][name]), f32(params["layers"][mlp_stack])
        with jax.default_matmul_precision("highest"):
            h = plain._rms(x, mixer["ln"], sizes)
            want = x + (
                plain._mamba(h, mixer["ssm1"], sizes) if unit == "m-"
                else plain._plain_attention(h, mixer["attn"], sizes, 8)
            )
            h = plain._rms(want, mlp["ln"], sizes)
            want = want + (
                jax.nn.silu(h @ mlp["mlp"]["w_gate"]) * (h @ mlp["mlp"]["w_up"])
            ) @ mlp["mlp"]["w_down"]
        assert float(jnp.max(jnp.abs(got - want))) < 1e-4, unit


@pytest.mark.parametrize("pattern, runs", [
    ("m-" * 7 + "*-" + "m-" * 6, [("m-", 7), ("*-", 1), ("m-", 6)]),
    ("MEMEMEMEM*E", [(c, 1) for c in "MEMEMEMEM*E"]),
    ("m-m-*-m-m-", [("m-", 2), ("*-", 1), ("m-", 2)]),
    ("MM**", [("M", 2), ("*", 2)]),
    ("EEE", [("E", 1)] * 3),
    ("m-*-m-*-m-", [("m-*-", 2), ("m-", 1)]),
    ("*-", [("*-", 1)]),
])
def test_runs_of_like_layers(pattern, runs):
    assert decoder._pattern_runs(pattern) == runs
    assert sum(len(u) * n for u, n in runs) == len(pattern)
    assert pattern_layers(pattern) == sum(
        len(pattern_parts(u)) * n for u, n in runs
    )


def test_scanned_runs_are_bit_equal_to_unrolled(model, monkeypatch):
    cfg, params = model
    batch = _batch()

    def step():
        # a function of its own each time: jit keeps a trace by function
        def both(params):
            loss, grads = jax.value_and_grad(
                lambda p: decoder.loss_fn(p, batch, cfg=cfg)[0]
            )(params)
            return decoder.forward(params, batch["tokens"], cfg), loss, grads

        return jax.jit(both)(params)

    scanned = step()
    assert tracing.counters()["pattern.scanned_parts"] == 8
    def unrolled_run(repeat, x, stacks):
        for r in range(jax.tree.leaves(stacks)[0].shape[0]):
            x, _ = repeat(x, jax.tree.map(lambda t: t[r], stacks))
        return x, None

    monkeypatch.setattr(decoder, "_scan_run", unrolled_run)
    unrolled = step()
    for a, b in zip(jax.tree.leaves(scanned), jax.tree.leaves(unrolled)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def _scans(jaxpr):
    """``scan`` equations of a jaxpr, those inside its equations too."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "scan"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _scans(sub)
    return n


def test_counters_and_scans_of_the_traced_trunk(model):
    cfg, params = model
    tracing._counters.clear()
    jaxpr = jax.make_jaxpr(
        lambda p, t: decoder.forward(p, t, cfg)
    )(params, _batch()["tokens"])
    counters = tracing.counters()
    assert counters["ssm1.layers"] == 4
    assert counters["ssm1.scan_chunk"] == SEQ  # one chunk: 40 < 128
    assert counters["pattern.scanned_parts"] == 8
    # two runs, and the selective scan's own two loops in each
    assert _scans(jaxpr.jaxpr) == 2 + 2 * 2


def test_a_mamba2_pattern_traces_no_scan_of_layers(monkeypatch):
    """Nemotron's kind of trunk is what it was: every part unrolled (its
    trace is the trace with no run ever scanned), no counter of the
    Mamba-1 mixer, none of its parts in a scan."""
    cfg = get_config(
        "nemotron-3-super", n_layer=5, layer_pattern="MEM*E", d_model=64,
        n_head=4, n_kv_head=2, d_head=16, vocab_size=256, max_seq=64,
        mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
        ssm_chunk=16, ssm_head_block=4, n_experts=16, expert_top_k=6, d_expert=48,
        moe_latent_size=32, d_shared_expert=96, dtype="float32",
    )
    params = decoder.init(jax.random.key(0), cfg)
    tracing._counters.clear()

    def traced():
        return str(jax.make_jaxpr(
            lambda p, t: decoder.forward(p, t, cfg)
        )(params, _batch()["tokens"]))

    text = traced()
    counters = tracing.counters()
    assert counters["pattern.scanned_parts"] == 0
    assert "ssm1.layers" not in counters and "ssm1.scan_chunk" not in counters
    assert counters["ssm.scan_in_kernel"] == 0
    # one stack a kind, as before there were runs
    assert {k: v["ln"]["scale"].shape[0] for k, v in params["layers"].items()
            } == {"mamba": 2, "attention": 1, "experts": 2}
    monkeypatch.setattr(decoder, "_scan_run", None)  # never reached
    assert traced() == text


# ---- the preset, the counts, the refusals -----------------------------------


def test_preset_is_the_published_model():
    cfg = get_config("jamba2-3b")
    parts = pattern_parts(cfg.layer_pattern)
    assert len(parts) == cfg.n_layer == 28
    # attn_layer_period 14, attn_layer_offset 7
    assert [i for i, p in enumerate(parts) if p == "*-"] == [7, 21]
    assert all(p == "m-" for i, p in enumerate(parts) if i % 14 != 7)
    assert (cfg.d_inner1, cfg.mamba_dt_rank, cfg.ssm_state_size) == (
        5120, 160, 16
    )
    assert (cfg.n_head, cfg.kv_heads, cfg.head_dim) == (20, 1, 128)
    assert cfg.tie_embeddings and cfg.pos == "none" and cfg.d_ff == 8192
    assert cfg.num_params() == 3_029_337_472
    assert cfg.train_only.startswith("state-space layers")
    period = get_config(
        "jamba2-3b", n_layer=14, layer_pattern=cfg.layer_pattern[:28]
    )
    assert period.num_params() == 1_598_556_096
    assert period.n_attention_layers == 1


def test_parameter_count_is_the_trees(model):
    cfg, params = model
    assert cfg.num_params() == sum(t.size for t in jax.tree.leaves(params))
    axes = decoder.logical_axes(cfg)
    assert jax.tree.structure(
        jax.tree.map(lambda t: 0, params)
    ) == jax.tree.structure(
        jax.tree.map(lambda t: 0, axes, is_leaf=lambda t: isinstance(t, tuple))
    )
    for (path, t), ax in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree.leaves(axes, is_leaf=lambda t: isinstance(t, tuple)),
    ):
        assert t.ndim == len(ax), path


def test_mixer_initialisation_is_mamba1s(model):
    cfg, params = model
    ssm = params["layers"]["mamba1"]["ssm1"]
    assert np.allclose(
        np.exp(np.asarray(ssm["a_log"])), np.arange(1, 5), rtol=1e-6
    )
    step = np.asarray(jax.nn.softplus(ssm["dt_bias"]))
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 1e-1 * 1.01
    assert np.all(np.asarray(ssm["d_skip"]) == 1.0)
    bound = cfg.mamba_dt_rank ** -0.5
    assert np.abs(np.asarray(ssm["w_dt"])).max() <= bound
    assert np.abs(np.asarray(ssm["conv_w"])).max() <= 0.5


@pytest.mark.parametrize("over, match", [
    (dict(layer_pattern="m-m-*-m-"), "names 4 layers"),
    (dict(layer_pattern="m-m-*-m-m-m-"), "names 6 layers"),
    (dict(layer_pattern="m-m-x-m-m-"), "made of M"),
    (dict(mamba_dt_rank=0), "Mamba-1 part needs"),
    (dict(mamba_expand=0), "Mamba-1 part needs"),
    (dict(act="relu2"), "dense MLP of d_ff"),
])
def test_a_pattern_the_letters_cannot_run_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        _cfg(**over)


def test_a_mixer_without_its_mlp_is_a_layer_of_its_own():
    assert pattern_parts("m-m-*-m-m") == ["m-", "m-", "*-", "m-", "m"]
    assert _cfg(layer_pattern="m-m-*-m-m").n_layer == 5


def test_cache_paths_refuse_the_model(model):
    for path in ("prefill", "sample"):
        SUITE.refuses(model, path, "jamba2-3b: state-space layers")
