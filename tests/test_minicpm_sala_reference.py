"""MiniCPM-SALA's architecture (``minicpm-sala``: mixer + MLP layers as
two ``layer_pattern`` parts each — ``S`` a block-sparse attention that
selects blocks of keys a KV head, ``L`` a lightning linear attention
through ``ops/ssd.py``'s scan at one head a group — with the model's
three multipliers on) against the benchmark's plain reference, at a tiny
size on the CPU with seeded weights in float32: logits, loss and every
parameter's gradient, the lightning part against a literal loop over
tokens, what the program hands the ``selected`` comparison (shape,
order, forced units), that the two KV heads choose differently, that a
short sequence runs dense, the kernels interpreted at one head of 128 a
group and under a selection a KV head, the counters and the refusals."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from reference_suite import Suite

from benchmarks.lib import selected as sel
from benchmarks.references import minicpm_sala_plain as plain
from dlrover_tpu.models import decoder, generate, get_config
from dlrover_tpu.models.config import (
    lightning_log_decay, pattern_parts, selected_span,
)
from dlrover_tpu.observability import tracing
from dlrover_tpu.ops import attention, pallas_attention, ssd

# blocks of 8 keys, 4 of them a query and KV head (the first and the two
# of a local window of 16 forced), keys pooled 4 every 2, dense up to 32
# tokens; 4 query heads on 2 KV heads of 16 channels
TINY = dict(
    n_layer=4, layer_pattern="S-L-L-L-", d_model=64, n_head=4, n_kv_head=2,
    d_head=16, d_ff=128, vocab_size=256, max_seq=64, sparse_block=8,
    index_topk=4, pool_window=4, pool_stride=2, select_init_blocks=1,
    select_local=16, select_dense_len=32, index_chunk=16, ssm_chunk=16,
    remat="full", dtype="float32",
)
SIZE_KEYS = (
    "n_layer", "layer_pattern", "d_model", "n_head", "n_kv_head", "d_head",
    "d_ff", "vocab_size", "rope_theta", "index_topk", "select_block",
    "select_groups", "pool_window", "pool_stride", "select_init_blocks",
    "select_local", "select_dense_len", "scale_emb", "residual_scale",
    "logit_scale", "tie_embeddings",
)
SEQ = 64


def _init(cfg, seed=0):
    """Seeded weights with norm scales off 1, so that each scale's place
    in the equations is compared."""
    params = decoder.init(jax.random.key(seed), cfg)

    def off_one(path, leaf):
        if path[-1].key != "scale":
            return leaf
        noise = jax.random.normal(jax.random.key(leaf.size), leaf.shape)
        return leaf * (1.0 + 0.1 * noise)

    return jax.tree_util.tree_map_with_path(off_one, params)


SUITE = Suite(
    "minicpm-sala", plain, TINY, SIZE_KEYS, seq=SEQ, q_block=16,
    make=_init, doubled=False,
)
_cfg, _sizes, _batch = SUITE.cfg, SUITE.sizes, SUITE.batch


@pytest.fixture(scope="module")
def model():
    return SUITE.model()


def _forward(params, tokens, cfg):
    """(logits, aux) of the program, once for a config, weights and
    length."""

    def forward():
        with jax.default_matmul_precision("highest"):
            return jax.jit(
                lambda p, t: decoder.forward(p, t, cfg, return_aux=True)
            )(params, tokens)

    return SUITE.once(("aux", tokens.shape), cfg, params, forward)


def test_program_matches_the_plain_reference(model):
    """Free-running: at float32 the program's selection IS the
    reference's, so logits and loss agree to rounding; and the mean
    squares of the sparse attention's output and of the lightning
    parts' fast heads' read-out are the reference's."""
    cfg, params = model
    batch = _batch()
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_logits = plain.loss_and_logits(
            params, batch, _sizes(cfg), 16
        )
        _, forced = plain.forward(params, batch["tokens"], _sizes(cfg), 16)
        loss, metrics = decoder.loss_fn(params, batch, cfg=cfg)
    logits, _ = _forward(params, batch["tokens"], cfg)
    scale = float(jnp.max(jnp.abs(ref_logits)))
    assert float(jnp.max(jnp.abs(logits - ref_logits))) / scale < 1e-5
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < 1e-6
    assert set(forced) == {"sparse_attn_out_ms", "lightning_fast_out_ms"}
    for name, want in forced.items():
        assert abs(float(metrics[name]) - float(want)) / float(want) < 1e-5


def test_every_parameters_gradient_matches_the_reference(model):
    with jax.default_matmul_precision("highest"):
        SUITE.gradients_match(model, forced=False)


def test_teacher_forced_on_the_programs_units(model):
    """What ``lib/selected.py`` asks of the program: bool [S layers x
    KV, B, S, S / b], every row a selection with its forced units, and
    at the reference's own scores no regret."""
    cfg, params = model
    batch = _batch()
    logits, aux = _forward(params, batch["tokens"], cfg)
    units = aux["attn_selected"]
    assert units.dtype == jnp.bool_ and units.shape == (2, 2, SEQ, SEQ // 8)
    assert sel._shape_fault(units.dtype, units.shape, (2, SEQ), 8, 2) is None
    assert sel.selection_faults(units, cfg.index_topk, cfg.select_block) == 0
    with jax.default_matmul_precision("highest"):
        _, forced_logits, forced = plain.loss_and_logits_selected(
            params, batch, _sizes(cfg), 16, {"attn_selected": units}
        )
    summary = sel.selection_summary(forced["selection"])
    assert int(summary["select_forced_missing"]) == 0
    assert float(summary["select_regret_max"]) == 0.0
    assert float(summary["select_moved_max"]) == 0.0
    scale = float(jnp.max(jnp.abs(forced_logits)))
    assert float(jnp.max(jnp.abs(logits - forced_logits))) / scale < 1e-5
    # every query past the forced blocks' reach has a choice to make
    assert np.isfinite(np.asarray(forced["selection"]["gap"])[:, :, 40:]).all()


def test_the_kv_heads_choose_differently_and_rows_are_group_minor():
    """Two sparse layers: rows 0, 1 are layer 0's KV heads, rows 2, 3
    layer 1's. With the rows of one layer swapped, or the layers, the
    reference's scores no longer bear the selection out."""
    cfg = _cfg(layer_pattern="S-L-S-L-")
    params = _init(cfg, seed=1)
    batch = _batch()
    _, aux = _forward(params, batch["tokens"], cfg)
    units = np.asarray(aux["attn_selected"])
    assert units.shape == (4, 2, SEQ, SEQ // 8)
    assert (units[0] != units[1]).any() and (units[2] != units[3]).any()
    assert (units[0] != units[2]).any()

    def regret(units):
        with jax.default_matmul_precision("highest"):
            forced = plain.loss_and_logits_selected(
                params, batch, _sizes(cfg), 16,
                {"attn_selected": jnp.asarray(units)},
            )[2]
        return np.asarray(sel.selection_summary(forced["selection"])[
            "select_regret_max_by_layer"
        ])

    assert (regret(units) == 0).all()
    assert (regret(units[[1, 0, 2, 3]])[:2] > 0).all()
    assert (regret(units[[2, 3, 0, 1]]) > 0).all()


def test_forced_blocks_are_taken_whatever_their_score(model):
    cfg, params = model
    _, aux = _forward(params, _batch()["tokens"], cfg)
    units = np.asarray(aux["attn_selected"])
    own = np.arange(SEQ) // 8
    for t in (0, 7, 8, 31, 40, 63):
        row = units[:, :, t]
        assert row[..., 0].all()  # the initial block
        assert row[..., own[t]].all()  # the local window's two
        assert row[..., max(own[t] - 1, 0)].all()
        assert not row[..., own[t] + 1:].any()
        assert (row.sum(-1) == min(own[t] + 1, 4)).all()


def test_a_short_sequence_runs_dense(model):
    """At most ``select_dense_len`` tokens: plain causal attention, no
    selection made or handed over; the reference likewise."""
    cfg, params = model
    batch = _batch(seq=32)
    tracing._counters.clear()
    logits, aux = _forward(params, batch["tokens"], cfg)
    assert "attn_selected" not in aux and "sparse_attn_out_ms" in aux
    with jax.default_matmul_precision("highest"):
        _, ref_logits = plain.loss_and_logits(params, batch, _sizes(cfg), 16)
    scale = float(jnp.max(jnp.abs(ref_logits)))
    assert float(jnp.max(jnp.abs(logits - ref_logits))) / scale < 1e-5
    text = str(jax.make_jaxpr(
        lambda p, t: decoder.forward(p, t, cfg)
    )(params, batch["tokens"]))
    assert "attn.block_select" not in text and "attn_selected" not in text


# ---- the lightning part -----------------------------------------------------


def _token_loop(q, k, v, decay):
    """S_t = lambda S_{t-1} + k_t^T v_t, o_t = q_t S_t, token by token:
    q, k, v [B, S, H, D], decay [H] (log lambda)."""
    lam = jnp.exp(decay)[None, :, None, None]

    def token(state, inp):
        q_t, k_t, v_t = inp
        state = lam * state + k_t[..., :, None] * v_t[..., None, :]
        return state, jnp.einsum("bhd,bhde->bhe", q_t, state)

    b, _, h, d = q.shape
    _, o = jax.lax.scan(
        token, jnp.zeros((b, h, d, d)),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v)),
    )
    return jnp.moveaxis(o, 0, 1)


def _qkv(seq=48, rows=2, heads=4, channels=16):
    keys = jax.random.split(jax.random.key(3), 4)
    shape = (rows, seq, heads, channels)
    q, k, v, w = (jax.random.normal(key, shape) for key in keys)
    return (q, k, v), w


@pytest.mark.parametrize("chunk", [8, 16, 48])
def test_scan_at_one_head_a_group_is_the_recurrence(chunk):
    """``ssd_scan(x = v, dt = 1, a = log lambda, b = k, c = q)`` with as
    many groups as heads, against the loop, value and derivative."""
    (q, k, v), weight = _qkv()
    decay = jnp.asarray(lightning_log_decay(4), jnp.float32)
    ones = jnp.ones(q.shape[:3], jnp.float32)

    def program(q, k, v):
        return jnp.sum(ssd.ssd_scan(v, ones, decay, k, q, chunk) * weight)

    def loop(q, k, v):
        return jnp.sum(_token_loop(q, k, v, decay) * weight)

    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.value_and_grad(program, (0, 1, 2))(q, k, v)
        want, want_grads = jax.value_and_grad(loop, (0, 1, 2))(q, k, v)
    assert abs(float(got - want)) < 1e-4 * abs(float(want))
    for g, w in zip(got_grads, want_grads):
        assert float(jnp.max(jnp.abs(g - w))) < 1e-4 * float(
            jnp.max(jnp.abs(w))
        )


def test_the_references_quadratic_form_is_the_recurrence():
    (q, k, v), _ = _qkv()
    decay = jnp.asarray(plain.log_decay(4))
    np.testing.assert_allclose(
        plain.log_decay(32), np.asarray(lightning_log_decay(32), np.float32)
    )
    with jax.default_matmul_precision("highest"):
        got = plain.lightning_attention(q, k, v, decay, 16)
        want = _token_loop(q, k, v, decay)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want))
    )


def test_lightning_part_is_the_published_layer(model):
    """The ``L`` part and its MLP, run part by part, against the
    reference's layer equations with the recurrence as a loop."""
    cfg, params = model
    sizes = _sizes(cfg)
    x = jax.random.normal(jax.random.key(5), (2, SEQ, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
    rope = decoder._rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    got = x
    for letter, stack in (("L", "lightning"), ("-", "mlp.1")):
        got, _ = decoder._part_body(
            got, jax.tree.map(lambda t: t[0], params["layers"][stack]),
            positions, letter=letter, cfg=cfg, mesh=None, attn_fn=None,
            rope=rope,
        )
    f32 = lambda tree: jax.tree.map(lambda t: t[0].astype(plain.F32), tree)
    mixer, mlp = f32(params["layers"]["lightning"]), f32(params["layers"]["mlp.1"])
    p, nh, hd = mixer["lin"], cfg.n_head, cfg.head_dim
    with jax.default_matmul_precision("highest"):
        h = plain._rms(x, mixer["ln"], sizes)
        heads = lambda w: (h @ w).reshape(2, SEQ, nh, hd)
        q = plain._rope(plain._rms(heads(p["wq"]), p["q_norm"], sizes), 1e4)
        k = plain._rope(plain._rms(heads(p["wk"]), p["k_norm"], sizes), 1e4)
        o = _token_loop(
            q * hd ** -0.5, k, heads(p["wv"]), jnp.asarray(plain.log_decay(nh))
        )
        o = plain._rms(o.reshape(2, SEQ, nh * hd), p["o_norm"], sizes)
        want = x + cfg.residual_scale * (
            (o * jax.nn.sigmoid(h @ p["wg"])) @ p["wo"]
        )
        h = plain._rms(want, mlp["ln"], sizes)
        want = want + cfg.residual_scale * ((
            jax.nn.silu(h @ mlp["mlp"]["w_gate"]) * (h @ mlp["mlp"]["w_up"])
        ) @ mlp["mlp"]["w_down"])
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


# ---- the kernels, interpreted -----------------------------------------------


def test_scan_kernels_at_one_head_of_128_a_group(monkeypatch):
    """The lightning shape of ``ops/pallas_ssd.py``: groups of ONE head
    of 128 channels over a state of 128 (one slab a group, one turn),
    interpreted, against the XLA body: value and every derivative."""
    keys = jax.random.split(jax.random.key(11), 4)
    shape = (1, 256, 2, 128)
    q, k, v, weight = (
        0.3 * jax.random.normal(key, shape, jnp.float32) for key in keys
    )
    decay = jnp.asarray(lightning_log_decay(2), jnp.float32)
    ones = jnp.ones(shape[:3], jnp.float32)

    def loss(q, k, v):
        return jnp.sum(ssd.ssd_scan(v, ones, decay, k, q, 128) * weight)

    tracing._counters.clear()
    want, want_grads = jax.value_and_grad(loss, (0, 1, 2))(q, k, v)
    assert tracing.counters()["ssm.scan_in_kernel"] == 0
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    assert ssd.kernel_chunk(256, 2, 128, 2, 128, 128) == 256
    got, got_grads = jax.value_and_grad(loss, (0, 1, 2))(q, k, v)
    assert tracing.counters()["ssm.scan_in_kernel"] == 1
    assert abs(float(got - want)) < 1e-4 * abs(float(want))
    for g, w in zip(got_grads, want_grads):
        assert float(jnp.max(jnp.abs(g - w))) < 1e-4 * float(
            jnp.max(jnp.abs(w))
        )


def test_sel_kernels_take_a_selection_a_kv_head(monkeypatch):
    """``flash_attention(selected=[B, G, Sq, Sk])``: the ``_sel`` kernels
    interpreted, 4 query heads on 2 KV heads each with its own mask,
    against the jnp reference: value and derivative; and one mask for
    all heads ([B, Sq, Sk]) is what it was."""
    keys = jax.random.split(jax.random.key(13), 5)
    q = jax.random.normal(keys[0], (1, 256, 4, 128), jnp.float32)
    k = jax.random.normal(keys[1], (1, 256, 2, 128), jnp.float32)
    v = jax.random.normal(keys[2], (1, 256, 2, 128), jnp.float32)
    weight = jax.random.normal(keys[3], q.shape, jnp.float32)
    mask = jax.random.bernoulli(keys[4], 0.5, (1, 2, 256, 256))
    mask = mask | jnp.eye(256, dtype=bool)  # a query sees itself

    def loss(fn, mask):
        def f(q, k, v):
            out, lse = fn(q, k, v, mask)
            return jnp.sum(out * weight), lse
        return jax.value_and_grad(f, (0, 1, 2), has_aux=True)(q, k, v)

    def plainly(q, k, v, mask):
        return attention.mha_reference(
            q, k, v, causal=True, selected=mask, return_lse=True
        )

    def kernels(q, k, v, mask):
        return pallas_attention.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128, selected=mask
        )

    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    for m in (mask, mask[:, 0]):
        (want, want_lse), want_grads = loss(plainly, m)
        (got, got_lse), got_grads = loss(kernels, m)
        assert abs(float(got - want)) < 1e-4 * abs(float(want))
        assert float(jnp.max(jnp.abs(got_lse - want_lse))) < 1e-4
        for g, w in zip(got_grads, want_grads):
            assert float(jnp.max(jnp.abs(g - w))) < 1e-4 * float(
                jnp.max(jnp.abs(w))
            )
    # the two groups' masks differ, and so do the heads' outputs
    both = kernels(q, k, v, mask)[0]
    one = kernels(q, k, v, mask[:, 0])[0]
    assert float(jnp.max(jnp.abs(both[:, :, :2] - one[:, :, :2]))) < 1e-6
    assert float(jnp.max(jnp.abs(both[:, :, 2:] - one[:, :, 2:]))) > 1e-2


# ---- the preset, the counts, the counters, the refusals ---------------------


def test_preset_is_the_published_model():
    cfg = get_config("minicpm-sala")
    parts = pattern_parts(cfg.layer_pattern)
    assert len(parts) == cfg.n_layer == 32
    kinds = "".join(p[0] for p in parts)
    assert kinds == "SLLLLLLLLSLLLLLLSSLLLLSLLLLLLSSS"
    assert set(parts) == {"S-", "L-"}
    assert (kinds.count("S"), kinds.count("L")) == (8, 24)
    assert (cfg.d_model, cfg.d_ff, cfg.vocab_size) == (4096, 16384, 73448)
    assert (cfg.n_head, cfg.kv_heads, cfg.head_dim) == (32, 2, 128)
    assert cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert (cfg.scale_emb, cfg.logit_scale) == (12.0, 256 / 4096)
    assert 9.4e9 < cfg.num_params() < 9.6e9  # the published 9B
    # the multiplier keeps the published depth whatever is run
    cut = get_config("minicpm-sala", n_layer=4, layer_pattern="S-L-L-L-")
    assert cut.residual_scale == cfg.residual_scale
    # an S layer rides out by itself, like layers are scanned
    assert decoder._pattern_runs("S-L-L-L-") == [("S-", 1), ("L-", 3)]
    assert decoder._pattern_runs("S-L-S-L-") == [
        ("S-", 1), ("L-", 1), ("S-", 1), ("L-", 1)
    ]


def test_counts_are_the_references_and_the_trees(model):
    cfg, params = model
    held = sum(int(np.prod(t.shape)) for t in jax.tree.leaves(params))
    assert cfg.num_params() == held
    for seq in (32, SEQ):  # dense, and selecting
        terms = plain.required_terms(_sizes(cfg), seq)
        want = 6.0 * terms["multiplied_params"] + 12.0 * (
            terms["attention_pair_channels"]
        )
        assert cfg.flops_per_token(seq) == pytest.approx(want, rel=1e-12)
    assert selected_span(16384, 64, 64) == 3560.5


def test_counters_of_the_traced_trunk(model):
    cfg, params = model
    tracing._counters.clear()
    jax.make_jaxpr(lambda p, t: decoder.forward(p, t, cfg))(
        params, _batch()["tokens"]
    )
    counters = tracing.counters()
    assert counters["attn.sparse_layers"] == 1
    assert counters["lin.layers"] == 3
    assert counters["attn.select_block"] == 8
    assert counters["attn.select_groups"] == 2
    assert counters["pattern.scanned_parts"] == 6
    assert counters["ssm.scan_in_kernel"] == 0


def _cuts(jaxpr):
    """Times a jaxpr (with the jaxprs its equations hold) cuts a top-k
    out of scores: ``_sortable_keys`` makes them by the one
    ``bitcast_convert_type`` of the model."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "bitcast_convert_type"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _cuts(sub)
    return n


def test_selection_is_kept_not_remade(model):
    """``remat: full`` keeps the named residual ``attn_selected``: the
    gradient's program scores and cuts once, as the forward alone."""
    cfg, params = model
    batch = _batch()
    assert "attn_selected" in decoder._kept_names(cfg, False)
    loss = lambda p: decoder.loss_fn(p, batch, cfg=cfg)[0]
    assert _cuts(jax.make_jaxpr(loss)(params).jaxpr) == 1
    assert _cuts(jax.make_jaxpr(jax.grad(loss))(params).jaxpr) == 1
    # without the name kept, the recomputed forward would make it again
    bare = get_config("minicpm-sala", **{**TINY, "remat": "none"})
    monkey = jax.checkpoint(lambda p: decoder.loss_fn(p, batch, cfg=bare)[0])
    assert _cuts(jax.make_jaxpr(jax.grad(monkey))(params).jaxpr) == 2


@pytest.mark.parametrize("call", [
    lambda cfg, p, t: decoder.prefill(p, t, cfg, 64),
    lambda cfg, p, t: decoder.init_kv_cache(cfg, 1, 64) and decoder.decode_step(
        p, t[:, 0], decoder.init_kv_cache(cfg, 1, 64), 0, cfg
    ),
    lambda cfg, p, t: generate.sample(p, cfg, t, 4, jax.random.key(0)),
])
@pytest.mark.parametrize("pattern, reason", [
    ("S-L-L-L-", "lightning"), ("S-S-S-S-", "block-sparse"),
])
def test_cache_paths_refuse_both_letters_by_name(call, pattern, reason):
    cfg = _cfg(layer_pattern=pattern)
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match=reason):
        call(cfg, None, tokens)


@pytest.mark.parametrize("over, message", [
    (dict(layer_pattern="L-L-L-L-"), "its S parts'"),
    (dict(sparse_block=0), "an S part needs"),
    (dict(pool_stride=3), "pool_stride divides"),
    (dict(index_topk=2), "fit in index_topk"),
    (dict(mtp_pattern="S-", n_mtp_module=1), "the trunk's"),
    (dict(post_norm=True), "post_norm"),
    (dict(qk_norm=True), "whole-projection"),
    (dict(layer_pattern="S-L-L-Q-"), "made of"),
])
def test_config_refusals(over, message):
    with pytest.raises(ValueError, match=message):
        _cfg(**over)


def test_multipliers_and_blocks_belong_to_a_pattern_model():
    with pytest.raises(ValueError, match="layer_pattern model's multipliers"):
        get_config("tiny", scale_emb=12.0)
    with pytest.raises(ValueError, match="the S part"):
        get_config("tiny", sparse_block=8)
