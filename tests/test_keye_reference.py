"""Keye-VL-2.0's language tower (``keye-vl-2.0``: a sparse-attention
indexer and a top-k selection of keys in every layer, GQA heads wider
than d_model / n_head with a per-head QK norm, softmax top-k experts of
which a part is held) against the benchmark's plain reference, at a tiny
size on the CPU with seeded weights: the comparison the chip's cell is
judged by (``benchmarks/lib/selected.py``), the gradient term by term,
the selection's exactness, the flash kernels that take a selection
(interpreted), the shares of an expert-parallel layer adding up, the
FLOPs by hand, and the paths that refuse the model."""

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from reference_suite import Suite, seeded

from benchmarks.lib import flops, selected
from benchmarks.references import keye_vl2_plain as plain
from dlrover_tpu.models import decoder, generate, get_config
from dlrover_tpu.observability import tracing
from dlrover_tpu.ops import pallas_attention
from dlrover_tpu.ops.attention import mha_reference

CONFIG = (
    pathlib.Path(__file__).parent.parent
    / "benchmarks" / "configs" / "keye-vl-2.0-ep8-1chip.json"
)
# index_topk 40 over chunks of 32: the first chunk takes every visible
# key unscored, the second has rows on both sides of t + 1 = k
TINY = dict(
    n_layer=2, d_model=128, n_head=4, n_kv_head=2, d_head=64,
    vocab_size=512, max_seq=128, n_experts=16, expert_top_k=4, d_expert=64,
    n_experts_held=4, expert_offset=4, index_n_heads=4, index_head_dim=16,
    index_topk=40, index_chunk=32, remat="full", dtype="float32",
)
# float32 on both sides: far inside the chip's limits, so that a defect
# shows by orders of magnitude
TOLERANCES = (1e-3, 1e-3, 1e-4)
Q_BLOCK = 32


# the configuration file's ``sizes`` keys, read off a config; norm
# scales away from one, so that a scale left out shows
SUITE = Suite(
    "keye-vl-2.0", plain, TINY,
    [k for k in json.loads(CONFIG.read_text())["sizes"] if k != "norm_eps"],
    seq=128, q_block=Q_BLOCK,
    make=lambda cfg, seed: seeded(
        cfg, seed, scales=jax.random.key(9), spread=0.2, head=False
    ),
)
_cfg, _sizes = SUITE.cfg, SUITE.sizes


def _batch(cfg, seq=128, rows=2):
    tokens = jax.random.randint(
        jax.random.key(1), (rows, seq), 0, cfg.vocab_size
    )
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}


@pytest.fixture(scope="module")
def model():
    return SUITE.model()


def _judged(cfg, params, batch):
    with jax.default_matmul_precision("highest"):
        logits, choices = selected.program_logits_and_choices(
            params, batch["tokens"], cfg
        )
        program = selected.program_losses(params, batch, cfg)
        results, record = selected.compare(
            plain, params, batch, _sizes(cfg), Q_BLOCK, logits, choices,
            program, TOLERANCES,
        )
    return logits, choices, program, results, record


def test_program_agrees_with_the_plain_reference(model):
    """Every check of ``selected`` in float32: the selection exact and
    the reference's own, the experts the reference's own, the forced
    logits, loss, ``indexer_loss`` and ``moe_lb_loss`` equal; and
    free-running, where float32 moves no key, the same logits."""
    cfg, params = model
    batch = _batch(cfg)
    logits, choices, program, results, record = _judged(cfg, params, batch)
    names = [name for name, *_ in results]
    assert names == [
        "selection_valid", "choices_valid", "selection_regret",
        "selection_moved", "routing_regret", "logits_vs_reference",
        "logits_rms_vs_reference", "loss_vs_reference",
        "indexer_loss_vs_reference", "moe_lb_loss_vs_reference",
    ]
    assert all(ok for _n, ok, _v, _l in results), results
    assert choices["attn_selected"].shape == (2, 2, 128, 128)
    assert choices["attn_selected"].dtype == np.bool_
    assert record["select_regret_max"] < 1e-4
    assert program["indexer_loss"] > 0.01 and program["moe_lb_loss"] > 0
    with jax.default_matmul_precision("highest"):
        free_loss, free_logits = jax.jit(
            lambda p, b: plain.loss_and_logits(p, b, _sizes(cfg), Q_BLOCK)
        )(params, batch)
    np.testing.assert_allclose(logits, free_logits, atol=2e-4)
    assert abs(program["loss"] - float(free_loss)) < 1e-5


def test_gradients_term_by_term(model):
    """Loss and every parameter's gradient against ``jax.grad`` of the
    reference's objective under the program's selection and choices:
    the indexer's three matrices and its norm from ``indexer_loss``
    alone (the reference's cross-entropy and balance term give them
    exactly zero under forcing), everything else from the cross-entropy
    and the balance term alone, untouched by ``indexer_loss``."""
    cfg, params = model
    batch = _batch(cfg)
    sizes = _sizes(cfg)

    def program(params):
        loss, metrics = decoder.loss_fn(params, batch, cfg)
        return loss, metrics

    with jax.default_matmul_precision("highest"):
        (loss, metrics), grads = jax.jit(
            jax.value_and_grad(program, has_aux=True)
        )(params)
        _, choices = selected.program_logits_and_choices(
            params, batch["tokens"], cfg
        )

        def reference(params, indexer_term):
            ce, _, forced = plain.loss_and_logits_selected(
                params, batch, sizes, Q_BLOCK, choices
            )
            if indexer_term:
                return forced["indexer_loss"]
            return ce + forced["moe_lb_loss"]

        want_trunk = jax.jit(jax.grad(lambda p: reference(p, False)))(params)
        want_index = jax.jit(jax.grad(lambda p: reference(p, True)))(params)
        ref_total = reference(params, False) + reference(params, True)
        only_index = jax.jit(jax.grad(
            lambda p: decoder.loss_fn(p, batch, cfg)[1]["indexer_loss"]
        ))(params)
    assert float(loss) == pytest.approx(float(ref_total), rel=1e-5)
    assert float(loss) == pytest.approx(float(
        metrics["loss"] + metrics["indexer_loss"] + metrics["moe_lb_loss"]
    ), rel=1e-6)

    def split(tree):
        index = tree["layers"]["indexer"]
        rest = dict(tree, layers={
            k: v for k, v in tree["layers"].items() if k != "indexer"
        })
        return index, rest

    got_index, got_rest = split(grads)
    ref_index, _ = split(want_index)
    ce_on_index, ref_rest = split(want_trunk)
    idx_only_index, idx_only_rest = split(only_index)

    def close(got, want, what):
        flat_g = jax.tree_util.tree_leaves_with_path(got)
        flat_w = jax.tree.leaves(want)
        assert len(flat_g) == len(flat_w)
        for (path, g), w in zip(flat_g, flat_w):
            scale = float(jnp.max(jnp.abs(w))) or 1.0
            err = float(jnp.max(jnp.abs(g - w))) / scale
            assert err < 2e-4, (what, jax.tree_util.keystr(path), err)

    close(got_index, ref_index, "indexer from indexer_loss")
    close(got_rest, ref_rest, "trunk from cross-entropy and balance")
    assert all(
        float(jnp.max(jnp.abs(w))) > 0 for w in jax.tree.leaves(ref_index)
    )
    # the cross-entropy reaches no indexer parameter (reference under
    # forcing: exactly), and indexer_loss nothing but them (program)
    assert all(
        float(jnp.max(jnp.abs(w))) == 0 for w in jax.tree.leaves(ce_on_index)
    )
    assert all(
        float(jnp.max(jnp.abs(w))) == 0 for w in jax.tree.leaves(idx_only_rest)
    )
    close(idx_only_index, ref_index, "indexer_loss alone")


# widths at which the alignment kernel's tiles fit (index heads of 64,
# chunks of 128): 256 tokens in two chunks, the first under index_topk
KERNEL_FIT = dict(
    index_head_dim=64, index_chunk=128, index_topk=160, max_seq=256
)


def _engage(monkeypatch, path):
    """(configuration overrides, sequence, loss_fn's keywords) for the
    alignment term's two back ends: the jnp rule as the CPU runs it, or
    the Pallas kernels interpreted under ``attn_impl="flash"``."""
    if path == "jnp":
        return {}, 128, {}
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    return KERNEL_FIT, 256, {"attn_impl": "flash"}


@pytest.mark.parametrize("path", ["jnp", "kernel"])
@pytest.mark.parametrize("remat", ["full", "none"])
def test_alignment_term_is_made_once_a_step(monkeypatch, remat, path):
    """The gradient program of the toy model (3 layers, 2 sequences in
    chunks, 2 kv groups): the alignment term's rule tags its derivative
    ``attn_align_grad`` (for qi, ki and w) once, and the forward scan
    hands the three on stacked over the layers. On the jnp rule (128
    tokens in 4 chunks of 32) the attention-side score product of
    ``_chunk_target`` (``bqrd,bkd->brqk``: [B, keys, 32, G] before its
    transpose) stands once a chunk and kv group in the program, 8
    times: under ``remat: full`` the recomputed forward makes none
    again (16 before the derivative was a kept residual), under
    ``remat: none`` nothing is recomputed. With the kernels engaged
    (256 tokens in 2 chunks of 128) the kernel ``align_kl`` stands once
    in the program, beside the flash forward, and no score product of
    the rule's at all."""
    import re

    over, seq, kw = _engage(monkeypatch, path)
    cfg = _cfg(remat=remat, n_layer=3, **over)
    params = decoder.init(jax.random.key(0), cfg)
    batch = _batch(cfg, seq=seq)
    tracing._counters.clear()
    text = str(jax.make_jaxpr(
        jax.grad(lambda p: decoder.loss_fn(p, batch, cfg, **kw)[0])
    )(params))
    assert text.count("name=attn_align_grad") == 3
    nc = cfg.index_head_dim
    for stacked in (
        f"f32[3,2,{seq},4,{nc}]", f"f32[3,2,{seq},{nc}]", f"f32[3,2,{seq},4]"
    ):
        assert stacked in text
    products = re.findall(r":f32\[2,(\d+),32,2\] = dot_general\[", text)
    kernels = re.findall(r"name=(align_kl|flash_fwd_sel)\b", text)
    if path == "jnp":
        assert sorted(map(int, products)) == [32, 32, 64, 64, 96, 96, 128, 128]
        assert not kernels
    else:
        assert not products
        # the flash forward once more where the layer is recomputed
        assert sorted(kernels) == ["align_kl"] + ["flash_fwd_sel"] * (
            2 if remat == "full" else 1
        )
    # said by ``_selecting_attention_block`` as it chose
    assert tracing.counters()["attn.align_in_kernel"] == (path == "kernel")


def _traced_counters(cfg, seq, **kw):
    """The counters ``decoder.forward`` sets while it is traced, alone:
    no train step around it."""
    params = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, seq), jnp.int32)
    tracing._counters.clear()
    jax.eval_shape(
        lambda p, t: decoder.forward(p, t, cfg, **kw), params, tokens
    )
    return tracing.counters()


@pytest.mark.parametrize("case", ["cpu", "reference", "odd_shapes", "fits"])
def test_which_alignment_path_runs(monkeypatch, case):
    """The kernel where the attention runs the Pallas kernels and the
    shapes fit its tiles, the jnp rule everywhere else: on the CPU
    (nothing interpreted), under ``attn_impl == "reference"``, and at
    widths the tiles do not fit (index heads of 16, chunks of 32)."""
    if case != "cpu":
        monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    cfg = _cfg(**({} if case == "odd_shapes" else KERNEL_FIT))
    impl = "reference" if case == "reference" else "flash"
    assert _traced_counters(cfg, 256, attn_impl=impl)[
        "attn.align_in_kernel"
    ] == (case == "fits")
    # auto: the CPU
    assert _traced_counters(cfg, 256)["attn.align_in_kernel"] == 0
    assert (decoder.alignment_tiles(cfg, 256) is None) == (
        case in ("cpu", "odd_shapes")
    )
    # a sequence the chunks do not divide takes the rule too
    assert decoder.alignment_tiles(_cfg(**KERNEL_FIT), 192) is None


@pytest.mark.parametrize("path", ["jnp", "kernel"])
def test_remat_full_gives_what_remat_none_gives(monkeypatch, model, path):
    """Loss, ``indexer_loss`` and every parameter's gradient of the toy
    model under ``remat: full`` (the selection and the alignment term's
    derivative kept, everything else recomputed) against ``remat:
    none``, on the jnp rule and with the kernels engaged (interpreted).
    The two losses are the same forward: bit-equal. The gradients read
    bit-equal too on this CPU (before the derivative was kept they
    differed by 1.9e-9); the limit is 1e-6 of a leaf's largest entry,
    float32 rounding of a forward recomputed in another fusion, so that
    another build of XLA does not fail it."""
    cfg, params = model
    over, seq, kw = _engage(monkeypatch, path)
    if over:
        cfg = _cfg(**over)
        params = decoder.init(jax.random.key(0), cfg)
    batch = _batch(cfg, seq=seq)
    got = {}
    for remat in ("full", "none"):
        cfg_r = dataclasses.replace(cfg, remat=remat)
        got[remat] = jax.jit(jax.value_and_grad(
            lambda p, c=cfg_r: decoder.loss_fn(p, batch, c, **kw),
            has_aux=True,
        ))(params)
    (loss_f, metrics_f), grads_f = got["full"]
    (loss_n, metrics_n), grads_n = got["none"]
    assert float(loss_f) == float(loss_n)
    assert float(metrics_f["indexer_loss"]) == float(metrics_n["indexer_loss"])
    assert float(metrics_f["indexer_loss"]) > 0
    flat_f = jax.tree_util.tree_leaves_with_path(grads_f)
    flat_n = jax.tree.leaves(grads_n)
    assert len(flat_f) == len(flat_n)
    for (path_, g), w in zip(flat_f, flat_n):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        err = float(jnp.max(jnp.abs(g - w))) / scale
        assert err < 1e-6, (jax.tree_util.keystr(path_), err)


def test_train_step_says_which_alignment_path():
    """A traced train step carries ``attn.align_in_kernel``, set where
    the block chose: 0 on the CPU, where the jnp rule runs
    (``tests/test_tpu_compile.py`` reads 1 from the step compiled for
    the chip)."""
    from dlrover_tpu.parallel import MeshConfig, build_mesh
    from dlrover_tpu.train import (
        TrainStepBuilder, batch_sharding, make_optimizer,
    )
    from dlrover_tpu.train.train_step import abstract_train_state

    cfg = _cfg(**KERNEL_FIT)
    mesh = build_mesh(MeshConfig(dp=-1), devices=jax.devices()[:1])
    opt = make_optimizer(learning_rate=1e-4, warmup_steps=1, decay_steps=10)
    builder = TrainStepBuilder(cfg, mesh, opt)
    state = abstract_train_state(cfg, mesh, opt, comm=builder.comm_resolved)
    batch = {
        k: jax.ShapeDtypeStruct(
            (2, 256), jnp.int32, sharding=batch_sharding(mesh)
        )
        for k in ("tokens", "targets")
    }
    tracing._counters.clear()
    builder.build().lower(state, batch)
    assert tracing.counters()["attn.align_in_kernel"] == 0


ALIGN_CASES = {
    # four chunks of 32 under index_topk 40: the first takes every
    # visible key, the others cut; one sequence, two kv groups
    "chunks": dict(rows=1),
    "batch2": dict(rows=2),
    # attention so peaked that p underflows to exact zeros on keys the
    # indexer selected: 0 log 0 = 0, and dI = softmax(I) there
    "p_zeros": dict(rows=1, sharpen=60.0),
    # every third query's first index head has no positive product: the
    # ReLU's mask is all zero there, d_qi and d_w exactly zero
    "relu_dead": dict(rows=1, dead_head=True),
    # tiles smaller than a chunk: two query blocks a chunk, two key
    # blocks a chunk
    "small_tiles": dict(rows=2, block=16),
}
_ALIGNED = {}


def _aligned(case, monkeypatch):
    """(jnp rule's, kernel's) (value, d_qi, d_ki, d_w) of one of
    ALIGN_CASES at the file's widths (4 index heads of 16, 4 / 2
    attention heads of 64, 128 tokens), the kernel interpreted."""
    if case in _ALIGNED:
        return _ALIGNED[case]
    spec = ALIGN_CASES[case]
    cfg = _cfg()
    b, s, block = spec["rows"], 128, spec.get("block", 32)
    keys = jax.random.split(jax.random.key(11), 7)
    nj, nc, hd = cfg.index_n_heads, cfg.index_head_dim, cfg.head_dim
    qi = jax.random.normal(keys[0], (b, s, nj, nc))
    ki = jax.random.normal(keys[1], (b, s, nc))
    w = jax.random.normal(keys[2], (b, s, nj)) * (nj * nc) ** -0.5
    q = jax.random.normal(keys[3], (b, s, cfg.n_head, hd))
    k = jax.random.normal(keys[4], (b, s, cfg.kv_heads, hd))
    v = jax.random.normal(keys[5], (b, s, cfg.kv_heads, hd))
    if spec.get("dead_head"):
        ki = jnp.abs(ki)
        qi = qi.at[:, ::3, 0].set(-jnp.abs(qi[:, ::3, 0]))
    q = q * spec.get("sharpen", 1.0)
    mask = decoder._select(qi, ki, w, cfg)
    _, lse = mha_reference(
        q, k, v, causal=True, selected=mask, return_lse=True
    )
    scale = hd ** -0.5
    operands = (qi, ki, w, q, k, lse, mask)
    if spec.get("sharpen"):
        p = decoder._chunk_target(q, k, lse, mask, scale)
        assert int(jnp.sum((mask != 0) & (p == 0))) > 100
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    with jax.default_matmul_precision("highest"):
        want, (want_grads, *_) = decoder._alignment_kl_fwd(
            *operands, cfg, scale, None
        )
        got, (got_grads, *_) = decoder._alignment_kl_fwd(
            *operands, cfg, scale, (cfg.index_chunk, block, block)
        )
    if spec.get("dead_head"):
        assert float(jnp.max(jnp.abs(want_grads[0][:, ::3, 0]))) == 0
        assert float(jnp.max(jnp.abs(got_grads[0][:, ::3, 0]))) == 0
        assert float(jnp.max(jnp.abs(got_grads[2][:, ::3, 0]))) == 0
    _ALIGNED[case] = (want, *want_grads), (got, *got_grads)
    return _ALIGNED[case]


@pytest.mark.parametrize("what", ["value", "d_qi", "d_ki", "d_w"])
@pytest.mark.parametrize("case", sorted(ALIGN_CASES))
def test_alignment_kernel_against_the_jnp_rule(monkeypatch, case, what):
    """The alignment kernel (``ops/pallas_align.py``, interpreted)
    against the jnp rule ``_alignment_kl_fwd`` it stands in for: the
    value to 1e-5 of it, each derivative to 2e-4 of its largest entry
    (``test_gradients_term_by_term``'s limits; float32 reads 1e-6)."""
    want, got = _aligned(case, monkeypatch)
    i = ("value", "d_qi", "d_ki", "d_w").index(what)
    assert got[i].shape == want[i].shape and got[i].dtype == want[i].dtype
    if what == "value":
        assert float(want[0]) > 1.0
        assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
        return
    scale = float(jnp.max(jnp.abs(want[i])))
    assert scale > 0
    assert float(jnp.max(jnp.abs(got[i] - want[i]))) / scale < 2e-4


def _sorted_selection(index, qpos, k):
    """The selection by a stable sort: min(t + 1, k) visible keys of
    largest score, ties to the lower s."""
    s = index.shape[-1]
    visible = np.arange(s)[None, :] <= np.asarray(qpos)[:, None]
    out = np.zeros(index.shape, bool)
    for b in range(index.shape[0]):
        for i, t in enumerate(np.asarray(qpos)):
            score = np.where(visible[i], index[b, i], -np.inf)
            order = np.argsort(-score, kind="stable")
            out[b, i, order[: min(t + 1, k)]] = True
    return out & visible[None]


@pytest.mark.parametrize("kind", ["normal", "tied", "zeros", "extremes"])
def test_selection_is_exact(kind):
    """``_select_keys`` (bisection over the scores' bits) names exactly
    the set a stable sort names: on scores with many exact ties, with
    both zeros, and with infinities and denormals among them."""
    rng = np.random.default_rng(3)
    index = rng.normal(size=(2, 24, 96)).astype(np.float32)
    if kind == "tied":
        index = np.round(index * 2) / 2
    elif kind == "zeros":
        index = np.where(rng.random(index.shape) < 0.7, 0.0, index)
        index = np.where(rng.random(index.shape) < 0.3, -0.0, index)
    elif kind == "extremes":
        index[:, :, ::7] = np.inf
        index[:, :, 3::11] = -np.inf
        index[:, :, 5::13] = 1e-42
        index[:, :, 6::13] = -1e-42
    index = index.astype(np.float32)
    qpos = jnp.arange(60, 84, dtype=jnp.int32)
    for k in (1, 7, 64, 96, 200):
        got = np.asarray(decoder._select_keys(jnp.asarray(index), qpos, k))
        want = _sorted_selection(index, qpos, k)
        assert (got == want).all(), (kind, k, np.argwhere(got != want)[:5])
        assert (got.sum(-1) == np.minimum(np.asarray(qpos) + 1, k)).all()


def _qkv_and_selection(key, b=2, s=256, h=4, hkv=2, d=128, k=40):
    keys = jax.random.split(key, 5)
    q = jax.random.normal(keys[0], (b, s, h, d))
    kk = jax.random.normal(keys[1], (b, s, hkv, d))
    v = jax.random.normal(keys[2], (b, s, hkv, d))
    scores = jax.random.normal(keys[3], (b, s, s))
    sel = decoder._select_keys(scores, jnp.arange(s, dtype=jnp.int32), k)
    return q, kk, v, sel, jax.random.normal(keys[4], q.shape)


@pytest.mark.parametrize("what", ["out", "lse", "dq", "dk", "dv"])
def test_flash_kernels_take_a_selection(monkeypatch, what):
    """The unpacked kernels with a selection operand, interpreted: GQA 4
    / 2 heads of 128 over a random valid selection of 40 keys a query,
    two q blocks by two k blocks, against plain attention under the
    same mask: output, lse, dq, dk, dv."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    q, k, v, sel, g = _qkv_and_selection(jax.random.key(2))

    def kernel(q, k, v):
        return pallas_attention.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128, selected=sel
        )

    def plain_attention(q, k, v):
        return mha_reference(
            q, k, v, causal=True, selected=sel, return_lse=True
        )

    if what in ("out", "lse"):
        i = ("out", "lse").index(what)
        np.testing.assert_allclose(
            kernel(q, k, v)[i], plain_attention(q, k, v)[i], atol=2e-5
        )
        return
    arg = ("dq", "dk", "dv").index(what)
    got, want = (
        jax.grad(lambda *a: (f(*a)[0] * g).sum(), argnums=arg)(q, k, v)
        for f in (kernel, plain_attention)
    )
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_a_selection_of_every_key_is_the_unmasked_kernel(monkeypatch):
    """With every visible key selected the ``_sel`` kernels give what the
    kernels without the operand give, bit for bit, forward and
    backward; and lse comes back detached."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    q, k, v, _, g = _qkv_and_selection(jax.random.key(5))
    everything = jnp.ones((2, 256, 256), jnp.int8)

    def with_operand(q, k, v):
        out, lse = pallas_attention.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128,
            selected=everything,
        )
        return (out * g).sum() + lse.sum()  # lse: no gradient

    def without(q, k, v):
        return (pallas_attention.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128
        ) * g).sum()

    got = jax.grad(with_operand, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(without, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_shares_of_the_expert_parallel_layer_add_up():
    """Eight chips hold experts 0-1 ... 14-15 of one routed layer (16
    experts as 8 x 2, softmax top-4 renormalised over all four chosen)."""
    SUITE.shares_add_up(8, 2, n_experts=16, expert_offset=0)


def test_required_terms_by_hand():
    """ISSUE 37's arithmetic for the cell: a layer multiplies 26,116,096
    parameters a token (attention 18,874,368, indexer 2,260,992, router
    262,144, 8 x 16 / 128 experts of 4,718,592) and 9,437,952
    pair-channels at 8192 tokens (32 x 128 x 1,792.125 selected + 16 x
    32 x 4,096.5 scored); the head 38,895,616; 12 layers 3.4728 GFLOP a
    token, 8 layers 2.3930."""
    config = json.loads(CONFIG.read_text())
    sizes = config["sizes"]
    terms = plain.required_terms(sizes, 8192)
    assert terms["multiplied_params"] == 12 * 26_116_096 + 38_895_616
    assert terms["multiplied_params"] == 352_288_768
    assert terms["attention_pair_channels"] == 12 * 9_437_952
    assert flops.mean_span(8192, 0, 2048) == 1792.125
    assert flops.resolve(config, 8192) == pytest.approx(
        6 * 352_288_768 + 12 * 113_255_424
    )
    assert flops.resolve(config, 8192) / 1e9 == pytest.approx(3.4728, abs=5e-5)
    eight = plain.required_terms(dict(sizes, n_layer=8), 8192)
    assert flops.flops_of(eight) / 1e9 == pytest.approx(2.3930, abs=5e-5)


def test_configuration_file_is_what_the_program_runs():
    """The file resolves through the runner's ``_program_config``: every
    key of ``sizes`` equals the program's field, the published widths
    among them, and the preset is the published model."""
    from benchmarks.runners.train import _program_config

    config = json.loads(CONFIG.read_text())
    cfg = _program_config(config)
    assert (cfg.d_model, cfg.n_head, cfg.head_dim, cfg.kv_heads) == (
        2048, 32, 128, 4
    )
    assert (cfg.n_experts, cfg.expert_width, cfg.routed_top_k) == (
        128, 768, 8
    )
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        16, 64, 2048
    )
    assert cfg.rope_theta == 1e7 and cfg.qk_head_norm and not cfg.qk_norm
    assert (cfg.n_layer, cfg.experts_here, cfg.vocab_size) == (12, 16, 18992)
    assert cfg.num_params() == pytest.approx(1.2406e9, rel=1e-4)
    full = get_config("keye-vl-2.0")
    assert (full.n_layer, full.vocab_size, full.experts_here) == (
        48, 151936, 128
    )
    published = {
        "hidden_size": full.d_model, "num_attention_heads": full.n_head,
        "num_key_value_heads": full.kv_heads, "head_dim": full.head_dim,
        "moe_intermediate_size": full.expert_width,
        "num_experts_per_tok": full.routed_top_k,
        "num_local_experts": full.n_experts, "rope_theta": full.rope_theta,
    }
    for key, value in published.items():
        assert config[key] == value, key
    sa = config["sa_config"]
    assert (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"],
        sa["q_chunk_size"], sa["indexer_num_kv_heads"],
    ) == (16, 64, 2048, full.index_chunk, 1)


def test_masks_leave_only_where_asked(model):
    """``forward(..., return_aux=True)`` hands the masks over; the call
    ``loss_fn`` makes (and so the train step) stacks none."""
    cfg, params = model
    tokens = _batch(cfg)["tokens"]
    aux = jax.eval_shape(
        lambda p: decoder.forward(p, tokens, cfg, return_aux=True)[1], params
    )
    assert aux["attn_selected"].shape == (cfg.n_layer, 2, 128, 128)
    aux = jax.eval_shape(
        lambda p: decoder.forward(
            p, tokens, cfg, return_aux=True, return_selected=False
        )[1], params
    )
    assert "attn_selected" not in aux and aux["indexer_loss"].shape == ()
    jaxpr = jax.make_jaxpr(
        lambda p: decoder.loss_fn(p, _batch(cfg), cfg)
    )(params)
    assert all(
        getattr(v.aval, "shape", ())[-2:] != (128, 128)
        for v in jaxpr.jaxpr.outvars
    )


@pytest.mark.parametrize(
    "path", ["init_kv_cache", "prefill", "generate", "pipeline"]
)
def test_cache_paths_refuse_the_model_by_name(model, path):
    cfg, params = model
    tokens = _batch(cfg)["tokens"]
    with pytest.raises(
        ValueError, match="keye-vl-2.0: a learned selection of keys has no "
        "cache path"
    ):
        if path == "init_kv_cache":
            decoder.init_kv_cache(cfg, 1, 64)
        elif path == "prefill":
            decoder.prefill(params, tokens[:, :16], cfg, 64)
        elif path == "generate":
            generate.sample(
                params, cfg, tokens[:, :8], 2, jax.random.key(0)
            )
        else:
            from dlrover_tpu.parallel import MeshConfig, build_mesh

            mesh = build_mesh(
                MeshConfig(pp=2, dp=-1), devices=jax.devices()[:2]
            )
            decoder.forward(params, tokens, cfg, mesh=mesh)
