"""The routed blocks' held-rows paths (``parallel/moe.py`` with ``Held``,
``ops/pallas_rows.py``): where a device holds a part of the experts, the
combine and both hand-written derivatives go by the number of rows the
experts here received, a device value. On the CPU: the row kernel
interpreted against the XLA body it replaces, the combine's derivative
as a loop over the held prefix against the parent's whole-array body,
what lies behind the prefix never read, the count the paths get, and a
two-block model's gradient against the parent bodies'."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import get_config
from dlrover_tpu.observability import tracing
from dlrover_tpu.ops import pallas_attention, pallas_rows
from dlrover_tpu.parallel import moe

F32 = jnp.float32
EXPERTS = 16  # the router's width in the routed cases below


def _routing(t, k, share, seed):
    """gate ids [t, k] local to ``e`` held experts of ``EXPERTS`` (an id
    of ``e`` and more is elsewhere) for a held ``share``: 0 none, 1 all
    (e = EXPERTS: every pair held, still through the held path),
    ``"one"`` a single held expert that every token chooses, a fraction
    the held experts' share of a uniform router."""
    rng = np.random.default_rng(seed)
    if share == "one":
        ids = np.stack(
            [np.concatenate([[0], 1 + rng.permutation(EXPERTS - 1)[: k - 1]])
             for _ in range(t)]
        )
        return jnp.asarray(ids, jnp.int32), 1
    ids = np.stack([rng.permutation(EXPERTS)[:k] for _ in range(t)])
    if share == 0:
        return jnp.asarray(ids + 2, jnp.int32), 2  # nobody is here
    e = max(int(EXPERTS * share), 1)
    return jnp.asarray(ids, jnp.int32), e


def _sorted(t, k, d, share, dtype, seed=0):
    """One block's sorted state: what ``_ragged_ffn`` hands the combine."""
    gate_idx, e = _routing(t, k, share, seed)
    keys = jax.random.split(jax.random.key(seed), 4)
    xt = jax.random.normal(keys[0], (t, d)).astype(dtype)
    flat_idx, order, inv, sorted_in, counts = moe._sort_by_expert(
        xt, gate_idx, e, True
    )
    mask = flat_idx < e
    weights = jax.nn.softmax(jax.random.normal(keys[1], (t, k)), -1)
    weights = jnp.where(mask.reshape(t, k), weights, 0)
    out_rows = jax.random.normal(keys[2], (order.shape[0], d)).astype(dtype)
    g = jax.random.normal(keys[3], (t, d)).astype(dtype)
    return dict(
        xt=xt, order=order, inv=inv, mask=mask, counts=counts,
        weights=weights, out_rows=out_rows, g=g, e=e,
        held_rows=counts.sum(),
    )


def _xla_sum(rows, inv, mask, weights, dtype):
    """The XLA body of both sums: gather by ``inv``, select, contract."""
    t, k = weights.shape
    picked = moe._rows(rows, inv).reshape(t, k, -1)
    picked = jnp.where(mask.reshape(t, k, 1), picked, 0)
    return jnp.einsum(
        "tkd,tk->td", picked, weights, preferred_element_type=F32
    ).astype(dtype)


def _bf16_ulp(ref):
    mag = np.maximum(np.abs(ref), np.finfo(np.float32).tiny)
    return 2.0 ** (np.floor(np.log2(mag)) - 7)


def _close(got, ref32, dtype):
    """To a float32 ulp of the sum's terms (the two bodies add the same
    terms in another order); in bfloat16 one more ulp of the float32
    sum, for the one rounding."""
    got = np.asarray(got, np.float32)
    ref32 = np.asarray(ref32, np.float32)
    if jnp.dtype(dtype) == jnp.float32:
        np.testing.assert_allclose(got, ref32, rtol=2e-6, atol=2e-6)
    else:
        assert (np.abs(got - ref32) <= _bf16_ulp(ref32) + 2e-6).all()


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)


SHARES = [0, 1 / 16, 1 / 8, 1, "one"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("d", [1024, 2048])
@pytest.mark.parametrize("share", SHARES, ids=str)
@pytest.mark.parametrize("t,k", [(64, 8), (88, 2)])
def test_row_kernel_sums_the_held_rows_as_the_xla_body_does(
    interpreted, t, k, share, d, dtype
):
    """``rows_sum`` interpreted, weighted (the combine) and unweighted
    (the dispatch's derivative), against the gather-select-contract body
    it replaces; 88 x 2 rows are eleven tiles of 16, no longer tile
    divides them."""
    dt = jnp.dtype(dtype)
    s = _sorted(t, k, d, share, dt)
    n = s["order"].shape[0]
    tiles = pallas_rows.tile(t, n, d, dt)
    if n == 88:  # one expert of k = 2 here: the rows are cut to t
        assert tiles is None
        return
    assert n % tiles[0] == 0 and d % tiles[1] == 0
    if (t, k) == (88, 2):
        assert tiles[0] == 16
    token_of = s["order"] // k
    w_sorted = moe._held_weights(s["weights"], s["order"], s["held_rows"])
    got = pallas_rows.rows_sum(
        s["out_rows"], token_of, w_sorted, s["held_rows"], t, dt, tiles
    )
    ref32 = _xla_sum(
        s["out_rows"].astype(F32), s["inv"], s["mask"], s["weights"], F32
    )
    _close(got, ref32, dt)
    ones = s["mask"].reshape(t, k).astype(F32)
    got = pallas_rows.rows_sum(
        s["g"][token_of], token_of, None, s["held_rows"], t, dt, tiles
    )
    rows32 = s["g"][token_of].astype(F32)
    _close(got, _xla_sum(rows32, s["inv"], s["mask"], ones, F32), dt)


@pytest.mark.parametrize("share", [1 / 8, 1 / 2], ids=str)
def test_row_kernel_reads_nothing_behind_the_held_prefix(interpreted, share):
    """What lies in rows no expert wrote is unspecified: NaN there
    changes no sum (a select on the row index, not a product)."""
    t, k, d = 64, 2, 1024
    s = _sorted(t, k, d, share, jnp.bfloat16)
    held_rows = int(s["held_rows"])
    assert 0 < held_rows < s["order"].shape[0]
    tiles = pallas_rows.tile(t, s["order"].shape[0], d, jnp.bfloat16)
    token_of = s["order"] // k
    w_sorted = moe._held_weights(s["weights"], s["order"], s["held_rows"])
    clean = pallas_rows.rows_sum(
        s["out_rows"], token_of, w_sorted, s["held_rows"], t, jnp.bfloat16,
        tiles,
    )
    dirty_rows = s["out_rows"].at[held_rows:].set(jnp.nan)
    dirty = pallas_rows.rows_sum(
        dirty_rows, token_of, w_sorted, s["held_rows"], t, jnp.bfloat16,
        tiles,
    )
    np.testing.assert_array_equal(
        np.asarray(clean, np.float32), np.asarray(dirty, np.float32)
    )


def test_shapes_off_the_tiles_take_the_xla_body(monkeypatch):
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    assert pallas_rows.tile(64, 512, 1024, jnp.bfloat16) == (512, 1024)
    assert pallas_rows.tile(45, 90, 1024, jnp.bfloat16) is None  # rows
    assert pallas_rows.tile(64, 512, 96, jnp.bfloat16) is None  # lanes
    assert pallas_rows.tile(60, 480, 1024, jnp.bfloat16) is None  # tokens
    # 16,384 tokens' sums do not fit VMEM at 1,024 columns: narrower
    assert pallas_rows.tile(8192, 65536, 2048, jnp.bfloat16) == (2048, 1024)
    assert pallas_rows.tile(16384, 131072, 2048, jnp.bfloat16) == (2048, 512)
    # the experts' interior: (rows, columns, rows a turn)
    bf16 = jnp.bfloat16
    assert pallas_rows.act_tile(512, 1024, bf16, True) == (512, 1024, 16)
    assert pallas_rows.act_tile(512, 256, F32, False) == (512, 256, 64)
    assert pallas_rows.act_tile(90, 1024, bf16, True) is None  # rows
    assert pallas_rows.act_tile(512, 96, bf16, True) is None  # lanes
    # the derivative's five arrays of 2,048 float32 rows, each in two
    # buffers, fit VMEM at half a row of 2,048 columns
    assert pallas_rows.act_tile(65536, 2048, F32, True) == (2048, 1024, 16)
    monkeypatch.setattr(pallas_attention, "INTERPRET", False)
    assert pallas_rows.tile(64, 512, 1024, jnp.bfloat16) is None  # the CPU
    assert pallas_rows.act_tile(512, 1024, bf16, True) is None


def _interior_xla(up, gate):
    """The XLA body between the grouped matmuls (``_ragged_experts``)."""
    if gate is None:
        return jnp.square(jax.nn.relu(up))
    return jax.nn.silu(gate) * up


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("act", ["swiglu", "relu2"])
@pytest.mark.parametrize(
    "held_rows", [0, 16, 128, 256, 1024, 200],
    ids=["none", "1/64", "1/8", "1/4", "all", "inside-a-tile"],
)
def test_interior_kernels_are_the_xla_bodys_over_the_prefix(
    interpreted, held_rows, act, dtype
):
    """``experts_act`` and ``experts_act_bwd`` interpreted, eight tiles
    of 128 rows by two passes of 128 columns, against the XLA body and
    its derivative in float32 rounded once: equal on the held prefix —
    200 rows end inside the second tile and inside a turn of 16 —, and
    NaN in every input behind the prefix changes nothing there."""
    dt = jnp.dtype(dtype)
    n, d, tiles = 1024, 256, (128, 128, 16)
    keys = jax.random.split(jax.random.key(held_rows), 3)
    up, gate, d_h = (jax.random.normal(k, (n, d)).astype(dt) for k in keys)
    if act == "relu2":
        gate = None
    ref, pull = jax.vjp(
        _interior_xla, *jax.tree.map(lambda a: a.astype(F32), (up, gate))
    )
    ref_up, ref_gate = pull(d_h.astype(F32))
    behind = (jnp.arange(n) >= held_rows)[:, None]
    up, gate, d_h = jax.tree.map(
        lambda a: jnp.where(behind, jnp.nan, a), (up, gate, d_h)
    )
    count = jnp.int32(held_rows)
    got = pallas_rows.experts_act(up, gate, count, tiles)
    got_up, got_gate = pallas_rows.experts_act_bwd(up, gate, d_h, count, tiles)
    assert (got_gate is None) == (gate is None)
    for a, b in ((got, ref), (got_up, ref_up), (got_gate, ref_gate)):
        if a is None:
            continue
        assert a.dtype == dt and a.shape == (n, d)
        _close(a[:held_rows], b[:held_rows], dt)


def _parent_combine_bwd(g, out_rows, weights, order, inv):
    """``_combine_bwd``'s whole-array body (``held is None``)."""
    d_out, d_w, *_ = moe._combine_bwd(
        out_rows.dtype, (out_rows, weights, order, inv, None), g
    )
    return d_out, d_w


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("share", SHARES, ids=str)
@pytest.mark.parametrize("t,k,chunk", [(64, 8, 96), (88, 2, 1024)])
def test_combine_derivative_over_the_prefix_is_the_whole_bodys(
    monkeypatch, t, k, chunk, share, dtype
):
    """``_combine_bwd_held`` — a loop of ceil(held / chunk) turns —
    against the parent's body over every row: ``d_out`` bit-equal on the
    prefix and zero behind its last chunk, ``d_weights`` equal on the
    held pairs and zero elsewhere. A chunk of 96 does not divide 512
    rows: the last turn overlaps the one before."""
    monkeypatch.setattr(moe, "HELD_CHUNK", chunk)
    dt = jnp.dtype(dtype)
    d = 256
    s = _sorted(t, k, d, share, dt)
    held_rows = int(s["held_rows"])
    n = s["order"].shape[0]
    ref_out, ref_w = _parent_combine_bwd(
        s["g"], s["out_rows"], s["weights"], s["order"], s["inv"]
    )
    # garbage behind the prefix, as ``ragged_dot`` leaves it
    dirty = s["out_rows"].at[held_rows:].set(jnp.nan)
    got_out, got_w = moe._combine_bwd_held(
        s["g"], dirty, s["weights"], s["order"], s["held_rows"]
    )
    got_out = np.asarray(got_out, np.float32)
    np.testing.assert_array_equal(
        got_out[:held_rows], np.asarray(ref_out, np.float32)[:held_rows]
    )
    step = min(chunk, n)
    assert not got_out[min(-(-held_rows // step) * step, n):].any()
    assert np.isfinite(got_out).all()
    mask = np.asarray(s["mask"]).reshape(t, k)
    got_w = np.asarray(got_w, np.float32)
    assert not got_w[~mask].any()
    np.testing.assert_allclose(
        got_w[mask], np.asarray(ref_w, np.float32)[mask], rtol=2e-6,
        atol=2e-6,
    )


TINY = dict(
    n_layer=2, d_model=128, n_head=2, n_kv_head=2, d_ff=128, vocab_size=256,
    max_seq=64, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=24,
    qk_rope_head_dim=8, v_head_dim=32, d_expert=128, n_experts=8,
    expert_top_k=2, n_experts_held=4, expert_offset=2, remat="full",
    dtype="float32",
)


def _two_blocks(cfg, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    blocks = [moe.init_moe_params(k, cfg, lead=()) for k in keys[:2]]
    x = jax.random.normal(keys[2], (2, 32, cfg.d_model))

    def loss(blocks, x):
        held = []
        for block in blocks:
            out, aux = moe.moe_block(x, block, cfg, return_aux=True)
            x = x + out
            held.append(aux["moe_held_rows"])
        return jnp.mean(x * x), jnp.stack(held)

    return blocks, x, loss


def _with_parent_bodies(monkeypatch):
    """The parent's program for a held model: the XLA sums, the XLA
    interior and the combine's derivative over every row."""
    monkeypatch.setattr(pallas_rows, "tile", lambda *a, **k: None)
    monkeypatch.setattr(pallas_rows, "act_tile", lambda *a, **k: None)

    def whole(g, out_rows, weights, order, held_rows):
        k = weights.shape[1]
        inv = jnp.argsort(order)
        if order.shape[0] < weights.size:  # the rows were cut
            raise AssertionError("k <= e in this test")
        d_out, d_w = _parent_combine_bwd(g, out_rows, weights, order, inv)
        return d_out, d_w.reshape(-1, k)

    monkeypatch.setattr(moe, "_combine_bwd_held", whole)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "xla"])
def test_two_held_blocks_gradient_is_the_parent_bodies(monkeypatch, kernel):
    """Loss and every gradient of two routed blocks in a row, each
    holding experts 2-5 of 8, through the held-rows paths (the kernels
    interpreted — the sums and the experts' interior —, or the XLA
    bodies with the prefix loop) against the parent's bodies; and the
    count the paths get is the rows the held experts received."""
    cfg = get_config("glm-4.7-flash", **TINY)
    blocks, x, loss = _two_blocks(cfg)
    monkeypatch.setattr(pallas_attention, "INTERPRET", kernel)
    seen = []
    real = pallas_rows.rows_sum

    def watched(rows, token_of, weights, held_rows, *a):
        seen.append(held_rows)
        return real(rows, token_of, weights, held_rows, *a)

    monkeypatch.setattr(pallas_rows, "rows_sum", watched)
    for name in ("experts_act", "experts_act_bwd"):
        def watched_act(*args, real=getattr(pallas_rows, name)):
            seen.append(args[-2])
            return real(*args)

        monkeypatch.setattr(pallas_rows, name, watched_act)
    (got, held), got_grads = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True
    )(blocks, x)
    assert tracing.counters()["moe.experts_by_prefix"] == int(kernel)
    # each block's combine and its dispatch's derivative, its interior
    # going forward, remade under ``remat`` or not, and coming back: the
    # count they get is the step metric's, the held experts' group sizes
    # summed
    assert len(seen) == (8 if kernel else 0)
    for count in seen:
        assert count.dtype == jnp.int32
        assert int(count) in [int(h) for h in held]
    assert 0 < int(held.min()) and int(held.max()) < 2 * 32 * 2

    monkeypatch.undo()
    _with_parent_bodies(monkeypatch)
    (ref, ref_held), ref_grads = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True
    )(blocks, x)
    np.testing.assert_array_equal(held, ref_held)
    np.testing.assert_allclose(got, ref, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "xla"])
def test_garbage_behind_the_groups_reaches_no_loss_or_gradient(
    monkeypatch, kernel
):
    """``ragged_dot`` leaves finite garbage in the rows behind its last
    group and its transposes leave it in their cotangents; here the
    experts leave NaN there, forward and backward, and so does their
    interior where it goes by the prefix (the rows its kernels do not
    write). The blocks' loss and gradients stay finite and equal to the
    clean run's."""
    cfg = get_config("glm-4.7-flash", **TINY)
    blocks, x, loss = _two_blocks(cfg, seed=1)
    monkeypatch.setattr(pallas_attention, "INTERPRET", kernel)
    grad = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
    (clean, _), clean_grads = grad(blocks, x)
    real = moe._ragged_experts

    @jax.custom_vjp
    def poison_out(rows, live):  # NaN behind the groups, going forward
        return jnp.where(live[:, None], rows, jnp.nan)

    poison_out.defvjp(
        lambda rows, live: (poison_out(rows, live), live),
        lambda live, g: (jnp.where(live[:, None], g, 0), None),
    )

    @jax.custom_vjp
    def poison_back(rows, live):  # NaN behind the groups, coming back
        return rows

    poison_back.defvjp(
        lambda rows, live: (rows, live),
        lambda live, g: (jnp.where(live[:, None], g, jnp.nan), None),
    )

    def poisoned(rows, w_up, w_gate_proj, w_down, group_sizes, held):
        live = jnp.arange(rows.shape[0]) < group_sizes.sum()
        out = real(
            poison_back(rows, live), w_up, w_gate_proj, w_down, group_sizes,
            held,
        )
        return poison_out(out, live)

    monkeypatch.setattr(moe, "_ragged_experts", poisoned)
    for name in ("experts_act", "experts_act_bwd"):
        def unwritten(*args, real=getattr(pallas_rows, name)):
            behind = (jnp.arange(args[0].shape[0]) >= args[-2])[:, None]
            return jax.tree.map(
                lambda a: jnp.where(behind, jnp.nan, a), real(*args)
            )

        monkeypatch.setattr(pallas_rows, name, unwritten)
    (dirty, _), dirty_grads = grad(blocks, x)
    assert np.isfinite(dirty)
    np.testing.assert_allclose(dirty, clean, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(dirty_grads), jax.tree.leaves(clean_grads)):
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


OLMOE_TINY = dict(
    n_layer=2, d_model=128, n_head=2, n_kv_head=2, d_ff=128, d_expert=128,
    n_experts=8, expert_top_k=2, vocab_size=256, max_seq=64, remat="full",
    dtype="float32",
)


@pytest.mark.parametrize(
    "case,by_prefix",
    [("held", 1), ("every-expert-here", 0), ("held-under-a-mesh", 0)],
)
def test_counter_says_which_interior_a_block_traces(
    interpreted, case, by_prefix
):
    """``moe.experts_by_prefix`` is set while a routed block is traced: 1
    where the experts' interior goes by the held prefix (a part of the
    experts on one device), 0 where the XLA body runs — every expert
    here (OLMoE's structure), or a mesh of several devices around the
    block (a Mosaic call is not partitioned)."""
    from dlrover_tpu.parallel import MeshConfig, build_mesh

    cfg, mesh = get_config("glm-4.7-flash", **TINY), None
    if case == "every-expert-here":
        cfg = get_config("olmoe-1b-7b", **OLMOE_TINY)
    if case == "held-under-a-mesh":
        mesh = build_mesh(MeshConfig(dp=2), devices=jax.devices()[:2])
    block = jax.eval_shape(
        lambda: moe.init_moe_params(jax.random.key(0), cfg, lead=())
    )
    x = jax.ShapeDtypeStruct((2, 32, cfg.d_model), F32)
    tracing._counters.pop("moe.experts_by_prefix", None)
    text = str(jax.make_jaxpr(
        jax.grad(lambda x, b: moe.moe_block(x, b, cfg, mesh=mesh).sum())
    )(x, block))
    assert tracing.counters()["moe.experts_by_prefix"] == by_prefix
    for kernel in ("experts_act", "experts_act_bwd"):
        assert len(re.findall(rf"name={kernel}(?!\w)", text)) == by_prefix


def _parent_ragged_experts(
    rows, w_up, w_gate_proj, w_down, group_sizes, held=None
):
    """``_ragged_experts`` as PR 71 had it."""
    with jax.named_scope("moe.experts"):
        up = jax.lax.ragged_dot(rows, w_up, group_sizes)
        if w_gate_proj is None:
            h = jnp.square(jax.nn.relu(up))
        else:
            h = jax.nn.silu(
                jax.lax.ragged_dot(rows, w_gate_proj, group_sizes)
            ) * up
        return jax.lax.ragged_dot(h, w_down, group_sizes)


@pytest.mark.parametrize(
    "model,overrides",
    [("olmoe-1b-7b", OLMOE_TINY),
     ("gpt2-124m", dict(n_layer=2, max_seq=64, dtype="float32"))],
    ids=["olmoe", "gpt2"],
)
def test_models_that_hold_every_expert_or_none_trace_the_parents_text(
    interpreted, monkeypatch, model, overrides
):
    """Nothing to skip, nothing changed: a model whose device holds
    every expert (``held is None``) and a dense one trace, loss and
    gradient, to the text they trace to with the parent's body between
    the grouped matmuls — even where a kernel could run."""
    from dlrover_tpu.models import decoder

    cfg = get_config(model, **overrides)
    params = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    batch = {
        k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
        for k in ("tokens", "targets")
    }
    trace = lambda: str(jax.make_jaxpr(jax.grad(
        lambda p: decoder.loss_fn(p, batch_of(p), cfg)[0]
    ))(params))

    def batch_of(_):
        return {k: jnp.zeros(v.shape, v.dtype) for k, v in batch.items()}

    ours = trace()
    monkeypatch.setattr(moe, "_ragged_experts", _parent_ragged_experts)
    assert ours == trace()
    assert ("ragged_dot" in ours) == bool(cfg.n_experts)
    assert "experts_act" not in ours
