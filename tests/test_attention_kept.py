"""``remat: full`` at long spans keeps the flash kernel's output
(``flash_out``, and ``flash_lse`` as numbers) and does not run the
kernel again in the recomputed forward (``decoder.keeps_attention_output``):
the kept value is the value that would have been remade, so losses and
gradients are the remade step's; where the line falls over the
benchmark's cells; and what the residual of that name holds."""

import functools
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals
from jax.ad_checkpoint import checkpoint_policies as cp

from dlrover_tpu.models import decoder, get_config
from dlrover_tpu.models.config import mean_span
from dlrover_tpu.ops import pallas_attention

ROOT = pathlib.Path(__file__).parent.parent
SMALL = dict(n_layer=2, vocab_size=512, max_seq=256, remat="full",
             dtype="float32")
# model, overrides, the kernel a layer's forward runs
LAYERS = {
    # MHA, two heads of 128: the unpacked kernels
    "causal": ("gpt2-1.5b", dict(d_model=256, n_head=2, d_ff=512),
               "flash_fwd"),
    # GQA 2 / 1 under a window that no block boundary meets
    "windowed": ("mistral-7b", dict(d_model=256, n_head=2, n_kv_head=1,
                                    d_ff=512, attn_window=96), "flash_fwd"),
    # three heads of 64: two 128-lane slabs, the second half full
    "packed": ("gpt2-1.5b", dict(d_model=192, n_head=3, d_ff=384),
               "flash_fwd_packed"),
    # a selection of keys, its alignment term through its kernel
    "selected": ("keye-vl-2.0", dict(
        d_model=128, n_head=4, n_kv_head=2, d_head=64, n_experts=16,
        expert_top_k=4, d_expert=64, n_experts_held=4, expert_offset=4,
        index_n_heads=4, index_head_dim=64, index_topk=160, index_chunk=128,
    ), "flash_fwd_sel"),
}


def _forward_calls(fn, *args, kernel):
    """How often the program of ``fn`` holds the forward ``kernel``."""
    return len(re.findall(
        rf"name={kernel}(?!\w)", str(jax.make_jaxpr(fn)(*args))
    ))


@pytest.mark.parametrize("case", sorted(LAYERS))
def test_kept_output_gives_the_remade_gradients(monkeypatch, case):
    """Two layers through ``loss_fn`` with the kernels interpreted: with
    the line under the span the backward scan's body holds no forward
    kernel (the forward scan's one is the program's only one), over it
    one; loss and every gradient leaf are equal to the bit."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    model, over, kernel = LAYERS[case]
    cfg = get_config(model, **{**SMALL, **over})
    params = decoder.init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 256), 0, 512)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}

    got = {}
    for kept, line in ((True, 1), (False, 10 ** 9)):
        monkeypatch.setattr(decoder, "KEEP_ATTN_SPAN", line)
        assert decoder.keeps_attention_output(cfg, 256, "flash") is kept

        def step(p):  # a function of its own a side: traces are cached
            return jax.value_and_grad(
                lambda p: decoder.loss_fn(p, batch, cfg, attn_impl="flash")[0]
            )(p)

        assert _forward_calls(step, params, kernel=kernel) == (
            1 if kept else 2
        )
        got[kept] = jax.jit(step)(params)
    (loss_k, grads_k), (loss_r, grads_r) = got[True], got[False]
    assert float(loss_k) == float(loss_r)
    flat_k = jax.tree_util.tree_leaves_with_path(grads_k)
    for (path, g), w in zip(flat_k, jax.tree.leaves(grads_r)):
        assert float(jnp.max(jnp.abs(w))) > 0
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=jax.tree_util.keystr(path)
        )


def test_kept_lse_variant_gives_the_remade_gradients(monkeypatch):
    """``flash_attention_with_lse`` (the ring's primitive) under the
    same two policies, with a cotangent for lse as the ring's merge has:
    the residual named ``flash_lse`` is the [B, H, S] array the caller
    got, and the backward rebuilds the kernels' tiles from it."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    b, s, h, d = 2, 256, 3, 64
    keys = jax.random.split(jax.random.key(3), 4)
    x = jax.random.normal(keys[0], (b, s, 96))
    w_qkv = jax.random.normal(keys[1], (96, 3 * h * d)) * 0.1
    g_out = jax.random.normal(keys[2], (b, s, h, d))
    g_lse = jax.random.normal(keys[3], (b, h, s))

    def layer(lse_rows, x, w):
        q, k, v = jnp.split((x @ w).reshape(b, s, 3 * h, d), 3, axis=2)
        out, lse = pallas_attention.flash_attention_with_lse(
            q, k, v, None, None, True, d ** -0.5, 128, 128, 0, 2, lse_rows
        )
        return jnp.vdot(out, g_out) + jnp.vdot(lse, g_lse)

    kept = jax.checkpoint(
        functools.partial(layer, True),
        policy=cp.save_only_these_names("flash_out", "flash_lse"),
    )
    remade = jax.checkpoint(functools.partial(layer, False))
    grad_k, grad_r = (
        jax.grad(fn, argnums=(0, 1)) for fn in (kept, remade)
    )
    assert _forward_calls(grad_k, x, w_qkv, kernel="flash_fwd_packed") == 1
    assert _forward_calls(grad_r, x, w_qkv, kernel="flash_fwd_packed") == 2
    for a, r in zip(grad_k(x, w_qkv), grad_r(x, w_qkv)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))


def test_kept_statistics_are_numbers(monkeypatch):
    """What a policy that lists ``flash_lse`` saves: with ``lse_rows``
    the [B, H, S] numbers, without it the kernels' tile array, 8 lanes
    a row of which memory holds 128."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    q = jax.random.normal(jax.random.key(5), (2, 256, 4, 128))

    def saved(lse_rows):
        fn = jax.checkpoint(
            lambda q, k, v: pallas_attention.flash_attention(
                q, k, v, block_q=128, block_k=128, lse_rows=lse_rows
            ).sum(),
            policy=cp.save_only_these_names("flash_out", "flash_lse"),
        )
        residuals = saved_residuals(fn, q, q, q)
        return {
            tuple(aval.shape) for aval, why in residuals
            if "flash_lse" in why
        }

    assert saved(True) == {(2, 4, 256)}
    assert saved(False) == {(8, 256, 8)}


def _cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        program = json.loads((ROOT / files[cell["config"]]).read_text())[
            "program"
        ]
        traffic = json.loads(
            (ROOT / "benchmarks" / "traffic" / f"{cell['traffic']}.json")
            .read_text()
        )
        yield cell["name"], program, traffic["seq"]


# every cell: the span a query attends to (``ModelConfig.executed_span``:
# what ``flops_per_token`` counts), the keys its forward kernel executes
# at tiles of 1,024 (``pallas_attention.forward_keys``: what the rule
# reads since PR 61), and whether ``full`` keeps the attention's output
CELL_SPANS = {
    "gpt2xl-train-b8s1024": (512.5, 1024, False),
    "gpt2xl-zero1-dp4-b32s1024": (512.5, 1024, False),
    "olmoe-1chip-train-b2s4096": (2048.5, 2560, True),
    # the window live: a band of 5 of 8 key blocks
    "mistral7b-l6-train-b1s8192": (3072.25, 3840, True),
    "glm47flash-ep8-train-b2s8192": (4096.5, 4608, True),
    # the causal span, not ``index_topk``: the kernels run every block
    "keyevl2-ep8-train-b1s8192": (4096.5, 4608, True),
    "nemotron3super-ep64-train-b1s8192": (4096.5, 4608, True),
    "jamba2-3b-l14-train-b1s8192": (4096.5, 4608, True),  # its one attention
    # the causal span, not the 64 chosen blocks': every block runs
    "minicpm-sala-l4-train-b1s16384": (8192.5, 8704, True),
    # an attention kind per layer, each decided by itself: a window
    # layer attends to 1,920 keys — under the line, remade until PR 61 —
    # and its forward's band of three tiles executes 2,880: kept
    "trinitymini-ep8-train-b1s16384": {
        "F": (8192.5, 8704, True), "S": (1920.0625, 2880, True),
    },
    "qwen3next-ep16-train-b1s16384": (8192.5, 8704, True),  # its one *
    # its one latent layer, 192 score channels and values padded to them
    "kimilinear-ep16-train-b1s16384": (8192.5, 8704, True),
    # a rope per layer kind: the full layer kept; a window of 1,024 keys
    # is a band of two tiles, 2,016 keys executed — under the line by
    # 32, remade
    "mellum2-ep4-train-b1s32768": {
        "Y": (16384.5, 16896, True), "S": (1008.015625, 2016, False),
    },
    # its one attention layer of six, heads of 64, as OLMoE's span
    "lfm2-ep4-train-b8s4096": (2048.5, 2560, True),
}


def test_cell_spans_cover_the_benchmark():
    """``CELL_SPANS`` names the benchmark's workloads, all of them and
    no other."""
    assert sorted(name for name, _, _ in _cells()) == sorted(CELL_SPANS)


def _cell_keys(cfg, seq, kind):
    return pallas_attention.forward_keys(
        seq, seq, cfg.attn_block_q, cfg.attn_block_k, cfg.causal,
        cfg.kind_window(kind),
    )


@pytest.mark.parametrize("cell", sorted(CELL_SPANS))
def test_the_line_falls_between_the_cells(cell):
    """The predicate over a benchmark cell as it is run, by the keys
    its forward kernel executes: true for the nine whose forward runs
    2,560 keys a query or more — Trinity's window layers among them,
    2,880 executed for 1,920 attended to —, false for the two at 1,024
    tokens, and false everywhere on the reference attention (the CPU's
    ``auto``), under ``none`` and at a sequence the kernels do not
    tile. The attended span keeps its values: ``flops_per_token``
    counts them."""
    import dataclasses

    (program, seq), = (
        (program, seq) for name, program, seq in _cells() if name == cell
    )
    cfg = get_config(program["model"], **program["overrides"])
    assert cfg.remat == "full"
    keeps = decoder.keeps_attention_output
    kinds = decoder.attention_kinds(cfg)
    by_kind = {
        kind: (
            cfg.executed_span(seq, kind),
            _cell_keys(cfg, seq, kind),
            keeps(cfg, seq, "flash", kind=kind),
        )
        for kind in kinds
    }
    assert (by_kind[""] if kinds == ("",) else by_kind) == CELL_SPANS[cell]
    other = dataclasses.replace(cfg, remat="none")
    for kind in kinds:
        assert not keeps(cfg, seq, "reference", kind=kind)
        assert not keeps(cfg, seq, kind=kind)  # auto: CPU
        assert not keeps(cfg, seq + 64, "flash", kind=kind)
        assert not keeps(other, seq, "flash", kind=kind)


# sq, sk, q tile, k tile, causal, window: the ten cells' shapes (tiles
# of 1,024) and odd ones
EXECUTED_SHAPES = {
    "gpt2xl-s1024": (1024, 1024, 1024, 1024, True, 0),
    "olmoe-s4096": (4096, 4096, 1024, 1024, True, 0),
    "mistral-s8192-w4096": (8192, 8192, 1024, 1024, True, 4096),
    "glm-keye-nemotron-jamba-s8192": (8192, 8192, 1024, 1024, True, 0),
    "sala-trinity-full-s16384": (16384, 16384, 1024, 1024, True, 0),
    "trinity-window-s16384-w2048": (16384, 16384, 1024, 1024, True, 2048),
    # the backward's tile on Trinity's window layers, as a forward's
    "tiles-512-w2048": (8192, 8192, 512, 512, True, 2048),
    "tiles-512-causal": (4096, 4096, 512, 512, True, 0),
    "tiles-unequal": (4096, 4096, 512, 1024, True, 1536),
    "tiles-unequal-wide-q": (4096, 4096, 1024, 256, True, 700),
    # a window no block boundary meets
    "window-off-boundary": (4096, 4096, 512, 512, True, 1000),
    "window-of-one-key": (2048, 2048, 256, 256, True, 1),
    "window-96-s256": (256, 256, 1024, 1024, True, 96),
    # a window that hides nothing is no window: the square grid
    "window-is-the-sequence": (2048, 2048, 512, 512, True, 2048),
    "window-past-the-sequence": (2048, 2048, 512, 512, True, 5000),
    # more keys than queries (a cache before the queries' block), fewer
    "sk-over-sq": (1024, 4096, 512, 512, True, 0),
    "sk-over-sq-window": (1024, 4096, 256, 512, True, 768),
    "sq-over-sk": (4096, 1024, 512, 512, True, 0),
    # a tile the sequence does not take whole is refitted, as the
    # kernels' caller does
    "tile-refitted": (1536, 1536, 1024, 1024, True, 0),
    "no-mask": (2048, 2048, 512, 512, False, 0),
}


def _gate_admits(sq, sk, block_q, block_k, causal, window):
    """Brute force: the (query block, step) pairs of the forward grid
    that the kernels' run gate admits x the key tile, a query."""
    pa = pallas_attention
    bq, bk = pa._fit_block(sq, block_q), pa._fit_block(sk, block_k)
    nq, nk = sq // bq, sk // bk
    band, (k_steps, _), _ = pa._inner_grid(
        pa._gate_is_static(causal, None, None), bq, bk, nq, nk, window
    )
    admitted = 0
    for i in range(nq):
        for step in range(k_steps):
            j, in_band = pa._band_k_block(i, step, bq, bk, window, nk * band)
            admitted += bool(pa._block_runs(
                causal, False, None, i * bq, j * bk, bq, bk, window, in_band
            ))
    return admitted * bk / nq


@pytest.mark.parametrize("shape", sorted(EXECUTED_SHAPES))
def test_forward_keys_are_what_the_run_gate_admits(shape):
    """``forward_keys`` — what ``full`` decides by — against the grid
    the forward kernel is launched on, step by step through the gate
    its body asks (``_block_runs``): the same count, so the rule
    cannot drift from what runs. Never fewer than the keys the mask
    lets through."""
    sq, sk, block_q, block_k, causal, window = EXECUTED_SHAPES[shape]
    got = pallas_attention.forward_keys(
        sq, sk, block_q, block_k, causal, window
    )
    assert got == _gate_admits(sq, sk, block_q, block_k, causal, window)
    if sq == sk:
        assert got >= (mean_span(sq, window) if causal else sq)


def test_forward_keys_of_a_sequence_no_tile_fits():
    """No 128-multiple divides it: the kernels do not run (the jnp
    path), nothing is executed by them and nothing is kept."""
    assert pallas_attention._fit_block(1000, 1024) is None
    assert pallas_attention.forward_keys(1000, 1000, 1024, 1024) == 0.0


# model, overrides, sequence, kind -> keys executed, kept: the
# decision where the two counts disagree, beyond the benchmark's cells
DECISIONS = {
    # causal at 3,072: 1,536.5 attended to, three tiles' 2,048 executed
    "causal-s3072": ("gpt2-1.5b", dict(max_seq=3072), 3072, "", 2048, True),
    "causal-s2048": ("gpt2-1.5b", dict(max_seq=2048), 2048, "", 1536, False),
    # the same sequence at tiles of 512 executes fewer keys: remade
    "causal-s3072-tiles-512": (
        "gpt2-1.5b", dict(max_seq=3072, attn_block_q=512, attn_block_k=512),
        3072, "", 1792, False,
    ),
    # Trinity's window at tiles of 512 is a band of five: 2,400
    "window-2048-tiles-512": (
        "trinity-mini", dict(attn_block_q=512, attn_block_k=512),
        16384, "S", 2400, True,
    ),
    "window-1024": (
        "trinity-mini", dict(attn_window=1024), 16384, "S", 1984, False,
    ),
    "window-1024-full-layer": (
        "trinity-mini", dict(attn_window=1024), 16384, "F", 8704, True,
    ),
    # no mask: every key
    "bidirectional-s2048": (
        "bert-base", dict(max_seq=2048), 2048, "", 2048, True,
    ),
}


@pytest.mark.parametrize("case", sorted(DECISIONS))
def test_the_decision_follows_the_executed_keys(case):
    """``keeps_attention_output`` at shapes where the attended span and
    the executed keys lie on different sides of ``KEEP_ATTN_SPAN``, or
    where the tile moves the count: the kernels' count decides."""
    model, over, seq, kind, executed, kept = DECISIONS[case]
    cfg = get_config(model, remat="full", **over)
    assert _cell_keys(cfg, seq, kind) == executed
    assert decoder.keeps_attention_output(cfg, seq, "flash", kind=kind) is kept


@pytest.mark.parametrize("remat,keep,lse_named", [
    ("none", False, False), ("full", False, False), ("full", True, True),
])
def test_which_tiers_name_the_statistics(remat, keep, lse_named):
    """``full`` lists ``flash_lse`` exactly where it keeps the kernel's
    output, and the kernels' rule is then told to name the numbers
    (``forward``: ``lse_rows=kind in keep_attn``); where nothing lists
    it the residual stays the tile array and the compiled step is what
    it was. A selecting model's own two names ride beside them."""
    cfg = get_config("gpt2-1.5b", n_layer=1, remat=remat)
    names = decoder._kept_names(cfg, keep)
    assert ("flash_lse" in names) == lse_named
    assert ("flash_out" in names) == lse_named
    keye = get_config("keye-vl-2.0", n_layer=1, remat=remat)
    assert {"attn_selected", "attn_align_grad"} <= set(
        decoder._kept_names(keye, keep)
    )


_REMOVED_TIERS = (
    "dots_saveable", "save_attn", "save_qkv", "save_qkv_gate", "save_dots",
    "offload_attn", "save_qkv_offload",
)


@pytest.mark.parametrize("tier", _REMOVED_TIERS)
def test_removed_remat_tiers_are_refused(tier):
    """The graded and offloaded tiers went in PR 51: no alias and no
    fall-back to ``full``; the error names the two policies there are."""
    with pytest.raises(ValueError, match=tier) as refused:
        get_config("tiny", remat=tier)
    assert "'none'" in str(refused.value) and "'full'" in str(refused.value)


def test_the_step_tags_the_four_kept_names_and_no_other(monkeypatch):
    """Every ``checkpoint_name`` in the gradient program of a selecting
    (Keye-shaped) and a windowed (Mistral-shaped) toy model is one that
    ``full`` may keep: the kernels' two, and a selecting model's two."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    monkeypatch.setattr(decoder, "KEEP_ATTN_SPAN", 1)
    tokens = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    tagged = {}
    for case in ("selected", "windowed"):
        model, over, _ = LAYERS[case]
        cfg = get_config(model, **{**SMALL, **over})
        params = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
        text = str(jax.make_jaxpr(jax.grad(
            lambda p, t: decoder.loss_fn(
                p, {"tokens": t, "targets": t}, cfg, attn_impl="flash"
            )[0]
        ))(params, tokens))
        tagged[case] = set(re.findall(r"\bname\[name=(\w+)\]", text))
    kernels = {"flash_out", "flash_lse"}
    assert tagged == {
        "selected": kernels | {"attn_selected", "attn_align_grad"},
        "windowed": kernels,
    }


def _forward_counters(cfg, seq=256, **kw):
    """The counters ``decoder.forward`` sets while it is traced alone:
    no train step around it."""
    from dlrover_tpu.observability import tracing

    params = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    tracing._counters.clear()
    jax.eval_shape(
        lambda p, t: decoder.forward(p, t, cfg, **kw),
        params, jax.ShapeDtypeStruct((2, seq), jnp.int32),
    )
    return tracing.counters()


def test_a_path_is_reported_by_the_code_that_takes_it(monkeypatch):
    """``attn.align_in_kernel`` and ``ssm.scan_in_kernel`` are set by
    the block that chooses, so ``decoder.forward`` traced alone carries
    them for a model with the mechanism, and a model with neither
    carries neither; the layers kept are counted by ``forward`` for
    every model."""
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    model, over, _ = LAYERS["windowed"]
    plain = _forward_counters(
        get_config(model, **{**SMALL, **over}), attn_impl="flash"
    )
    assert "attn.align_in_kernel" not in plain
    assert "ssm.scan_in_kernel" not in plain
    assert plain["attn.output_kept"] == 0  # a span of 96 keys

    model, over, _ = LAYERS["selected"]
    selecting = _forward_counters(
        get_config(model, **{**SMALL, **over}), attn_impl="flash"
    )
    assert selecting["attn.align_in_kernel"] == 1
    assert "ssm.scan_in_kernel" not in selecting

    mixer = get_config(
        "nemotron-3-super", n_layer=2, layer_pattern="ME", d_model=64,
        n_head=4, n_kv_head=2, d_head=16, vocab_size=256, max_seq=256,
        mamba_num_heads=4, mamba_head_dim=64, ssm_state_size=128,
        n_groups=2, ssm_chunk=128, n_experts=16, expert_top_k=6,
        d_expert=48, moe_latent_size=32, d_shared_expert=96,
        n_experts_held=4, expert_offset=0, remat="full", dtype="float32",
    )
    scanning = _forward_counters(mixer)
    assert scanning["ssm.scan_in_kernel"] == 1
    assert "attn.align_in_kernel" not in scanning


def test_analyser_counts_the_kept_attention_output(monkeypatch):
    """Under ``full`` the activation bytes add the attention's kept
    output and row statistics where ``keeps_attention_output`` holds
    (the flash kernels, a forward that executes 2,048 keys a query or
    more): Mistral's widths at 8,192 tokens on the chip, not on the
    CPU's reference attention and not at 1,024 tokens. The estimate
    asks ``kept_attention_layers`` and so follows the rule by itself:
    Trinity-Mini's five layers at 16,384 tokens, window layers and all
    (PR 61; one before it)."""
    from dlrover_tpu.accelerate.analyser import analyse
    from dlrover_tpu.accelerate.strategy import apply_strategy
    from dlrover_tpu.common import device

    cfg = get_config("mistral-7b", n_layer=6, max_seq=8192)
    kinds = get_config(
        "trinity-mini", n_layer=5, layer_types="SSSSF", max_seq=16384
    )
    plan = apply_strategy([("mixed_parallel", {"dp": 1})])
    plan.remat, plan.compute_dtype = "full", "bfloat16"
    on_cpu = analyse(cfg, plan, 1, 1, 8192, hbm_bytes=16e9)
    kinds_on_cpu = analyse(kinds, plan, 1, 1, 16384, hbm_bytes=16e9)
    monkeypatch.setattr(device, "on_cpu", lambda: False)
    on_chip = analyse(cfg, plan, 1, 1, 8192, hbm_bytes=16e9)
    kept = 8192 * 6 * 32 * (128 * 2 + 4)  # flash_out + flash_lse, 6 layers
    assert on_chip.act_bytes_per_chip - on_cpu.act_bytes_per_chip == kept
    short = analyse(cfg, plan, 1, 8, 1024, hbm_bytes=16e9)
    assert short.act_bytes_per_chip == on_cpu.act_bytes_per_chip
    kinds_on_chip = analyse(kinds, plan, 1, 1, 16384, hbm_bytes=16e9)
    assert (
        kinds_on_chip.act_bytes_per_chip - kinds_on_cpu.act_bytes_per_chip
        == 16384 * 5 * 32 * (128 * 2 + 4)
    )
