"""The main path's kernels, compiled for the chip without the chip.

The TPU's compiler is installed in the sandbox and compiles for a device
that is described, not attached (``tests/described_chip.py``). These
are the first tests in the repo that the chip's compiler, not the Pallas
interpreter, decides: interpret mode accepted every kernel here while
Mosaic refused the norm backward's partials block, every prefill chunk of
256 rows or more, and the int8 paged dequant at 25 heads x 64.

Widths are GPT-2 XL's (25 heads x 64, d 1600 — the unaligned ones) and a
lane-aligned control (16 x 128, d 2048). Nothing runs, so nothing here
says a kernel is right or fast — only that the chip would take it.

This file holds the attention, alignment, norm and paged kernels, the
recipes' train steps and the cells dealt to this side;
``tests/test_tpu_compile_mixers.py`` holds the rest.
"""

import jax
import jax.numpy as jnp
import pytest
from described_chip import (  # noqa: F401 — fixtures
    BF16, F32, STEP_CASES, _STEP_MEMORY, _as_on_the_chip, _compiled_step,
    _kernel_calls, chip, compiled_kernel, topo,
)

from dlrover_tpu.models.config import get_config
from dlrover_tpu.ops import (
    pallas_align, pallas_attention, pallas_norm, pallas_paged,
)
from dlrover_tpu.serving import kv_cache as kvc


def _flash(heads, head_dim, grad):
    def build(S):
        q = S((8, 1024, heads, head_dim), BF16)

        def fwd(q, k, v):
            return pallas_attention.flash_attention(q, k, v, causal=True)

        if not grad:
            return fwd, (q, q, q)
        loss = lambda q, k, v: fwd(q, k, v).astype(F32).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2)), (q, q, q)

    return build


def _flash_window(seq, kv_heads, window):
    """A window layer's three kernels on the banded grid at a cell's own
    shape: GQA 32 / ``kv_heads`` heads of 128 over one sequence, at the
    model's blocks of 1024 (the tile comes from the window)."""
    def build(S):
        q = S((1, seq, 32, 128), BF16)
        k = S((1, seq, kv_heads, 128), BF16)

        def loss(q, k, v):
            return pallas_attention.flash_attention(
                q, k, v, causal=True, block_q=1024, block_k=1024,
                window=window,
            ).astype(F32).sum()

        return jax.grad(loss, argnums=(0, 1, 2)), (q, k, k)

    return build


def _flash_selected(grad):
    """Keye-VL-2.0's attention: GQA 32 / 4 heads of 128 over one
    sequence of 8192 with the int8 selection operand, at the model's
    blocks of 1024 (the backward's tile is 1024 x 1024 too)."""
    def build(S):
        q = S((1, 8192, 32, 128), BF16)
        k = S((1, 8192, 4, 128), BF16)
        sel = S((1, 8192, 8192), jnp.int8)

        def fwd(q, k, v, sel):
            return pallas_attention.flash_attention(
                q, k, v, causal=True, block_q=1024, block_k=1024,
                selected=sel,
            )

        if not grad:
            return fwd, (q, k, k, sel)
        loss = lambda *a: fwd(*a)[0].astype(F32).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2)), (q, k, k, sel)

    return build


def _align():
    """Keye-VL-2.0's alignment term for one layer of the cell: 16 index
    heads of 64 over one sequence of 8192 in chunks of 512, against the
    attention's 32 / 4 heads of 128, at the tiles the program picks."""
    def build(S):
        s = 8192
        args = (
            S((1, s, 16, 64), BF16), S((1, s, 64), BF16), S((1, s, 16), F32),
            S((1, s, s), jnp.int8), S((1, s, 32, 128), BF16),
            S((1, s, 4, 128), BF16), S((1, 32, s), F32),
        )
        tiles = pallas_align.tiles(s, 512, 16, 64)
        assert tiles == (256, 512)

        def fn(*a):
            return pallas_align.alignment_kl_and_grads(
                *a, 128 ** -0.5, 512, *tiles
            )

        return fn, args

    return build


def _flash_selected_by_kv_head(grad):
    """MiniCPM-SALA's sparse attention: GQA 32 / 2 heads of 128 over one
    sequence of 16,384 with an int8 selection A KV HEAD, at the model's
    blocks of 1024."""
    def build(S):
        q = S((1, 16384, 32, 128), BF16)
        k = S((1, 16384, 2, 128), BF16)
        sel = S((1, 2, 16384, 16384), jnp.int8)

        def fwd(q, k, v, sel):
            return pallas_attention.flash_attention(
                q, k, v, causal=True, block_q=1024, block_k=1024,
                selected=sel,
            )

        if not grad:
            return fwd, (q, k, k, sel)
        loss = lambda *a: fwd(*a)[0].astype(F32).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2)), (q, k, k, sel)

    return build


def _flash_gqa_256(grad):
    """Qwen3-Next's full layers: 16 query heads on 2 key-value heads of
    256 channels, one sequence of 16,384."""
    def build(S):
        q = S((1, 16384, 16, 256), BF16)
        k = S((1, 16384, 2, 256), BF16)

        def fwd(q, k, v):
            return pallas_attention.flash_attention(q, k, v, causal=True)

        if not grad:
            return fwd, (q, k, k)
        loss = lambda q, k, v: fwd(q, k, v).astype(F32).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2)), (q, k, k)

    return build


def _norm(d, grad, residual):
    def build(S):
        x, scale = S((8, 1024, d), BF16), S((d,), F32)

        def fwd(x, scale, bias, res):
            out = pallas_norm.norm(
                x, scale, bias, kind="layernorm",
                residual=res if residual else None,
            )
            return out if residual else (out,)

        if not grad:
            return fwd, (x, scale, scale, x)
        loss = lambda *a: sum(o.astype(F32).sum() for o in fwd(*a))  # noqa: E731
        argnums = (0, 1, 2, 3) if residual else (0, 1, 2)
        return jax.grad(loss, argnums=argnums), (x, scale, scale, x)

    return build


def _paged(variant, c, mode):
    def build(S):
        cfg = get_config("gpt2-1.5b")
        geom = kvc.make_geometry(
            cfg, n_slots=4, max_len=1024, page_size=16, mode=mode
        )
        pools = jax.eval_shape(lambda: kvc.init_pools(geom))
        layer = {k: S(v.shape[1:], v.dtype) for k, v in pools.items()}
        b, h, d = 4, cfg.n_head, cfg.head_dim
        q = S((b, c, h, d), BF16)
        tables = S((b, geom.max_pages_per_slot), jnp.int32)
        pos = S((b,) if variant == "decode" else (b, c), jnp.int32)
        extra = (q, q) if variant == "verify" else ()

        def fn(q, layer, tables, pos, *extra):
            kw = dict(zip(("extra_k", "extra_v"), extra))
            return pallas_paged.paged_attention(
                q, layer, tables, pos, scale=d**-0.5, kv_heads=h,
                variant=variant, **kw,
            )

        return fn, (q, layer, tables, pos, *extra)

    return build


CASES = {
    "flash-fwd-25x64-packed": (_flash(25, 64, grad=False), 1),
    "flash-bwd-25x64-packed": (_flash(25, 64, grad=True), 3),
    "flash-fwd-16x128": (_flash(16, 128, grad=False), 1),
    "flash-bwd-16x128": (_flash(16, 128, grad=True), 3),
    # a live window, the banded grid: Trinity-Mini's window layers (five
    # tiles of 512) and Mistral's (five of 1024)
    "flash-bwd-32x4x128-window2048-of-16384": (
        _flash_window(16384, 4, 2048), 3),
    "flash-bwd-32x8x128-window4096-of-8192": (
        _flash_window(8192, 8, 4096), 3),
    # latent attention expanded (GLM-4.7-Flash): 20 heads of 256
    "flash-fwd-20x256": (_flash(20, 256, grad=False), 1),
    "flash-bwd-20x256": (_flash(20, 256, grad=True), 3),
    # latent attention at 192 score channels, values padded to them
    # (Kimi-Linear): a head and a half of lanes, the tiles of 256
    "flash-fwd-32x192": (_flash(32, 192, grad=False), 1),
    "flash-bwd-32x192": (_flash(32, 192, grad=True), 3),
    # a selection of keys (Keye-VL-2.0): the ``_sel`` kernels
    # GQA at head size 256 (Qwen3-Next)
    "flash-fwd-16x2x256-of-16384": (_flash_gqa_256(grad=False), 1),
    "flash-bwd-16x2x256-of-16384": (_flash_gqa_256(grad=True), 3),
    "flash-fwd-sel-32x4x128": (_flash_selected(grad=False), 1),
    "flash-bwd-sel-32x4x128": (_flash_selected(grad=True), 3),
    # a selection a KV head (MiniCPM-SALA): the same kernels, the tile's
    # row picked by the head's group
    "flash-fwd-sel-32x2x128-by-kv-head": (
        _flash_selected_by_kv_head(grad=False), 1),
    "flash-bwd-sel-32x2x128-by-kv-head": (
        _flash_selected_by_kv_head(grad=True), 3),
    # and its alignment term (``ops/pallas_align.py``)
    "align-kl-16x64-32x4x128": (_align(), 1),
    # its two rank norms
    "norm-bwd-d768": (_norm(768, grad=True, residual=False), 1),
    "norm-bwd-d512": (_norm(512, grad=True, residual=False), 1),
    "norm-fwd-d1600": (_norm(1600, grad=False, residual=False), 1),
    "norm-bwd-d1600": (_norm(1600, grad=True, residual=False), 1),
    "norm-residual-bwd-d1600": (_norm(1600, grad=True, residual=True), 2),
    "norm-fwd-d2048": (_norm(2048, grad=False, residual=False), 1),
    "norm-bwd-d2048": (_norm(2048, grad=True, residual=False), 1),
    "norm-residual-bwd-d2048": (_norm(2048, grad=True, residual=True), 2),
    **{
        f"paged-{variant}{c}-{mode}": (_paged(variant, c, mode), 1)
        for mode in ("bf16", "int8")
        for variant, c in (("decode", 1), ("chunk", 256), ("verify", 4))
    },
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    build, n_kernels = CASES[case]
    _, text = compiled_kernel(chip, build, n_kernels)
    if case.startswith("norm-") and case.endswith("-d1600"):
        # the width as it is (PR 74): nothing rounded up to 13 whole
        # lane tiles around the kernels, no pad made and none taken off
        assert "1664" not in text
        assert " pad(" not in text and " slice(" not in text
    if "-sel-" in case:
        names = ("flash_fwd_sel", "flash_bwd_dq_sel", "flash_bwd_dkv_sel")
        assert sum(name in text for name in names) == n_kernels
    if case.startswith("align-"):
        assert "%align_kl" in text


@pytest.mark.parametrize(
    "case,kernels",
    [
        ("flash-bwd-25x64-packed",
         {"flash_fwd_packed", "flash_bwd_dq_packed", "flash_bwd_dkv_packed"}),
        ("flash-bwd-16x128", {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    ],
)
def test_flash_layout_around_the_kernels(chip, case, kernels):
    """Head size 64 runs the three packed kernels on slabs of the
    projections' own ``[8, 1024, 1600]`` arrays: no array with the heads
    padded to 26 and moved in front of the sequence (``[8,26,1024,64]``,
    ``[104,2,1024,64]``) exists around them — before PR 32 seven relayout
    passes a tensor did, 80-95 ms of GPT-2 XL's step. Head size 128 keeps
    the unpacked kernels."""
    import re

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    fn, args = CASES[case][0](struct)
    text = jax.jit(fn).lower(*args).compile().as_text()
    named = set()
    for line in text.splitlines():
        if "tpu_custom_call" in line:
            m = re.match(
                r"\s*(?:ROOT )?%\w*?(flash_(?:fwd|bwd_dq|bwd_dkv)"
                r"(?:_packed)?)[_.\d]* = ", line
            )
            assert m, line[:160]
            named.add(m.group(1))
    assert named == kernels
    for shape in ("[8,26,1024,64]", "[104,2,1024,64]"):
        assert shape not in text


def _pallas_grids(jaxpr, found):
    """{kernel name: grid} of every ``pallas_call`` in a jaxpr."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = tuple(eqn.params["grid_mapping"].grid)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr's
                if hasattr(sub, "eqns"):
                    _pallas_grids(sub, found)
    return found


@pytest.mark.parametrize(
    "case,grids",
    [
        # no window: the square, the grid before PR 48
        ("flash-bwd-16x128", {"flash_fwd": (128, 2, 2),
                              "flash_bwd_dq": (128, 2, 2),
                              "flash_bwd_dkv": (128, 2, 2)}),
        ("flash-bwd-25x64-packed", {"flash_fwd_packed": (8, 13, 2, 2),
                                    "flash_bwd_dq_packed": (8, 13, 2, 2),
                                    "flash_bwd_dkv_packed": (8, 13, 2, 2)}),
        ("flash-bwd-sel-32x4x128", {"flash_fwd_sel": (32, 8, 8),
                                    "flash_bwd_dq_sel": (32, 8, 8),
                                    "flash_bwd_dkv_sel": (32, 8, 8)}),
        # a live window: the forward a band of 3 blocks of 1024 and the
        # backward one of 5 of 512 (the square had 16 x 16 of 1024);
        # Mistral's a band of 5 of 1024 (8 x 8)
        ("flash-bwd-32x4x128-window2048-of-16384",
         {"flash_fwd": (32, 16, 3), "flash_bwd_dq": (32, 32, 5),
          "flash_bwd_dkv": (32, 32, 5)}),
        ("flash-bwd-32x8x128-window4096-of-8192",
         {"flash_fwd": (32, 8, 5), "flash_bwd_dq": (32, 8, 5),
          "flash_bwd_dkv": (32, 8, 5)}),
    ],
)
def test_flash_grids(case, grids):
    """The grid each flash kernel is lowered with: without a live window
    the square ``(…, q blocks, k blocks)`` it always had, under one the
    band of key blocks the window admits, the backward's at the tile
    taken from the window."""
    fn, args = CASES[case][0](jax.ShapeDtypeStruct)
    assert _pallas_grids(jax.make_jaxpr(fn)(*args).jaxpr, {}) == grids


@pytest.mark.parametrize(
    "case", sorted(c for c in STEP_CASES if "scopes" in STEP_CASES[c])
)
def test_step_names_its_kernels_and_phases(topo, case):
    import re

    from dlrover_tpu.observability import runtime_timer

    spec = STEP_CASES[case]
    builder, text, counters = _compiled_step(topo, case)
    comm = spec["comm"]

    # every Pallas kernel's instruction is named after the kernel
    kernel_lines = [
        line for line in text.splitlines() if "tpu_custom_call" in line
    ]
    named = set()
    for line in kernel_lines:
        m = re.match(r"\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = ", line)
        assert m and m.group(1) in spec["kernels"], line[:160]
        named.add(m.group(1))
    assert named == spec["kernels"]

    # and every part of the step shows in the op_names the reducer reads
    op_names = runtime_timer.op_names_from_hlo(text)
    scopes = {runtime_timer.scope_of(n) for n in op_names.values()}
    assert spec["scopes"] <= scopes, spec["scopes"] - scopes
    phases = {
        runtime_timer.phase_of("%" + i + " = x", n)
        for i, n in op_names.items()
    }
    wanted = {"forward", "recompute", "backward", "optimizer"}
    if comm:
        wanted.add("exchange")
        plan = builder._plan
        assert counters["zero.exchange_bytes"] == (
            (plan.n_buckets + plan.n_tie_buckets) * plan.bucket_elems * 4
        )
    assert wanted <= phases, wanted - phases
    # which attention kernels a trace took, and that the packed ones
    # keep the projections' layout in the whole step too
    packed = "flash_fwd_packed" in spec["kernels"]
    assert counters["attn.heads_per_slab"] == (2 if packed else 1)
    # the norm kernels' call sites at a width off the 128 lanes (PR 74:
    # GPT-2 XL's 1,600 columns as they are, no [.., 1664] array made)
    unaligned = builder.cfg.d_model % 128 != 0
    assert (counters["norm.unaligned_calls"] > 0) == unaligned
    if unaligned:
        assert not re.search(r"[\[,]1664[\],]", text)
    # the forward grid's inner axis: the band of key blocks under a live
    # window, else every key block; the backward's tile under a window
    if "flash_fwd_sel" not in spec["kernels"]:  # (no window with one)
        seq = spec["batch"][1]
        band_blocks, window_tile = spec.get("band", (None, 0))
        assert counters["attn.window_tile"] == window_tile
        assert counters["attn.band_blocks"] == (
            band_blocks or seq // pallas_attention._fit_block(
                seq, builder.cfg.attn_block_k
            )
        )
    if packed:
        assert "[8,26,1024,64]" not in text and "[104,2,1024,64]" not in text
    # the row statistics (lse, delta) stay in the kernels' own tiles from
    # the kernel that makes them to those that read them: no slice,
    # broadcast or copy of that shape, and delta comes from the dq
    # kernel, not from a reduce over 64-lane heads (before PR 35 four
    # operations a layer, 33 ms of GPT-2 XL's step)
    assert counters["attn.delta_in_kernel"] == 1
    made = [
        line for line in text.splitlines()
        if re.match(
            r"\s*(?:ROOT )?%[\w.\-]+ = " + re.escape(spec["stat_tiles"]),
            line,
        )
    ]
    # what ``remat: full`` keeps of the attention (``decoder.
    # keeps_attention_output``: a forward kernel that executes 2,048
    # keys a query or more): where it keeps nothing, no [B, H, S] statistics exist and
    # every layer body runs its forward kernel twice
    # (the counter counts the layers that keep it)
    kept = spec.get("kept", False)
    assert counters["attn.output_kept"] == (
        builder.cfg.n_attention_layers if kept else 0
    )
    calls = {
        k: sum(bool(re.match(rf"\s*(?:ROOT )?%{k}[.\d]* = ", ln))
               for ln in kernel_lines)
        for k in spec["kernels"] if k.startswith("flash_")
    }
    fwd = next(k for k in calls if k.startswith("flash_fwd"))
    bwd = calls[fwd.replace("fwd", "bwd_dq")]
    assert calls[fwd] == (bwd if kept else 2 * bwd), calls
    if kept:
        # the statistics are kept as numbers: the forward slices the
        # [B, H, S] array out of the kernel's tiles (a fusion with the
        # tiles as its parameter) and the backward pads it into tiles
        # again, once a kernel pair; nothing else touches either
        made = [ln for ln in made if " parameter(" not in ln]
        pads = [ln for ln in made if " pad(" in ln]
        assert len(pads) == bwd and all(
            "transpose(jvp" in ln for ln in pads
        ), [ln[:160] for ln in pads]
        # (a copy-done of the shape is the compiler prefetching the
        # padded tiles to another memory space, not a pass of the step's)
        made = [
            ln for ln in made if ln not in pads and " copy-done(" not in ln
        ]
    else:
        # [B, H, S] float32, or stacked by a scan of layers
        assert not re.search(rf"f32\[(?:\d+,)?{spec['stat_rows']}\]", text)
    by_kernel = [
        re.search(r" get-tuple-element\(%(flash_\w+?)[.\d]*\), index=1", ln)
        for ln in made
    ]
    assert all(by_kernel), [ln[:160] for ln in made]
    assert {m.group(1) for m in by_kernel} == {
        k for k in spec["kernels"] if k.startswith(("flash_fwd", "flash_bwd_dq"))
    }
    assert "f32[104,2,1024,8]" not in text  # the tiles before PR 35
    assert not re.search(r"= f32\[8,1024,25\]\S* reduce\(", text)
    # the held rows' paths (PR 59) are a held model's alone: with every
    # expert here — or no routed block — a step holds no ``rows_sum``
    # call and no loop under the routed blocks' two row scopes
    row_loops = [
        name for name, op_name in op_names.items()
        if name.startswith("while")
        and runtime_timer.scope_of(op_name) in ("moe.sort", "moe.combine")
    ]
    held_rows = [ln for ln in kernel_lines if "%rows_sum" in ln]
    if "rows_sum" in spec["kernels"]:
        assert row_loops and held_rows
        assert all(
            "/moe.sort/" in ln or "/moe.combine/" in ln for ln in held_rows
        )
    else:
        assert not row_loops and not held_rows
        assert not any(
            "/moe.sort/" in ln or "/moe.combine/" in ln for ln in kernel_lines
        )
    if builder.cfg.n_experts:
        # which interior the routed blocks traced: by the held prefix
        # where a part of the experts is here, the XLA body otherwise
        assert counters["moe.experts_by_prefix"] == int(
            "experts_act" in spec["kernels"]
        )
    if spec["model"] == "olmoe-1b-7b":
        # the routed layer's grouped matmuls under their scope (by the
        # kernel's name: it has no name stack)
        grouped = [
            name for name, op_name in op_names.items()
            if name.startswith("ragged-dot-none")
            and runtime_timer.scope_of(op_name) == "moe.experts"
        ]
        assert len(grouped) == 12  # a layer: 3 forward, 3 recomputed, 6 back
    if spec["model"] == "keye-vl-2.0":
        # the alignment term through its kernel: one custom call in the
        # step, in the forward's scanned body and under the term's
        # scope; the recomputed body holds none (its derivative is a
        # kept residual)
        assert counters["attn.align_in_kernel"] == 1
        (align,) = [ln for ln in kernel_lines if "%align_kl" in ln]
        name = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", align).group(1)
        assert runtime_timer.scope_of(op_names[name]) == "attn.index_loss"
        assert runtime_timer.phase_of(align, op_names[name]) == "forward"
        # GQA 32 / 4 heads of 128 over d 2048, one scanned layer body:
        # the forward, dq and dk/dv once each (before PR 42 the forward
        # twice: ``full`` remade its output)
        flash = [ln for ln in kernel_lines if "%flash_" in ln]
        assert len(flash) == 3 and all(
            "bf16[32,8192,128]" in ln and "s8[1,8192,8192]" in ln
            for ln in flash
        )
        _no_whole_score_array(text)
    if spec["model"] == "glm-4.7-flash":
        # the flash kernels run at head size 256, all three layers
        flash = [ln for ln in kernel_lines if "%flash_" in ln]
        assert len(flash) == 3 * 3 and all(
            "bf16[40,8192,256]" in ln for ln in flash
        )
    for line in kernel_lines:
        name = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1)
        kernel, phase = name.split(".")[0], runtime_timer.phase_of(
            line, op_names[name]
        )
        if kernel.startswith("ragged-dot"):
            continue  # the compiler's kernels run in every phase
        if kernel == "rows_sum":
            # the combine's sum going forward, the dispatch's coming back
            want = "moe.sort" if phase == "backward" else "moe.combine"
            assert runtime_timer.scope_of(op_names[name]) == want
            continue
        if kernel in ("experts_act", "experts_act_bwd"):
            # the experts' interior over the held prefix (PR 72), under
            # the scope the XLA body's passes had
            assert runtime_timer.scope_of(op_names[name]) == "moe.experts"
            assert (phase == "backward") == (kernel == "experts_act_bwd")
            continue
        if kernel.startswith("flash_bwd") or kernel == "norm_bwd":
            assert phase == "backward", (name, op_names[name])
        else:
            assert phase in ("forward", "recompute"), (name, op_names[name])


def _no_whole_score_array(text):
    """A selecting model's step at 8192 tokens: no float [.., S, S] is
    ever whole — not the attention's [B, H, S, S], not an index head's
    [B, S, S], not the head-summed index scores — the selection is int8
    [B, S, S] a layer (a residual of the forward: the stacked [L, B, S,
    S] is the scan's), and no mask is among the step's results."""
    import re

    assert not re.search(r"(?:f32|bf16|f16)\[[\d,]*8192,8192\]", text)
    assert not re.search(r"pred\[[\d,]*8192,8192\]", text)
    assert "s8[1,8192,8192]" in text
    entry = next(
        ln for ln in text.splitlines() if ln.startswith("ENTRY ")
    )
    assert "8192,8192" not in entry.split("->")[-1], entry[-400:]


@pytest.mark.parametrize("case", ["zero1-dp4", "zero2-dp4"])
def test_zero_step_has_no_stream_relayout_loop(topo, case):
    """A 2-D array is tiled (8, 128) on the chip and a 1-D one by 1024,
    so a reshape between the 1-D parameter stream and
    ``[n_buckets, bucket_elems]`` compiles to a ``while`` that copies
    one 4 MiB row per trip at a twentieth of the memory's rate (17% of
    the dp=4 step before PR 26). Its signature is a ``while`` that
    carries the whole stream as a 1-D f32 array."""
    import re

    builder, text, _ = _compiled_step(topo, case)
    plan = builder._plan
    assert plan.tie_size and plan.n_buckets > 100
    streams = {plan.padded, plan.n_tie_buckets * plan.bucket_elems}
    whiles = [ln for ln in text.splitlines() if " while(" in ln]
    assert whiles  # the layer scans: the text is the step's
    for ln in whiles:
        carried = ln.split(" while(")[0]
        hit = [
            n for n in re.findall(r"f32\[(\d+)\]", carried)
            if int(n) in streams
        ]
        assert not hit, ln[:200]
    assert "tpu_custom_call" in text


def test_routed_layer_scatters_no_rows(topo):
    """Between token order and expert order rows move by gather in both
    directions of the derivative (``parallel/moe.py``'s ``inv``). A
    gather left to jax's transpose comes back as a scatter-add of
    floating-point rows under a ``moe.*`` scope, which the chip
    serialises where indices repeat: 14 ms each a step at OLMoE's 65,536
    rows before PR 30. Integer scatters (a count, a permutation) are
    allowed there; the embedding's gradient is a row scatter outside."""
    import re

    from dlrover_tpu.observability import runtime_timer

    _, text, _ = _compiled_step(topo, "olmoe-like")
    op_names = runtime_timer.op_names_from_hlo(text)
    scatters = []  # (dtype, shape, scope, line) of every scatter
    for line in text.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* scatter\(", line
        )
        if m:
            scope = runtime_timer.scope_of(op_names.get(m.group(1), ""))
            scatters.append((m.group(2), m.group(3).split(","), scope, line))
    # the text is the step's, and the pattern finds its scatters
    assert any(scope == "embed" for _, _, scope, _ in scatters)
    for dtype, shape, scope, line in scatters:
        if scope.startswith("moe.") and len(shape) > 1:
            assert dtype[0] in "su", line[:200]


def test_backward_tile_of_1024_is_refused_at_head_size_256(chip, monkeypatch):
    """Why ``BWD_BLOCK_256`` is not ``BWD_BLOCK_WIDE``: at 256 channels a
    1024 x 1024 backward tile needs 17.3 MB of the 16 MB of VMEM a kernel
    may use, and Mosaic refuses it (interpret mode takes it). The model's
    own forward blocks (``attn_block_q/k`` 1024) compile with the cut."""
    q = jax.ShapeDtypeStruct((2, 8192, 20, 256), BF16, sharding=chip)

    def loss(q, k, v):
        return pallas_attention.flash_attention(
            q, k, v, causal=True, block_q=1024, block_k=1024
        ).astype(F32).sum()

    def compiled():
        # a fresh function each time: the tile is read while tracing
        fn = jax.grad(lambda q, k, v: loss(q, k, v), argnums=(0, 1, 2))
        return jax.jit(fn).lower(q, q, q).compile()

    assert compiled().as_text().count("tpu_custom_call") == 3
    monkeypatch.setattr(pallas_attention, "BWD_BLOCK_256", (1024, 1024))
    with pytest.raises(Exception, match="vmem"):
        compiled()


def test_glm_cell_fits_the_chip_at_its_depth(topo):
    """The benchmark's GLM-4.7-Flash configuration as it is run (1 dense
    + 8 routed layers + the module, 2 x 8192 tokens) compiles for a
    described v5e and fills the chip. The count of memory made here
    does not track the chip (D18): 15.42 GB before PR 42, where the
    chip itself read 13.26, and **18.36 GB** since, over the 16.9 GB
    the runtime gives, where the chip reads **14.77** (my chip run, PR
    42) — ``full`` keeps the ten attention layers' kernel output, 1.68
    GB, and the count rises by 2.94. So the limit is this count's own,
    not the chip's: it guards a change that adds a gigabyte unseen.

    What is kept shows in the text: each of the three layer bodies (the
    dense layer, the scanned routed layers, the module's block) holds
    ONE forward kernel where it held two, the scan's residuals hold the
    stacked output ``bf16[8,2,8192,20,256]`` and the statistics as
    numbers ``f32[8,2,20,8192]``, and no stacked tile array."""
    import json
    import pathlib

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads((path / "glm-4.7-flash-ep8-1chip.json").read_text())
    STEP_CASES["glm-cell"] = dict(
        model=config["program"]["model"],
        overrides=config["program"]["overrides"],
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(2, 8192),
    )
    try:
        _, text, counters = _compiled_step(topo, "glm-cell")
    finally:
        del STEP_CASES["glm-cell"]
    stats = _STEP_MEMORY["glm-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    assert 15e9 < need < 18.7e9, need
    assert counters["attn.output_kept"] == 10  # 9 layers and the module
    assert _kernel_calls(text, "flash_fwd") == 3
    assert _kernel_calls(text, "flash_bwd_dq") == 3
    assert "bf16[8,2,8192,20,256]" in text and "f32[8,2,20,8192]" in text
    assert "f32[8,40,8192,8]" not in text
    assert stats.argument_size_in_bytes == pytest.approx(
        6 * 1_133_834_752, rel=1e-3  # bf16 parameters and two moments
    )


def test_keye_cell_compiles_at_its_depth(topo):
    """The benchmark's Keye-VL-2.0 configuration as it is run (12
    layers, 16 of 128 experts held, 1 x 8192 tokens; STEP_CASES'
    ``keye-cell`` IS the file's program): the step compiles for a
    described v5e; bf16 parameters and two moments are 7.44 GB of
    arguments and the compiler counted 17.63 GB in all before PR 38,
    where the chip itself read 14.10 GB (my chip run, PR 37: on GLM's
    cell the same count read 2.2 GB high, here 3.5). No float
    [.., 8192, 8192] array in the step, the twelve selections int8,
    none among its results.

    The alignment term is made once a step (PR 38): its derivative is
    a kept residual, bf16[12,1,8192,16,64] and two smaller. Since PR 40
    the term is the kernel ``align_kl``: nothing of the jnp rule is
    left under the term's scope — not the float32 [1, 8, 512, keys]
    score products of ``_chunk_target`` (a convolution f32[keys,512,8]
    and its exponential, one a chunk and kv group: 64 in the parent's
    text), not an index head's float32 products of a chunk's keys
    (f32[keys,512,16]: written once and read three times there), no
    convolution at all. The count of memory reads 17.40 GB against the
    parent's 16.22 (the chip itself 13.80 against 13.68: my chip runs,
    PR 40; the described-chip count has read 2.5-3.6 GB high on this
    cell since PR 37), under PR 37's 17.63.

    Since PR 42 ``full`` keeps the kernel's output at this span: the
    scanned body holds one ``flash_fwd_sel`` where it held two, the
    scan's residuals hold ``bf16[12,1,8192,32,128]`` and the statistics
    as numbers ``f32[12,1,32,8192]`` (12 x 68 MB) and no stacked tile
    array; the count reads **17.93 GB** and the chip **14.11** (13.80
    before: my chip runs, PR 42), so the limit is 18.2."""
    import json
    import pathlib
    import re

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads((path / "keye-vl-2.0-ep8-1chip.json").read_text())
    spec = STEP_CASES["keye-cell"]
    assert (spec["model"], spec["overrides"]) == (
        config["program"]["model"], config["program"]["overrides"]
    )
    _, text, counters = _compiled_step(topo, "keye-cell")
    stats = _STEP_MEMORY["keye-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    assert 15e9 < need < 18.2e9, need
    assert stats.argument_size_in_bytes == pytest.approx(
        6 * 1_240_585_984, rel=1e-3  # bf16 parameters and two moments
    )
    _no_whole_score_array(text)
    assert _kernel_calls(text, "flash_fwd_sel") == 1
    # a routed block's held rows (PR 59): the combine's sum in the
    # scanned forward body, the dispatch's derivative in the backward
    # one; the remade forward needs no combine
    assert _kernel_calls(text, "rows_sum") == 2
    # the experts' interior by the held prefix (PR 72): in the scanned
    # forward body, remade in the backward one, and its derivative there
    assert counters["moe.experts_by_prefix"] == 1
    assert _kernel_calls(text, "experts_act") == 2
    assert _kernel_calls(text, "experts_act_bwd") == 1
    assert "bf16[12,1,8192,32,128]" in text and "f32[12,1,32,8192]" in text
    assert "f32[12,32,8192,8]" not in text
    assert "s8[12,1,8192,8192]" in text  # the saved selections, stacked
    assert "bf16[12,1,8192,16,64]" in text  # and the derivative for qi
    under_term = [ln for ln in text.splitlines() if "attn.index_loss" in ln]
    assert sum("%align_kl" in ln and "custom-call(" in ln
               for ln in under_term) == 1
    assert not [ln for ln in under_term if " convolution(" in ln]
    assert not [ln for ln in under_term if " exponential(" in ln]
    assert not re.search(r"f32\[\d+,512,(?:8|16)\]", "\n".join(under_term))
    # an index head's products of a chunk's keys live only inside the
    # selection's own fusion (product, ReLU, then weighted and summed
    # over the heads before anything is written): no fusion's result,
    # no fusion's parameter
    products = [
        ln for ln in text.splitlines()
        if re.search(r"f32\[\d{3,},512,16\]", ln)
    ]
    assert len(products) == 3 * 12  # the chunks past index_topk
    assert all(
        "/attn.index/" in ln and not ln.lstrip().startswith("ROOT")
        and re.search(r" (?:convolution|broadcast|maximum)\(", ln)
        for ln in products
    ), [ln[:200] for ln in products[:3]]


def test_mellum_cell_builds_a_table_a_rope_kind(topo):
    """The benchmark's Mellum2 configuration as it is run (one period
    SSSY, 16 of 64 experts held, 1 x 32,768 tokens): the step compiles
    for a described v5e and fits the chip's 15.75 GiB; one step holds
    BOTH flash variants — the window layers' banded calls at a window of
    1,024 and the full layer's over the whole causal span —; the forward
    builds two rope tables, the plain one and YaRN's, outside the layer
    scan, and every turning of q and k sits under the scope
    ``attn.rope`` inside its kind's. ``remat: full`` keeps the full
    layer's flash output (16,896 keys a query executed) and remakes the
    window layers' (a band of two tiles of 1,024, 2,016 keys, under
    ``KEEP_ATTN_SPAN``)."""
    import json
    import pathlib
    import re

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads(
        (path / "mellum2-12b-a2.5b-ep4-1chip.json").read_text()
    )
    STEP_CASES["mellum-cell"] = dict(
        model=config["program"]["model"],
        overrides=config["program"]["overrides"],
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(1, 32768),
    )
    try:
        builder, text, counters = _compiled_step(topo, "mellum-cell")
    finally:
        del STEP_CASES["mellum-cell"]
    cfg = builder.cfg
    assert cfg.rope_kinds == ("plain", "scaled")
    stats = _STEP_MEMORY["mellum-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    # 13.78 GB = 12.83 GiB (PR 70)
    assert 12e9 < need < 15.75 * 2 ** 30, need
    assert counters["attn.rope_tables"] == 2
    assert counters["attn.scaled_rope_layers"] == 1
    assert counters["attn.window_layers"] == 3
    assert counters["attn.full_layers"] == 1
    assert counters["attn.output_kept"] == 1
    lines = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]

    def calls(kernel, scope):
        return sum(
            bool(re.match(rf"\s*(?:ROOT )?%{kernel}[.\d]* = ", ln))
            and f"/{scope}/" in ln
            for ln in lines
        )

    # the window layers' forward runs again in the recomputed forward
    assert calls("flash_fwd", "attn.window") == 2 * 3
    assert calls("flash_bwd_dq", "attn.window") == 3
    assert calls("flash_bwd_dkv", "attn.window") == 3
    assert calls("flash_fwd", "attn.full") == 1
    assert calls("flash_bwd_dq", "attn.full") == 1
    assert calls("flash_bwd_dkv", "attn.full") == 1
    assert "/attn.window/attn.rope/" in text
    assert "/attn.full/attn.rope/" in text
