"""The second compile file (``tests/described_chip.py`` says why there
are two): the kernels of the mixers (state-space scans, the delta
rules, the causal and the gated conv, the L2 norm a head) and of the
routed experts' rows, each compiled for a described v5e, their bodies'
build budget, and the cells dealt to this side by their seconds."""

import jax
import jax.numpy as jnp
import pytest
from described_chip import (  # noqa: F401 — fixtures
    BF16, F32, STEP_CASES, _STEP_LOWERED, _STEP_MEMORY, _as_on_the_chip,
    _compiled_step, _kernel_calls, chip, compiled_kernel, topo,
)

from dlrover_tpu.ops import (
    gated_delta, pallas_conv, pallas_norm, pallas_rows, pallas_selective_scan,
    pallas_ssd, selective_scan, ssd,
)
from dlrover_tpu.parallel import moe


def _ssd(grad):
    """A Mamba-2 layer's scan at Nemotron-3-Super's widths: one sequence
    of 8,192, 128 heads of 64 in 8 groups over a state of 128, at the
    chunk the program picks (``ssd.kernel_chunk``)."""
    def build(S):
        s, heads, channels, groups, state = 8192, 128, 64, 8, 128
        args = (
            S((1, s, heads, channels), BF16), S((1, s, heads), F32),
            S((heads,), F32), S((1, s, groups, state), BF16),
            S((1, s, groups, state), BF16),
        )
        assert ssd.kernel_chunk(s, heads, channels, groups, state, 128) == 256

        def fwd(*a):
            return ssd.ssd_scan(*a, 128, 16)

        if not grad:
            return fwd, args
        loss = lambda *a: fwd(*a).astype(F32).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4)), args

    return build


def _ssd_lightning(grad):
    """A lightning layer's recurrence at MiniCPM-SALA's widths: one
    sequence of 16,384, 32 groups of ONE head of 128 channels over a
    state of 128 (one slab a group, one turn), Δ ≡ 1, gradients for q, k
    and v alone."""
    def build(S):
        s, heads, channels, state = 16384, 32, 128, 128
        args = (
            S((1, s, heads, channels), BF16), S((1, s, heads), F32),
            S((heads,), F32), S((1, s, heads, state), BF16),
            S((1, s, heads, state), BF16),
        )
        assert ssd.kernel_chunk(s, heads, channels, heads, state, 128) == 256

        def fwd(*a):
            return ssd.ssd_scan(*a, 128, 0)

        if not grad:
            return fwd, args
        loss = lambda *a: fwd(*a).astype(F32).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 3, 4)), args

    return build


def _sscan(grad):
    """A Mamba-1 layer's selective scan at Jamba2-3B's widths: one
    sequence of 8,192, 5,120 channels of 16 states, float32 as the mixer
    hands them over, at the module's chunk."""
    def build(S):
        s, channels, states = 8192, 5120, 16
        args = (
            S((1, s, channels), F32), S((1, s, channels), F32),
            S((channels, states), F32), S((1, s, states), F32),
            S((1, s, states), F32),
        )
        assert pallas_selective_scan.tile(
            s, channels, states, selective_scan.SCAN_CHUNK
        )

        if not grad:
            return selective_scan.selective_scan, args
        loss = lambda *a: selective_scan.selective_scan(*a).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4)), args

    return build


def _gdn(grad):
    """A gated-delta-rule layer at Qwen3-Next's widths (16 key heads
    shared by 32 value heads of 128, one sequence of 16,384, float32 as
    the mixer hands them over), under the caller's scope: the kernels
    take their names behind it."""
    def build(S):
        s = 16384
        args = (
            S((1, s, 16, 128), F32), S((1, s, 16, 128), F32),
            S((1, s, 32, 128), F32), S((1, s, 32), F32), S((1, s, 32), F32),
        )
        assert gated_delta.in_kernels(128, 128)

        def rule(*a):
            with jax.named_scope("gdn.rule"):
                return gated_delta.gated_delta_rule(*a)

        if not grad:
            return rule, args
        loss = lambda *a: rule(*a).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4)), args

    return build


def _conv(channels, dtype, grad):
    """A mixer's causal conv of 4 taps over one sequence of 8,192 as the
    two cells run it: Nemotron-3-Super's 10,240 channels in bf16 with
    bf16 taps, Jamba2-3B's 5,120 in float32 with bf16 taps; the ``silu``
    behind it keeps the forward kernel in the gradient's program, as a
    mixer does."""
    def build(S):
        args = (
            S((1, 8192, channels), dtype), S((4, channels), BF16),
            S((channels,), BF16),
        )
        assert pallas_conv.tile(8192, channels, 4) == 1024

        if not grad:
            return ssd.causal_conv, args
        loss = lambda *a: jax.nn.silu(  # noqa: E731
            ssd.causal_conv(*a).astype(F32)
        ).sum()
        return jax.grad(loss, argnums=(0, 1, 2)), args

    return build


def _conv_of_projection(grad):
    """The gated-delta-rule mixer's conv (Qwen3-Next): 4 taps over the
    first 8,192 columns [q | k | v] of a 12,288-wide in-projection, read
    where they lie (``ssd.Columns``), one sequence of 16,384, bf16."""
    def build(S):
        args = (
            S((1, 16384, 12288), BF16), S((4, 8192), BF16), S((8192,), F32),
        )
        assert pallas_conv.tile(16384, 8192, 4, 0) is not None

        def fwd(proj, w, b):
            return ssd.causal_conv(ssd.Columns(proj, 0), w, b)

        if not grad:
            return fwd, args
        loss = lambda *a: jax.nn.silu(fwd(*a).astype(F32)).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1)), args

    return build


def _gated_conv(grad):
    """LFM2's gated short conv: 3 taps over B ⊙ x gated by C, the three
    read where they lie in the 6,144-wide in-projection [B | C | x],
    eight sequences of 4,096, bf16."""
    def build(S):
        args = (S((8, 4096, 6144), BF16), S((3, 2048), BF16))
        assert ssd.gated_conv_in_kernel(4096, 3, 2048, BF16) == 1024

        if not grad:
            return ssd.gated_conv, args
        loss = lambda *a: jax.nn.silu(  # noqa: E731
            ssd.gated_conv(*a).astype(F32)
        ).sum()
        return jax.grad(loss, argnums=(0, 1)), args

    return build


def _held_rows(t, k, d, tiles, bound=None, back=False):
    """A routed block's sum over the rows its held experts received
    (``ops/pallas_rows.py``) as the cells run it: the combine (weighted)
    of Keye-VL-2.0's and GLM-4.7-Flash's 65,536 rows and Trinity-Mini's
    131,072 at 2,048 columns, and the dispatch's derivative
    (unweighted, ``back``) of Nemotron-3-Super's 180,224 pairs cut to
    65,536 rows at its latent's 1,024."""
    def build(S):
        n = bound or t * k
        assert pallas_rows.tile(t, n, d, BF16) == tiles
        mask, rows = S((t * k,), jnp.bool_), S((), jnp.int32)
        order, inv = S((n,), jnp.int32), S((t * k,), jnp.int32)
        if back:
            def fn(xt, cot, order, inv, mask, rows):
                held = moe.Held(mask, rows, True)
                return jax.grad(
                    lambda x: (
                        moe._dispatch(k, x, order // k, inv, held)
                        .astype(F32) * cot
                    ).sum()
                )(xt)

            return fn, (S((t, d), BF16), S((n, d), F32), order, inv, mask,
                        rows)

        def fn(out_rows, weights, order, inv, mask, rows):
            return moe._combine_weighted(
                out_rows, weights, order, inv, BF16,
                moe.Held(mask, rows, True),
            )

        return fn, (S((n, d), BF16), S((t, k), F32), order, inv, mask, rows)

    return build


def _interior(n, d, gated, tiles):
    """The experts' activation between the grouped matmuls and its
    derivative over the held prefix (``moe._interior_held``:
    ``pallas_rows.experts_act`` / ``experts_act_bwd``) at a held cell's
    rows and expert width; ``gated`` False: Nemotron-3-Super's
    relu(.)² experts."""
    def build(S):
        assert pallas_rows.act_tile(n, d, BF16, gated) == tiles

        def fn(up, gate, cot, rows):
            h, pull = jax.vjp(
                lambda u, g: moe._interior_held(u, g, rows, tiles), up, gate
            )
            return h, pull(cot)

        a = S((n, d), BF16)
        return fn, (a, a if gated else None, a, S((), jnp.int32))

    return build


def _l2(heads):
    """Both kernels of ``pallas_norm.l2_heads`` at a delta-rule mixer's
    q (or k) in its cell: one sequence of 16,384, ``heads`` heads of 128
    a run of columns each, float32."""
    def build(S):
        x = S((1, 16384, heads * 128), F32)

        def fn(x, dy):
            y, pull = jax.vjp(
                lambda x: pallas_norm.l2_heads(x, 128, 128 ** -0.5), x
            )
            return y, pull(dy)[0]

        return fn, (x, x)

    return build


CASES = {
    # a Mamba-2 layer's scan (Nemotron-3-Super): ``ops/pallas_ssd.py``
    "ssd-fwd-128x64-8x128": (_ssd(grad=False), 1),
    "ssd-bwd-128x64-8x128": (_ssd(grad=True), 2),
    # a lightning layer's recurrence (MiniCPM-SALA): one head of 128 a
    # group
    "ssd-fwd-32x128-32x128": (_ssd_lightning(grad=False), 1),
    "ssd-bwd-32x128-32x128": (_ssd_lightning(grad=True), 2),
    # a Mamba-1 layer's selective scan (Jamba2-3B): the gradient alone
    # still needs the forward kernel, for the chunks' starting states
    "sscan-fwd-5120x16": (_sscan(grad=False), 1),
    "sscan-bwd-5120x16": (_sscan(grad=True), 2),
    # a gated-delta-rule layer's walk over the chunks (Qwen3-Next)
    # behind the triangular inverse of whole chunks: the gradient alone
    # needs the inverse, the state pass and the walk back
    "gdn-fwd-16x2x128": (_gdn(grad=False), 2),
    "gdn-bwd-16x2x128": (_gdn(grad=True), 3),
    # both mixers' causal conv (``ops/pallas_conv.py``)
    "conv-fwd-10240-bf16": (_conv(10240, BF16, grad=False), 1),
    "conv-bwd-10240-bf16": (_conv(10240, BF16, grad=True), 2),
    "conv-fwd-8192-of-12288-bf16": (_conv_of_projection(grad=False), 1),
    "conv-bwd-8192-of-12288-bf16": (_conv_of_projection(grad=True), 2),
    "conv-fwd-5120-f32": (_conv(5120, F32, grad=False), 1),
    "conv-bwd-5120-f32": (_conv(5120, F32, grad=True), 2),
    # the gated short conv (LFM2's ``C`` part)
    "gated-conv-fwd-3x2048-bf16": (_gated_conv(grad=False), 1),
    "gated-conv-bwd-3x2048-bf16": (_gated_conv(grad=True), 2),
    # the routed blocks' sums over the held rows (``ops/pallas_rows.py``)
    "rows-sum-8192x8-2048": (_held_rows(8192, 8, 2048, (2048, 1024)), 1),
    "rows-sum-16384x8-2048": (_held_rows(16384, 8, 2048, (2048, 512)), 1),
    "rows-sum-back-8192x22-1024": (
        _held_rows(8192, 22, 1024, (2048, 1024), bound=65536, back=True), 1),
    # the experts' interior over the held prefix, forward and back, at
    # the seven held cells' rows x expert width: Trinity-Mini and
    # Kimi-Linear, Mellum2, Keye-VL-2.0, GLM-4.7-Flash, Nemotron-3-Super
    # (its pairs cut to 65,536 rows, no gate), Qwen3-Next
    "experts-act-131072x1024": (
        _interior(131072, 1024, True, (2048, 1024, 16)), 2),
    "experts-act-262144x896": (
        _interior(262144, 896, True, (2048, 896, 16)), 2),
    "experts-act-65536x768": (_interior(65536, 768, True, (2048, 768, 16)), 2),
    "experts-act-65536x1536": (
        _interior(65536, 1536, True, (2048, 1536, 16)), 2),
    "experts-act-relu2-65536x2688": (
        _interior(65536, 2688, False, (2048, 2688, 16)), 2),
    "experts-act-163840x512": (
        _interior(163840, 512, True, (2048, 512, 32)), 2),
    # q's and k's L2 norm a head on the flat layout, forward and back
    # (Kimi-Linear's 32 heads, Qwen3-Next's 16 key heads)
    "l2-heads-32x128": (_l2(32), 2),
    "l2-heads-16x128": (_l2(16), 2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    compiled, text = compiled_kernel(chip, *CASES[case])
    if case.startswith("ssd-"):
        # the gradient alone needs no y: the forward kernel is dead code
        # there, and the backward rule's two kernels are what is left
        names = ("ssd_states", "ssd_bwd") if "bwd" in case else ("ssd_fwd",)
        assert all(f"%{name}" in text for name in names)
    if case.startswith("sscan-"):
        names = ("sscan_fwd", "sscan_bwd") if "bwd" in case else ("sscan_fwd",)
        assert all(f"%{name}" in text for name in names)
    if case.startswith("gdn-"):
        import math
        import re

        names = ("gdn_states", "gdn_bwd") if "bwd" in case else ("gdn_fwd",)
        assert all(f"%{name}" in text for name in names)
        assert _kernel_calls(text, "tri_inverse") == 1
        # the 256 chunks' dependence is the kernels' grid: no loop of
        # the compiler's around a chunk step, and what XLA makes of
        # whole chunks beside them (K K^T and T; going back the states,
        # 537 MB, T's cotangent and A's) fits beside the cell's 9.4 GB
        # of state: 0.81 and 2.03 GB by the compiler's count (0.95 and
        # 2.3 before PR 71)
        assert "while(" not in text and " while " not in text
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < (2.23e9 if "bwd" in case else 0.89e9)
        # T and the cotangents of T and A cross HBM a key head's two
        # value heads side by side, f32[.., 64, 128], and A going
        # forward not at all: no array of the 8,192 chunk-heads ends in
        # [64, 64] or [32, 32], which a tile pads to its 128 lanes
        # (K K^T and its cotangent, a matrix a KEY head, are what is
        # left)
        padded = re.findall(r"f32\[([\d,]*),(?:64,64|32,32)\]", text)
        assert padded
        assert all(
            math.prod(map(int, dims.split(","))) <= 256 * 16
            for dims in padded
        ), sorted(set(padded))
    if case.startswith("l2-heads-"):
        import re

        for name in ("l2_heads_fwd", "l2_heads_bwd"):
            assert re.search(rf"%\w*{name}[_.\d]* = ", text), name
        # x as it lies: a head a run of columns, nothing made [S, H, D]
        assert not re.search(r"f32\[[\d,]*16384,\d+,128\]", text)
    if case.startswith("rows-sum-"):
        assert "%rows_sum" in text
    if case.startswith("experts-act-"):
        for name in ("experts_act", "experts_act_bwd"):
            assert _kernel_calls(text, name) == 1
    if case.startswith("gated-conv-"):
        names = ("gated_conv_fwd", "gated_conv_bwd")
        assert all(f"%{n}" in text for n in names[:2 if "bwd" in case else 1])
        # the in-projection as it lies: no window of it copied out, no
        # float32 copy, and going back ONE array of the three cotangents
        entry = text.split("ENTRY")[1]
        assert "f32[8,4096,2048]" not in entry
        assert "f32[8,4096,6144]" not in entry
        assert " slice(" not in entry and " concatenate(" not in entry
        assert " pad(" not in entry
    if case.startswith("conv-"):
        names = ("conv_fwd", "conv_bwd") if "bwd" in case else ("conv_fwd",)
        assert all(f"%{name}" in text for name in names)
        # x as it lies: no padded copy and no float32 copy of it
        assert "8195" not in text and "16387" not in text
        if "of-12288" in case:
            assert "f32[1,16384,8192]" not in text.split("ENTRY")[1]
        elif "bf16" in case:
            assert "f32[1,8192,10240]" not in text.split("ENTRY")[1]


def test_gated_delta_rule_compiles_a_stretch_at_a_time(topo, chip):
    """The gated delta rule's XLA body at Qwen3-Next's widths (16 key
    heads shared by 32 value heads of 128, one sequence of 16,384,
    chunks of 64, float32 operands as the mixer hands them over),
    forward and backward, for a described v5e: matmuls and no kernel
    (the op's fallback since PR 64), and with each stretch of 2,048 tokens under its own checkpoint
    the compiler counts 1.26 GB of temporaries (on bf16 operands 0.94
    GB where the sequence whole took 3.6, and two periods' step then
    needed 15.98 GiB of 15.75). No array has blocks of 16 rows as its
    trailing dimensions, which a tile pads to 128 lanes: the inverse
    works with the batch on the lanes."""
    import re

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    s = 16384
    args = (
        struct((1, s, 16, 128), F32), struct((1, s, 16, 128), F32),
        struct((1, s, 32, 128), F32), struct((1, s, 32), F32),
        struct((1, s, 32), F32),
    )
    # the fallback's own entry: a mesh of several devices rules the
    # kernels out
    several = jax.sharding.Mesh(topo.devices[:2], ("dp",))
    assert not gated_delta.in_kernels(128, 128, mesh=several)
    loss = lambda *a: gated_delta.gated_delta_rule(  # noqa: E731
        *a, chunk=64, mesh=several
    ).astype(F32).sum()
    compiled = jax.jit(jax.grad(loss, argnums=range(5))).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6e9
    assert not re.search(r"f32\[[\d,]*,16,\d+,16\]", text)


def _vector_rule_args(chip):
    """Kimi-Linear's rule: 32 heads of 128 key and 128 value channels,
    one sequence of 16,384, float32 operands as the mixer hands them."""
    def struct(shape):
        return jax.ShapeDtypeStruct(shape, F32, sharding=chip)

    wide = struct((1, 16384, 32, 128))
    return wide, wide, wide, wide, struct((1, 16384, 32))


def test_vector_delta_rule_compiles_a_stretch_at_a_time(topo, chip):
    """The delta rule with a decay a key channel at Kimi-Linear's widths
    (32 heads of 128 key and 128 value channels, one sequence of 16,384,
    chunks of 64 in sub-blocks of 16, float32 operands as the mixer
    hands them over), its XLA body (the op's fallback since PR 66),
    forward and backward, for a described v5e: matmuls and no kernel,
    and with each stretch of 1,024 tokens under its own checkpoint the
    compiler counts 0.53 GB of temporaries (1.03 at stretches of 2,048).
    No array holds a whole chunk's [64, 64, 128] differences: the
    largest with two token axes and the channels is a sub-block's
    [16, 16, 128]."""
    import re

    # the fallback's own entry: a mesh of several devices rules the
    # kernels out
    several = jax.sharding.Mesh(topo.devices[:2], ("dp",))
    assert not gated_delta.in_kernels(
        128, 128, mesh=several, per_channel=True
    )
    loss = lambda *a: gated_delta.gated_delta_rule(  # noqa: E731
        *a, chunk=64, mesh=several
    ).astype(F32).sum()
    compiled = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        *_vector_rule_args(chip)
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7e9
    assert not re.search(r"f32\[[\d,]*64,64,128\]", text)
    assert re.search(r"f32\[[\d,]*16,16,128\]", text)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_vector_delta_rule_kernels_compile_for_v5e(chip, grad):
    """The same rule on its kernels (``ops/pallas_kda.py``: what a v5e
    runs at these widths on one device): the pairs, the triangular
    inverse and the walk going forward; going back the pairs and the
    inverse again, the state pass, the walk back and the pairs'
    pull-back — no loop of the compiler's around a chunk step and no
    stretch. What XLA holds around them, by the compiler's
    count: 1.62 GB of temporaries forward (A, M and T, float32
    [256, 32, 64, 64] each, the 64 padded to 128 lanes: 268 MB, and what
    the substitution holds between A and T) and 2.97 GB going back
    (those, the states every chunk starts from, 537 MB, the walk's parts
    of dq, dk and dγ, 268 MB each, and dT, dM and dA), which the cell's
    step fits beside its train state
    (``test_kimi_cell_fits_the_chip``): 1.61 and 2.96 GB since T is the
    kernel ``tri_inverse``'s (PR 71). Neither a sub-block's
    [16, 16, 128] nor a chunk's [64, 64, 128] differences exist as an
    array."""
    import re

    assert gated_delta.in_kernels(128, 128, per_channel=True)
    fwd = lambda *a: gated_delta.gated_delta_rule(*a)  # noqa: E731
    fn = jax.grad(
        lambda *a: fwd(*a).astype(F32).sum(), argnums=range(5)
    ) if grad else fwd
    compiled = jax.jit(fn).lower(*_vector_rule_args(chip)).compile()
    text = compiled.as_text()
    names = (
        ("kda_pairs", "kda_states", "kda_bwd", "kda_pairs_bwd") if grad
        else ("kda_pairs", "kda_fwd")
    ) + ("tri_inverse",)
    assert text.count("tpu_custom_call") == len(names)
    for name in names:
        assert re.search(rf"%\w*{name}[_.\d]* = ", text), name
    assert "while(" not in text and " while " not in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (3.26e9 if grad else 1.77e9)
    assert not re.search(r"f32\[[\d,]*(16,16|64,64),128\]", text)
    # the inverse is a kernel: nothing of the substitution's — blocks of
    # 16 or 32 rows, the batch on the lanes — is XLA's
    assert not re.search(r"f32\[[\d,]*(16,16|32,32),8192\]", text)


def _equations(jaxpr):
    """Equations of a jaxpr with those of the jaxprs its equations hold."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _equations(sub)
    return n


# Equations of a kernel's body traced at Nemotron-3's widths (TURN 4;
# the state pass a group whole), with a tenth of room. PR 50's form
# reads 332 / 288 / 717, each traced once a process, and costs the
# chip's host +0.91 s of the step's trace and lowering over the parent's
# 3.90 s (my chip runs, PR 50: that host traces a thousand kernel
# equations in about a third of a second); PR 49's bodies were 651 / 393
# / 1,696, the forward's traced twice, behind a nested ``jit`` that cost
# 1.6 s by itself: +4.3 s in the driver's runs, and the PR refused
SCAN_BODY_BUDGET = {
    "ssd-bwd-128x64-8x128": {"ssd_fwd": 365, "ssd_states": 317, "ssd_bwd": 789},
    # the same three at MiniCPM-SALA's lightning widths (PR 57: one head
    # of 128 a group is one slab and one turn, 61 / 37 / 141; traced
    # once a process beside no other shape of theirs in that cell)
    "ssd-bwd-32x128-32x128": {"ssd_fwd": 67, "ssd_states": 41, "ssd_bwd": 155},
    # the selective scan's two at Jamba2-3B's widths (PR 54: 242 / 797,
    # each traced once a process; the per-state text, 16 states, is the
    # body — the token loops are rolled)
    "sscan-bwd-5120x16": {"sscan_fwd": 266, "sscan_bwd": 877},
    # the causal conv's two at both cells' widths (PR 55: 48 / 79 in
    # bf16, 46 / 76 in float32, each traced once a process: a tap is a
    # rotate, a select and a concatenate)
    "conv-bwd-10240-bf16": {"conv_fwd": 53, "conv_bwd": 87},
    "conv-bwd-5120-f32": {"conv_fwd": 51, "conv_bwd": 84},
    # the held rows' sum (PR 59: 115 weighted, the combine's, and 99
    # unweighted, the dispatch's derivative's; 8 rows of the loop
    # unrolled; each traced once a process)
    "rows-sum-8192x8-2048": {"rows_sum": 127},
    "rows-sum-back-8192x22-1024": {"rows_sum": 109},
    # the experts' interior over the held prefix and its derivative (PR
    # 72: 36 / 45 with a gate, 33 / 36 without; a turn of rows is one
    # rolled loop whatever the tile; each traced once a process)
    "experts-act-131072x1024": {"experts_act": 40, "experts_act_bwd": 50},
    "experts-act-relu2-65536x2688": {
        "experts_act": 37, "experts_act_bwd": 40,
    },
    # the L2 norm a head, forward and back (PR 69: 9 and 16 equations a
    # head of the block, 76 / 132 at the 8 heads a block holds whatever
    # the width — 292 / 516 with all of Kimi-Linear's 32 in it, which
    # cost its cell 2-5 s of warm ``setup_s`` for no speed; each traced
    # twice a process, once for q's scale and once for k's)
    "l2-heads-32x128": {"l2_heads_fwd": 83, "l2_heads_bwd": 145},
    "l2-heads-16x128": {"l2_heads_fwd": 83, "l2_heads_bwd": 145},
    # the gated delta rule's walk (212 / 158 / 492) and, since PR 71, the
    # triangular inverse before it (926 where it makes A of two value
    # heads itself, 893 of a given A: the fifteen steps of a diagonal
    # block and the 16 or 32 terms of a product's eight rows are
    # unrolled — rolled they were 412 equations and 2.3 times the
    # kernel's time —, the blocks, the pairs merged, the rows of eight
    # and the transposes are rolled loops; traced once a process)
    "gdn-bwd-16x2x128": {
        "tri_inverse": 1018, "gdn_fwd": 233, "gdn_states": 174,
        "gdn_bwd": 541,
    },
}


@pytest.mark.parametrize("case", sorted(SCAN_BODY_BUDGET))
def test_scan_kernels_stay_inside_their_build_budget(case):
    """A kernel's body is traced and lowered to Mosaic in every process
    before anything runs, warm or cold, and the seconds go by the
    equations (``ops/pallas_ssd.py``'s docstring): a body that grows
    past its budget fails here, not in the benchmark's ``setup_s``."""
    build, _ = CASES[case]
    fn, args = build(jax.ShapeDtypeStruct)
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = _equations(eqn.params["jaxpr"])
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    budget = SCAN_BODY_BUDGET[case]
    assert set(found) == set(budget)
    for name, most in budget.items():
        assert 0.5 * most < found[name] <= most, (name, found[name])


def test_kimi_cell_fits_the_chip(topo):
    """The benchmark's Kimi-Linear configuration as it is run (published
    layers 1-5 — a KDA mixer and the dense MLP, then KDA, KDA, latent
    attention, KDA with sixteen held experts each — one sequence of
    16,384 tokens) compiles for a described v5e under the chip's 15.75
    GiB (16.91 GB; 13.98 GiB by this count since PR 69, 14.76 since PR
    66, 14.08 before):
    the vector rule as its kernels in every KDA layer (``ops/
    pallas_kda.py``: under ``remat: full`` the pairs and the forward
    walk twice a layer — the layer's and the remade one, whose pairs the
    backward rule shares —, the state pass, the walk back and the pairs'
    pull-back once; no stretch and none of the scalar rule's kernels),
    the latent layer's three flash kernels at 192 channels with the
    backward's tile of 1024 x 512 (1024 x 1024 asks 17.5 MB of VMEM's
    16 at a head and a half of lanes), its output kept, the convs as
    kernels. No array holds a whole chunk's [64, 64, 128]
    differences."""
    import json
    import pathlib
    import re

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads(
        (path / "kimi-linear-48b-a3b-ep16-1chip.json").read_text()
    )
    STEP_CASES["kimi-cell"] = dict(
        model=config["program"]["model"],
        overrides=config["program"]["overrides"],
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(1, 16384),
    )
    try:
        _, text, counters = _compiled_step(topo, "kimi-cell")
    finally:
        del STEP_CASES["kimi-cell"]
    stats = _STEP_MEMORY["kimi-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    assert 12e9 < need < 15.75 * 2 ** 30, need
    assert stats.argument_size_in_bytes == pytest.approx(
        6 * 828_925_824, rel=1e-3  # bf16 parameters and two moments
    )
    assert counters["kda.layers"] == 4
    assert counters["kda.kernel_layers"] == 4
    assert counters["kda.norm_kernel_layers"] == 4
    # q's and k's L2 norms (PR 69): a kernel each in the layer's forward
    # and again in the one ``full`` remakes (2 x 4 x 2), their
    # derivatives once (2 x 4)
    for kernel, calls in (
        ("kda_pairs", 8), ("tri_inverse", 8), ("kda_fwd", 8),
        ("kda_states", 4), ("kda_bwd", 4), ("kda_pairs_bwd", 4),
        ("l2_heads_fwd", 16), ("l2_heads_bwd", 8),
    ):
        assert _kernel_calls(text, kernel) == calls, kernel
    # between the conv and the rule's kernels [B, S, H, D] is a view:
    # nothing under the scope makes, copies or relays an array of a
    # whole sequence's heads in that form or out of it (the parent held
    # 24 broadcasts of the norms to f32[16384,32,128] and 24 relayouts
    # of them to f32[1,16384,4096], 268 MB each)
    under_rule = [ln for ln in text.splitlines() if "kda.rule" in ln]
    assert under_rule
    assert not [
        ln for ln in under_rule if re.search(
            r"= f32\[(?:1,)?16384,(?:4096|32,128)\]\S* "
            r"(?:copy|reshape|transpose)\(", ln
        ) or re.search(r"= f32\[(?:1,)?16384,32,128\]\S* broadcast\(", ln)
    ]
    assert counters["attn.output_kept"] == 1
    assert counters["ssm.conv_in_kernel"] == 1
    assert _kernel_calls(text, "flash_fwd") == 1
    assert _kernel_calls(text, "flash_bwd_dq") == 1
    assert _kernel_calls(text, "flash_bwd_dkv") == 1
    assert "bf16[32,16384,192]" in text
    assert "gdn_fwd" not in text and "gdn_bwd" not in text
    assert "gdn_states" not in text
    assert not re.search(r"f32\[[\d,]*64,64,128\]", text)


def _count_traced_bodies(monkeypatch, module, kernels):
    """{name: times traced from here on} for ``module``'s kernel bodies
    ``kernels`` = {name: the body's attribute}."""
    traced = dict.fromkeys(kernels, 0)

    def counting(name, kernel):
        def body(*refs, **statics):
            traced[name] += 1
            return kernel(*refs, **statics)

        return body

    for name, attr in kernels.items():
        monkeypatch.setattr(
            module, attr, counting(name, getattr(module, attr))
        )
    return traced


# the causal conv's bodies (``ops/pallas_conv.py``), by kernel name
CONV_BODIES = {"conv_fwd": "_fwd_kernel", "conv_bwd": "_bwd_kernel"}


def _conv_calls_sit_under(text, op_names, scope, forward, backward):
    """A compiled step's ``conv_fwd`` / ``conv_bwd`` calls, counted, each
    under ``scope`` (what the benchmark's mixer-share readers sum)."""
    import re

    assert _kernel_calls(text, "conv_fwd") == forward
    assert _kernel_calls(text, "conv_bwd") == backward
    calls = [
        op_name for name, op_name in op_names.items()
        if name.startswith("conv_")
    ]
    assert len(calls) == forward + backward and all(
        scope in re.split(r"[/()]", op_name) for op_name in calls
    )


def test_nemotron_cell_fits_the_chip_with_its_rows_cut(topo, monkeypatch):
    """The benchmark's Nemotron-3-Super configuration as it is run (one
    period MEMEMEMEM*E + the module, 8 of 512 experts held, 1 x 8192
    tokens): the step the chip's compiler lays out needs under the 16.9
    GB the runtime gives and over 12 GB (14.54 GB by this count, PRs 41
    and 42 alike, where the chip itself reads 14.83 — the one cell whose
    count reads low, D18; PR 42 keeps the two attention layers' kernel
    output, 2 x 68 MB, and neither number moves; bf16 parameters and
    two moments are 8.27 GB of arguments). Every
    part shows under its scope, the attention goes through the unpacked
    flash kernels (GQA 32 / 2 at head size 128), and the held experts'
    rows are cut to 8,192 x 8: no array of 180,224 rows is as wide as
    an expert.

    Since PR 50 the five Mamba-2 layers' scan runs its kernels
    (``ops/pallas_ssd.py``). What they cost BEFORE the step runs is
    held here without a clock (PR 49's form ran as fast and was refused
    for 4.5 s of set-up): while the step is traced the forward kernel's
    body is traced twice (the forward — the primal's and the forward
    rule's are one trace — and the state pass) and the backward's once,
    not once a layer and not once a rule; and in the step LOWERED,
    before XLA, the twenty ``ssd_*`` calls hold three bodies."""
    import json
    import pathlib
    import re

    from dlrover_tpu.observability import runtime_timer

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads(
        (path / "nemotron-3-super-ep64-1chip.json").read_text()
    )
    STEP_CASES["nemotron-cell"] = dict(
        model=config["program"]["model"],
        overrides=config["program"]["overrides"],
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(1, 8192), keep_lowered=True,
    )
    # the kernels' traces are kept by shape for the process: forget the
    # ``ssd-*`` cases' above, and count the bodies traced from here on
    jax.clear_caches()
    traced = _count_traced_bodies(
        monkeypatch, pallas_ssd,
        {"ssd_fwd": "_fwd_kernel", "ssd_bwd": "_bwd_kernel"},
    )
    traced_conv = _count_traced_bodies(monkeypatch, pallas_conv, CONV_BODIES)
    try:
        _, text, counters = _compiled_step(topo, "nemotron-cell")
    finally:
        del STEP_CASES["nemotron-cell"]
    assert traced == {"ssd_fwd": 2, "ssd_bwd": 1}, traced
    assert traced_conv == {"conv_fwd": 1, "conv_bwd": 1}, traced_conv
    bodies = {}
    for line in _STEP_LOWERED.pop("nemotron-cell").splitlines():
        name = re.search(r'kernel_name = "((?:ssd|conv)_\w+)"', line)
        if name:
            bodies.setdefault(name.group(1), []).append(
                re.search(r'body\W+(\w+)', line).group(1)
            )
    assert {k: (len(v), len(set(v))) for k, v in bodies.items()} == {
        "ssd_fwd": (10, 1), "ssd_states": (5, 1), "ssd_bwd": (5, 1),
        "conv_fwd": (10, 1), "conv_bwd": (5, 1),
    }
    stats = _STEP_MEMORY["nemotron-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    assert 12e9 < need < 16.9e9, need
    assert stats.argument_size_in_bytes == pytest.approx(
        6 * 1_378_721_664, rel=1e-3  # bf16 parameters and two moments
    )
    op_names = runtime_timer.op_names_from_hlo(text)
    parts = {
        part for name in op_names.values()
        for part in re.split(r"[/()]", name)
    }
    scopes = {"ssm", "ssm.conv", "ssm.scan", "attn", "mlp", "moe.route",
              "moe.sort", "moe.latent", "moe.experts", "moe.combine",
              "moe.shared", "mtp", "head_loss", "optimizer"}
    assert scopes <= parts, scopes - parts
    kernels = {
        line.split("=")[0].strip().removeprefix("ROOT ").lstrip("%")
        .split(".")[0]
        for line in text.splitlines() if "tpu_custom_call" in line
    }
    assert kernels == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "norm_fwd",
        "norm_bwd", "ragged-dot-none", "ragged-dot-metadata",
        "ssd_fwd", "ssd_states", "ssd_bwd", "conv_fwd", "conv_bwd",
        "rows_sum", "experts_act", "experts_act_bwd",
    }
    # the routed blocks' relu(.)² by the held prefix (PR 72): a block's
    # interior going forward, remade, and its derivative
    assert counters["moe.experts_by_prefix"] == 1
    routed = _kernel_calls(text, "experts_act_bwd")
    assert routed and _kernel_calls(text, "experts_act") == 2 * routed
    # the five layers' conv through its kernels (PR 55), every call
    # under ``ssm.conv``: forward, remade, backward; x read as it lies,
    # no padded float32 copy of it
    assert counters["ssm.conv_in_kernel"] == 1
    _conv_calls_sit_under(text, op_names, "ssm.conv", forward=10, backward=5)
    assert "f32[1,8195,10240]" not in text
    # the five Mamba-2 layers' scan through its kernels, every call
    # under the scope the benchmark's ``ssm.*`` readers sum: the forward
    # once in the forward and once remade, and in the backward the pass
    # that makes the chunks' starting states and the backward kernel —
    # where the XLA body ran three forwards a backward
    assert counters["ssm.scan_in_kernel"] == 1
    assert _kernel_calls(text, "ssd_fwd") == 10
    assert _kernel_calls(text, "ssd_states") == 5
    assert _kernel_calls(text, "ssd_bwd") == 5
    scan_calls = [
        (name, op_name) for name, op_name in op_names.items()
        if name.startswith("ssd_")
    ]
    assert len(scan_calls) == 20 and all(
        "ssm.scan" in re.split(r"[/()]", op_name)
        for _, op_name in scan_calls
    )
    assert sorted(
        runtime_timer.phase_of(f"%{name} = x", op_name)
        for name, op_name in scan_calls
    ) == ["backward"] * 10 + ["forward"] * 5 + ["recompute"] * 5
    # the starting states live only around the backward kernel, not at
    # the step's peak: the count of memory is the parent's (14.54 GB)
    assert need < 14.65e9, need
    # the trunk's attention layer and the module's keep their kernel's
    # output (PR 42): one forward call each where there were two
    assert counters["attn.output_kept"] == 2
    assert _kernel_calls(text, "flash_fwd") == 2
    assert _kernel_calls(text, "flash_bwd_dq") == 2
    assert "[65536,2688]" in text and "[65536,1024]" in text
    assert "[180224,2688]" not in text
    # no score or decay block in memory, of all 128 heads at once or of
    # a block of 16 (the XLA body's), at either chunk
    assert not re.search(r"\[1,(?:64|32),\d+,16,(?:128,128|256,256)\]", text)


def test_jamba_cell_compiles_with_its_runs_scanned(topo, monkeypatch):
    """The benchmark's Jamba2-3B configuration as it is run (one period
    of 14 mixer + MLP layers, 1 x 8192 tokens) compiles for a described
    v5e: the two runs of ``m-`` as scans over their own stacks with the
    unit the remat unit, the attention layer's two parts unrolled. The
    count of memory made here reads high (D18: the chip reads 15.84 GB,
    and the compiler's own check, which passes, is what says the step
    fits), so it is held to its own reading, under the XLA body's 19.04.
    The kernels are the unpacked flash kernels at 20 / 1 heads of 128,
    the fused norms and, since PR 54, the selective scan's two
    (``ops/pallas_selective_scan.py``), nothing else; no array holds a
    state a token (``[B, S, 5120, 16]`` in either order) before XLA or
    after, nor a chunk of states (the backward remakes one in VMEM);
    u, Δ, y and their cotangents reach the kernels as bitcasts of the
    ``[1, 8192, 5120]`` arrays, not as copies; and while the step is
    traced each kernel's body is traced once."""
    import json
    import pathlib
    import re

    from dlrover_tpu.observability import runtime_timer

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads((path / "jamba2-3b-l14.json").read_text())
    STEP_CASES["jamba-cell"] = dict(
        model=config["program"]["model"],
        overrides=config["program"]["overrides"],
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(1, 8192), keep_lowered=True,
    )
    # the kernels' traces are kept by shape for the process: forget the
    # ``sscan-*`` cases' above, and count the bodies traced from here on
    jax.clear_caches()
    traced = _count_traced_bodies(
        monkeypatch, pallas_selective_scan,
        {"sscan_fwd": "_fwd_kernel", "sscan_bwd": "_bwd_kernel"},
    )
    traced_conv = _count_traced_bodies(monkeypatch, pallas_conv, CONV_BODIES)
    try:
        _, text, counters = _compiled_step(topo, "jamba-cell")
    finally:
        del STEP_CASES["jamba-cell"]
    assert traced == {"sscan_fwd": 1, "sscan_bwd": 1}, traced
    assert traced_conv == {"conv_fwd": 1, "conv_bwd": 1}, traced_conv
    lowered = _STEP_LOWERED.pop("jamba-cell")
    assert counters["ssm1.layers"] == 13
    assert counters["ssm1.scan_chunk"] == 128
    assert counters["ssm1.scan_in_kernel"] == 1
    assert counters["pattern.scanned_parts"] == 26
    assert counters["attn.output_kept"] == 1  # a span of 4,096.5 keys
    stats = _STEP_MEMORY["jamba-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    # 19.27 GB (18.85 before PR 55, whose conv kernels leave LESS alive
    # at the peak, 14.38 GB for 14.57, in a temporary heap the compiler
    # packs 0.2 GB looser: PERF.md section 7); 19.04 at PR 53, with the
    # scan's XLA body and its chunk of states
    assert 18.0e9 < need < 19.4e9, need
    assert stats.argument_size_in_bytes == pytest.approx(
        6 * 1_598_556_096, rel=1e-3  # bf16 parameters and two moments
    )
    kernels = {
        line.split("=")[0].strip().removeprefix("ROOT ").lstrip("%")
        .split(".")[0]
        for line in text.splitlines() if "tpu_custom_call" in line
    }
    assert kernels == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "norm_fwd", "norm_bwd",
        "sscan_fwd", "sscan_bwd", "conv_fwd", "conv_bwd",
    }
    flash = [
        ln for ln in text.splitlines()
        if "tpu_custom_call" in ln and "%flash_" in ln
    ]
    # multi-query: 20 heads of 128 on one; the output kept, so one
    # forward call
    assert len(flash) == 3 and all(
        "bf16[20,8192,128]" in ln and "bf16[1,8192,128]" in ln for ln in flash
    )
    # the scan's kernels once a scanned run's body: the forward in the
    # forward and remade in the backward, the backward kernel beside it
    assert _kernel_calls(text, "sscan_fwd") == 4
    assert _kernel_calls(text, "sscan_bwd") == 2
    op_names = runtime_timer.op_names_from_hlo(text)
    scan_calls = [
        (name, op_name) for name, op_name in op_names.items()
        if name.startswith("sscan_")
    ]
    assert len(scan_calls) == 6 and all(
        "ssm1.scan" in re.split(r"[/()]", op_name)
        for _, op_name in scan_calls
    )
    # the conv's kernels (PR 55) once a scanned run's body as the scan's
    # are, under the mixer's ``ssm1.conv`` (and the function's own
    # ``ssm.conv``); no padded copy of the float32 u
    assert counters["ssm.conv_in_kernel"] == 1
    _conv_calls_sit_under(text, op_names, "ssm1.conv", forward=4, backward=2)
    _conv_calls_sit_under(text, op_names, "ssm.conv", forward=4, backward=2)
    assert "f32[1,8195,5120]" not in text
    parts = {
        part for name in op_names.values()
        for part in re.split(r"[/()]", name)
    }
    scopes = {"ssm1", "ssm1.conv", "ssm1.dbc", "ssm1.scan", "attn", "mlp",
              "head_loss", "optimizer"}
    assert scopes <= parts, scopes - parts
    # a state a token, in either order, with any leading axes
    whole = r"8192[x,](?:1[x,])?(?:5120[x,]16|16[x,]5120)\b"
    assert not re.search(whole, lowered) and not re.search(whole, text)
    assert not re.search(r"\b64[x,]128[x,]1[x,]16[x,]5120\b", lowered)
    # no chunk of states in memory any more; one state a chunk kept, as
    # the kernels tile it
    assert "128x1x16x5120xf32" not in lowered
    assert "64x1x16x5x8x128xf32" in lowered
    # the kernels' operands of [1, 8192, 5120] are handed over in the
    # order their tiles lie in already: no copy to or from the view
    view = r"f32\[1,1024,320,128\]"
    assert re.search(view, text)
    assert not re.search(
        rf"= {view}\S* (?:copy|transpose)\(|(?:copy|transpose)\(\S*{view}", text
    )


def test_sala_cell_compiles_inside_the_memory_its_count_allows(
    topo, monkeypatch
):
    """The benchmark's MiniCPM-SALA configuration as it is run (published
    layers 0-3, ``S-L-L-L-``, an eighth of the vocabulary, 1 x 16,384
    tokens) compiles for a described v5e — the compiler's own check,
    which passes, is what says the step fits; the count of memory made
    here reads high (D18), 17.99 GB where the chip reads 15.95 (my chip
    runs, PR 57), and is held to its own reading. Arguments are the bf16
    parameters and two moments, 6 bytes each of 1,184,654,336. The
    kernels are the ``_sel`` flash kernels (32 / 2 heads of 128, a
    selection a KV head), the scan's three at one head of 128 a group
    (each body traced once) and the fused norms, nothing else; the
    lightning layers run as one scan of three; the selection is made
    once, in the forward, under ``attn.block_select`` (none of it under
    the remade part), what is kept of it the units, int8 [1, 2, 16384,
    256] (8 MB), and not the key mask they are expanded to; the scorer
    is never whole (no float [.., 16384, 1023])."""
    import json
    import pathlib
    import re

    from dlrover_tpu.observability import runtime_timer

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads((path / "minicpm-sala-l4.json").read_text())
    STEP_CASES["sala-cell"] = dict(
        model=config["program"]["model"],
        overrides=config["program"]["overrides"],
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(1, 16384), keep_lowered=True,
    )
    jax.clear_caches()
    traced = _count_traced_bodies(
        monkeypatch, pallas_ssd,
        {"ssd_fwd": "_fwd_kernel", "ssd_bwd": "_bwd_kernel"},
    )
    try:
        _, text, counters = _compiled_step(topo, "sala-cell")
    finally:
        del STEP_CASES["sala-cell"]
    lowered = _STEP_LOWERED.pop("sala-cell")
    # the forward kernel's body serves ``ssd_fwd`` and ``ssd_states``
    assert traced == {"ssd_fwd": 2, "ssd_bwd": 1}, traced
    assert counters["attn.sparse_layers"] == 1
    assert counters["attn.select_block"] == 64
    assert counters["attn.select_groups"] == 2
    assert counters["lin.layers"] == 3
    assert counters["ssm.scan_in_kernel"] == 1
    assert counters["pattern.scanned_parts"] == 6
    assert counters["attn.output_kept"] == 1  # a span of 8,192.5 keys
    stats = _STEP_MEMORY["sala-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    assert 17.0e9 < need < 18.6e9, need
    assert stats.argument_size_in_bytes == pytest.approx(
        6 * 1_184_654_336, rel=1e-3  # bf16 parameters and two moments
    )
    kernels = {
        line.split("=")[0].strip().removeprefix("ROOT ").lstrip("%")
        .split(".")[0]
        for line in text.splitlines() if "tpu_custom_call" in line
    }
    assert kernels == {
        "flash_fwd_sel", "flash_bwd_dq_sel", "flash_bwd_dkv_sel",
        "ssd_fwd", "ssd_states", "ssd_bwd", "norm_fwd", "norm_bwd",
    }
    flash = [
        ln for ln in text.splitlines()
        if "tpu_custom_call" in ln and "%flash_" in ln
    ]
    # the output kept, so one forward call; the selection's rows are the
    # KV heads', batch-major
    assert len(flash) == 3 and all(
        "bf16[32,16384,128]" in ln and "bf16[2,16384,128]" in ln
        and "s8[2,16384,16384]" in ln for ln in flash
    )
    # the scan's kernels once in the scanned run's body: the forward in
    # the forward and remade in the backward, the other two beside it
    assert _kernel_calls(text, "ssd_fwd") == 2
    assert _kernel_calls(text, "ssd_states") == 1
    assert _kernel_calls(text, "ssd_bwd") == 1
    op_names = runtime_timer.op_names_from_hlo(text)
    scan_calls = [
        op_name for name, op_name in op_names.items()
        if name.startswith("ssd_")
    ]
    assert len(scan_calls) == 4 and all(
        {"lin", "ssm.scan"} <= set(re.split(r"[/()]", op_name))
        for op_name in scan_calls
    )
    parts = {
        part for name in op_names.values()
        for part in re.split(r"[/()]", name)
    }
    scopes = {"embed", "attn", "attn.block_select", "attn.gate", "lin",
              "ssm.scan", "mlp", "head_loss", "optimizer"}
    assert scopes <= parts, scopes - parts
    select = [
        name for name in op_names.values() if "attn.block_select" in name
    ]
    assert select and not [
        name for name in select
        if "rematted_computation" in name or "transpose" in name
    ]
    # what is kept between forward and backward: the units, not the mask
    assert "1x2x16384x256xi8" in lowered
    # the scorer a chunk of queries at a time, never whole
    assert not re.search(r"f32\[[\d,]*16384,1023\]", text)
    assert re.search(r"f32\[1,2,16,512,1023\]", text)


def test_trinity_cell_keeps_both_kinds_output(topo):
    """The benchmark's Trinity-Mini configuration as it is run (1 dense
    + 4 routed layers, ``layer_types`` SSSSF, 16 of 128 experts held,
    1 x 16,384 tokens): the step compiles for a described v5e and fits
    (at 1 + 8 it does not: 16.45 GiB of 15.75, PR 47); one step holds BOTH flash variants — the window layers' and
    the full layers' calls are different programs of the same three
    kernels — and ``remat: full`` decides kind by kind, by the keys the
    forward kernel EXECUTES (PR 61): a window layer attends to 1,920
    keys a query but its forward walks a band of three tiles of 1,024,
    2,880 keys, over ``KEEP_ATTN_SPAN`` as a full layer's 8,704 are, so
    both kinds' output and row statistics are kept and no forward
    kernel runs again in the recomputed forward (before PR 61 the
    window layers' did: the rule read the 1,920). The routed stack is
    one period of four: three window layers and one full one."""
    import json
    import pathlib

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads((path / "trinity-mini-ep8-1chip.json").read_text())
    STEP_CASES["trinity-cell"] = dict(
        model=config["program"]["model"],
        overrides=config["program"]["overrides"],
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(1, 16384),
    )
    try:
        builder, text, counters = _compiled_step(topo, "trinity-cell")
    finally:
        del STEP_CASES["trinity-cell"]
    cfg = builder.cfg
    assert cfg.executed_span(16384, "S") == 1920.0625
    assert cfg.executed_span(16384, "F") == 8192.5
    stats = _STEP_MEMORY["trinity-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    # 10.45 GB, PR 47; + 0.54 for the four window layers' kept output
    assert 9e9 < need < 12e9, need
    assert counters["attn.window_layers"] == 4
    assert counters["attn.full_layers"] == 1
    assert counters["attn.output_kept"] == 5
    # the window layers' kernels walk the band (PR 48): the forward
    # three key blocks of 1024 a query block, on a grid of three where
    # it was sixteen; the backward five of 512
    assert counters["attn.band_blocks"] == 3
    assert counters["attn.window_tile"] == 512
    # by scope: every layer's forward kernel once — its output is kept,
    # the recomputed forward holds none; the dense prefix's window layer
    # outside the scan, the period's three inside it
    lines = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]

    def calls(kernel, scope):
        return sum(
            bool(re.match(rf"\s*(?:ROOT )?%{kernel}[.\d]* = ", ln))
            and f"/{scope}/" in ln
            for ln in lines
        )

    import re

    assert calls("flash_fwd", "attn.window") == 1 + 3
    assert calls("flash_bwd_dq", "attn.window") == 1 + 3
    assert calls("flash_bwd_dkv", "attn.window") == 1 + 3
    assert calls("flash_fwd", "attn.full") == 1
    assert calls("flash_bwd_dq", "attn.full") == 1
    assert calls("flash_bwd_dkv", "attn.full") == 1
    assert "/attn.gate/" in text


def test_lfm2_cell_runs_the_gated_conv_in_kernels(topo):
    """The benchmark's LFM2 configuration as it is run (the first six
    published layers ``C-C-*eCeCeCe``, 8 of 32 experts held, 8 x 4,096
    tokens): the step compiles for a described v5e and fits the chip's
    15.75 GiB; the five conv mixers run the gated conv's kernels — a
    forward and a recomputed forward a layer (the scanned ``C-`` pair
    holds one body), one backward — under ``conv.gate`` inside ``conv``,
    beside ``conv.in_proj`` and ``conv.out_proj``, with no window of the
    in-projection copied out and no float32 copy of it; the one
    attention layer runs the flash kernels at 32 / 8 heads of 64."""
    import json
    import pathlib
    import re

    path = pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
    config = json.loads((path / "lfm2-8b-a1b-ep4-1chip.json").read_text())
    STEP_CASES["lfm2-cell"] = dict(
        model=config["program"]["model"],
        overrides=config["program"]["overrides"],
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(8, 4096),
    )
    try:
        builder, text, counters = _compiled_step(topo, "lfm2-cell")
    finally:
        del STEP_CASES["lfm2-cell"]
    assert builder.cfg.num_params() == 568_647_808
    stats = _STEP_MEMORY["lfm2-cell"]
    need = (
        stats.argument_size_in_bytes + stats.output_size_in_bytes
        - stats.alias_size_in_bytes + stats.temp_size_in_bytes
    )
    # 9.84 GB = 9.16 GiB (PR 73)
    assert 8e9 < need < 15.75 * 2 ** 30, need
    assert counters["conv.layers"] == 5
    assert counters["conv.kernel_layers"] == 5
    assert counters["pattern.scanned_parts"] == 4
    assert counters["moe.experts_by_prefix"] == 1
    lines = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]

    def calls(kernel, scope):
        return sum(
            bool(re.match(rf"\s*(?:ROOT )?%{kernel}[.\d]* = ", ln))
            and f"/{scope}" in ln
            for ln in lines
        )

    # the scanned pair's body once, the three unrolled layers each
    assert calls("gated_conv_fwd", "conv.gate") == 2 * (1 + 3)
    assert calls("gated_conv_bwd", "conv.gate") == 1 + 3
    for scope in ("conv.in_proj", "conv.gate", "conv.out_proj"):
        assert f"/conv/{scope}" in text, scope
    assert "f32[8,4096,6144]" not in text
    flash = {
        m.group(1) for ln in lines
        if (m := re.match(r"\s*(?:ROOT )?%(flash_\w+?)[.\d]* = ", ln))
    }
    assert len(flash) == 3, flash
