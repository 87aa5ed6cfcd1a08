"""Single-chip training throughput benchmark.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Metric: model FLOPs utilization (MFU) of a jitted train step on the largest
config that fits the local chip (lead attempt: llama-1.4b, whose dims all
tile the MXU exactly; gpt2-family fallbacks follow). The reference's
headline is Llama2-7B FSDP at 65.6% HFU on A100s (BASELINE.md #8);
``vs_baseline`` is our MFU / 0.656 — a hardware-neutral comparison of how
well each framework drives its accelerator.

Each candidate config runs in a subprocess with its own timeout, so a hung
compile or OOM on the big config cannot eat the whole bench budget.
"""

import json
import math
import os
import subprocess
import sys
import time

_REFERENCE_HFU = 0.656  # BASELINE.md #8

# one deadline for the whole run: attempts + aux passes must fit the
# documented `timeout 900 python bench.py` with slack for interpreter
# startup (the per-attempt budgets below must sum to <= this)
_DEADLINE_S = 870

# (config, batch, seq, remat, subprocess timeout seconds)
# llama-1.4b leads: every hot dim is a 128-multiple (d=16·128,
# head_dim=128, ff=44·128), measured ~10 MFU points over gpt2-1.5b's
# d=1600/head_dim=64 shapes on v5e — the MXU tiles cleanly.
# remat=save_qkv: fused CE (ops/fused_ce.py) freed the ~2 GiB f32
# logits working set, which buys pinning the qkv projections + flash
# residuals — backward skips ~30% of the full-remat recompute flops.
# Sequence length: b1·s8192 leads (same 8192 tokens/step as b8·s1024,
# so identical optimizer amortization and activation footprint) —
# longer sequences spend MORE of each token's flops in attention, which
# the Pallas flash kernel runs at MXU density, so utilization RISES
# with context length (measured r3, save_qkv: 0.626 b8·s1024 → 0.651
# b2·s4096 → 0.692 b1·s8192; 0.667 b1·s16384 save_attn). The
# reference's 65.6% HFU headline ran BLOCK_SIZE=4096
# (fsdp_llama2_entry.sh:11); the s4096 attempt is the seq-matched
# comparison and rides along as mfu_at_baseline_seq4096 in the
# emitted record.
# budgets sum to ≤870s so the documented `timeout 900 python bench.py`
# always reaches the last config even if every larger attempt grinds to
# its per-attempt timeout. Off the chip every attempt fails at once:
# run_config needs a TPU.
_ATTEMPTS = [
    ("llama-1.4b", 1, 8192, "save_qkv", 280),
    ("llama-1.4b", 2, 4096, "save_qkv", 170),
    ("llama-1.4b", 8, 1024, "save_qkv", 110),
    # gpt2-1.5b's tied 50k-vocab embedding puts params at 1.56B, so
    # save_qkv's HBM-pinned residuals OOM the 16 GiB chip — but the
    # offload twin keeps the same residual set in pinned host memory,
    # escaping full remat's ~30% backward recompute; with d=64 the
    # attention kernels also run head-packed (attn_head_pack auto)
    ("gpt2-1.5b", 8, 1024, "save_qkv_offload", 110),
    ("gpt2-355m", 16, 1024, "full", 60),
    ("gpt2-124m", 16, 512, "none", 60),
    ("tiny", 8, 128, "none", 80),
]

# seq-matched companion for the long-context lead config (the baseline
# measured at 4096): embedded in the record when budget allows. Derived
# from the attempt ladder so the fallback record and the companion are
# always the SAME recipe.
_BASELINE_SEQ_COMPANION = _ATTEMPTS[1][:4]
assert _BASELINE_SEQ_COMPANION[2] == 4096

# the gpt2-family fallback stays MEASURED even when the flagship wins
# (BASELINE.md #8 is judged per shape family; without this the gpt2
# series would only appear in rounds where the flagship fails) —
# embedded as record["fallback"] when budget allows
_GPT2_FALLBACK = _ATTEMPTS[3][:4]
assert _GPT2_FALLBACK[0].startswith("gpt2")


# (n_head, head_dim) pairs the flash gate runs: the flagship's clean
# 128-wide heads AND the gpt2-family narrow-head shapes — gpt2-1.5b's
# odd 25 heads exercise auto head-packing (pack=2) plus the zero-pad
# path; gpt2-355m's even 16×64 packs without padding. The d<128
# entries double as the fp8 gate's shape source: the fp8 train path
# targets exactly this shape family (see _check_fp8_shape).
_KERNEL_CHECK_SHAPES = [(16, 128), (25, 64), (16, 64)]


def check_kernels(b=2, s=1024) -> bool:
    """On-chip numerics gate for the hand-written gradients in the hot
    path: the Pallas flash kernels (fwd+bwd vs mha_reference, at every
    _KERNEL_CHECK_SHAPES head geometry), the fused lm-head
    cross-entropy custom_vjp (vs the materialized-logits path), and the
    fp8 delayed-scaling GEMM (vs the plain dot, at the narrow-head
    family's projection shapes). Which gates run comes from the one
    capability table (accelerate.device_context.kernel_capabilities),
    the same gating the train step uses — so the bench checks exactly
    the kernel set that will execute.

    Runs at bench-like shapes on the REAL device (tests/test_ops.py and
    tests/test_fused_ce.py cover CPU/interpret mode only), so silent
    tile/clamp/chunk regressions show up in the BENCH json as
    kernels_ok=false instead of as quietly-wrong training.
    """
    import jax
    import numpy as np

    from dlrover_tpu.accelerate.device_context import kernel_capabilities
    from dlrover_tpu.common import device

    device.require_tpu()  # off the chip there is no kernel to check

    caps = kernel_capabilities()

    def close(a, b, tol):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        denom = np.maximum(np.abs(b).max(), 1e-6)
        return float(np.abs(a - b).max() / denom) < tol

    ok = True
    if caps.flash_attention:
        for h, d in _KERNEL_CHECK_SHAPES:
            ok = ok and _check_flash_shape(close, b, s, h, d)
    # paged decode kernel at the same head geometries: the serving path
    # gates on caps.paged_attention exactly like the engine does
    if caps.paged_attention:
        for h, d in _KERNEL_CHECK_SHAPES:
            ok = ok and _check_paged_shape(close, h, d)
    ok = ok and _check_fused_ce(close)
    # fp8 gate at the narrow-head family's GEMM shapes (d_model = h·d,
    # ff = 4·d_model — the gpt2 projections the fp8 path targets);
    # runs everywhere the bench runs on-device: non-native hardware
    # executes the same recipe through bf16 upcasts
    for h, d in _KERNEL_CHECK_SHAPES:
        if d < 128:
            ok = ok and _check_fp8_shape(
                close, h * d, 4 * h * d, caps.fp8_native
            )
    return bool(ok)


def _check_fp8_shape(close, k, n, native) -> bool:
    """fp8_dot (delayed scaling) vs the plain f32 GEMM at one (K, N):
    quantization noise only after the amax histories warm up, plus the
    state-on-cotangent convention (the backward's state output is a
    pushed amax history, not a gradient). On fp8-native hardware also
    pins native MXU dots against the bf16-upcast of the SAME quantized
    values — the documented everywhere-identical-numerics contract."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops import fp8

    kx, kw = jax.random.split(jax.random.key(23))
    x = jax.random.normal(kx, (256, k), jnp.bfloat16)
    w = jax.random.normal(kw, (k, n), jnp.bfloat16) * 0.02

    def loss(x, w, st):
        out = fp8.fp8_dot(x, w, st)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    # warm one step so the delayed scales reflect this data (the init
    # histories of ones would clip a unit-normal x)
    st = jax.jit(jax.grad(loss, argnums=2))(x, w, fp8.init_fp8_state())
    out = jax.jit(fp8.fp8_dot)(x, w, st)
    ref = jnp.dot(
        x.astype(jnp.float32), w.astype(jnp.float32)
    )
    ok = close(out, ref, 0.1)  # e4m3 quantization noise
    st2 = jax.jit(jax.grad(loss, argnums=2))(x, w, st)
    amax_x = float(jnp.max(jnp.abs(x.astype(jnp.float32))))
    ok = ok and abs(float(st2["amax_x"][-1]) - amax_x) < 1e-3 * amax_x
    ok = ok and st2["amax_g"].shape == st["amax_g"].shape
    if native:
        out_bf16 = jax.jit(
            lambda x, w, st: fp8.fp8_dot(x, w, st, native=False)
        )(x, w, st)
        ok = ok and close(out, out_bf16, 1e-2)
    return bool(ok)


def _check_flash_shape(close, b, s, h, d) -> bool:
    """Flash fwd+bwd vs mha_reference at one head geometry."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops.attention import mha_reference
    from dlrover_tpu.ops.pallas_attention import flash_attention

    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.bfloat16)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=True, block_q=1024,
                              block_k=1024)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    def loss_ref(q, k, v):
        out = mha_reference(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    (lf, of), gf = jax.jit(
        jax.value_and_grad(loss_flash, argnums=(0, 1, 2), has_aux=True)
    )(q, k, v)
    (lr_, orr), gr = jax.jit(
        jax.value_and_grad(loss_ref, argnums=(0, 1, 2), has_aux=True)
    )(q, k, v)

    ok = close(of, orr, 2e-2)
    for a, b_ in zip(gf, gr):
        ok = ok and close(a, b_, 3e-2)
    return bool(ok)


def _check_paged_shape(close, h, d, b=4, page_size=8, pages=6) -> bool:
    """Fused paged-decode kernel vs the pure-jnp block-table reference
    at one head geometry, on the REAL device: ragged per-slot lengths
    (pages partially filled, tables partially assigned), GQA when the
    head count allows it, decode (C=1) and chunk (C=4) variants, plus
    one sliding-window decode. The reference gathers only the pages the
    table names, so a kernel that walks one page too few/too many or
    mis-masks the tail shows up here as kernels_ok=false."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.ops import pallas_paged

    hkv = h // 4 if h % 4 == 0 else h  # GQA groups=4 when divisible
    n_phys = 1 + b * pages  # physical page 0 is the trash page
    ks = jax.random.split(jax.random.key(11), 4)
    pools = {
        "k": jax.random.normal(
            ks[0], (n_phys, page_size, hkv, d), jnp.bfloat16
        ),
        "v": jax.random.normal(
            ks[1], (n_phys, page_size, hkv, d), jnp.bfloat16
        ),
    }
    rng = np.random.default_rng(29)
    lens = rng.integers(page_size, pages * page_size, b)
    tables = np.full((b, pages), -1, np.int32)
    nxt = 1
    for i in range(b):
        for j in range(-(-int(lens[i]) // page_size)):
            tables[i, j] = nxt
            nxt += 1
    tables = jnp.asarray(tables)
    pos = jnp.asarray(lens - 1, jnp.int32)
    scale = d ** -0.5

    ok = True
    q1 = jax.random.normal(ks[2], (b, 1, h, d), jnp.bfloat16)
    for window in (0, 3 * page_size // 2):
        out_k = pallas_paged.paged_attention(
            q1, pools, tables, pos, scale=scale, window=window,
            kv_heads=hkv, variant="decode",
        )
        out_r = pallas_paged.paged_attention_reference(
            q1, pools, tables, pos, scale=scale, window=window,
            kv_heads=hkv, variant="decode",
        )
        ok = ok and close(out_k, out_r, 2e-2)
    c = 4
    qc = jax.random.normal(ks[3], (b, c, h, d), jnp.bfloat16)
    pos_c = pos[:, None] - jnp.arange(c - 1, -1, -1)[None, :]
    out_k = pallas_paged.paged_attention(
        qc, pools, tables, pos_c, scale=scale, kv_heads=hkv,
        variant="chunk",
    )
    out_r = pallas_paged.paged_attention_reference(
        qc, pools, tables, pos_c, scale=scale, kv_heads=hkv,
        variant="chunk",
    )
    ok = ok and close(out_k, out_r, 2e-2)
    return bool(ok)


def _check_fused_ce(close, b=2, s=512, dm=2048, v=32000) -> bool:
    """Fused CE vs materialized logits: logz + grads w.r.t. x and w."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops.fused_ce import fused_linear_ce

    kx, kw, kt = jax.random.split(jax.random.key(11), 3)
    x = jax.random.normal(kx, (b, s, dm), jnp.bfloat16)
    w = jax.random.normal(kw, (dm, v), jnp.bfloat16) * 0.02
    t = jax.random.randint(kt, (b, s), 0, v)

    def nll_fused(x, w):
        logz, tgt, _ = fused_linear_ce(x, w, t)
        return jnp.mean(logz - tgt)

    def nll_ref(x, w):
        logits = jnp.einsum(
            "bsd,dv->bsv", x, w, preferred_element_type=jnp.float32
        )
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t[..., None], -1)[..., 0]
        return jnp.mean(logz - tgt)

    lf, gf = jax.jit(jax.value_and_grad(nll_fused, argnums=(0, 1)))(x, w)
    lr, gr = jax.jit(jax.value_and_grad(nll_ref, argnums=(0, 1)))(x, w)
    ok = abs(float(lf) - float(lr)) / max(abs(float(lr)), 1e-6) < 1e-2
    for a, b_ in zip(gf, gr):
        ok = ok and close(a, b_, 3e-2)
    return bool(ok)


def measure_mxu_ceiling(n_pairs: int = 40, reps: int = 5) -> dict:
    """Achievable chained-matmul rate at the flagship's MLP shapes, plus
    the gpt2-1.5b fallback's shapes for comparison.

    The practical ceiling the step competes against — NOT the nominal
    peak. The second measurement quantifies the fallback config's
    documented shape penalty (d=1600 is 12.5 MXU tiles, so every matmul
    pads 1600 -> 1664): the bound the gpt2-1.5b MFU should be judged
    against rides in the BENCH json instead of only in the README.
    Methodology: a single timed call folds one host round-trip into a
    short measurement; chaining ``reps`` calls and syncing once
    amortizes it.
    """
    import time as _time

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.common import device

    # the one peak table; a device kind it does not know is an error
    peak = device.chip_spec(device.require_tpu().device_kind).bf16_tflops

    def chained_rate(n, d, f):
        a0 = jax.random.normal(jax.random.key(5), (n, d), jnp.bfloat16)
        wm = jax.random.normal(jax.random.key(6), (d, f), jnp.bfloat16)
        wm = wm * 0.02
        wn = jax.random.normal(jax.random.key(7), (f, d), jnp.bfloat16)
        wn = wn * 0.0005

        @jax.jit
        def chain(a):
            def body(c, _):
                c = jnp.dot(c, wm, preferred_element_type=jnp.bfloat16)
                c = jnp.dot(c, wn, preferred_element_type=jnp.bfloat16)
                return c, None

            out, _ = jax.lax.scan(body, a, None, length=n_pairs)
            return out

        out = chain(a0)
        float(jnp.sum(out.astype(jnp.float32)))  # warm + sync
        t0 = _time.perf_counter()
        for _ in range(reps):
            out = chain(out)
        float(jnp.sum(out.astype(jnp.float32)))
        dt = _time.perf_counter() - t0
        fl = 2 * n * d * f * 2 * n_pairs * reps
        return fl / dt / 1e12

    tf = chained_rate(8192, 2048, 5632)  # llama-1.4b MLP shapes
    tf_gpt2 = chained_rate(8192, 1600, 6400)  # gpt2-1.5b MLP shapes
    return {
        "mxu_tflops": round(tf, 1),
        "mxu_ceiling_frac": round(tf / peak, 4),
        "mxu_ceiling_frac_gpt2_shapes": round(tf_gpt2 / peak, 4),
    }


# bytes per element for the HLO shape dtypes that ride collectives
_HLO_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVE_OPS = (
    "reduce-scatter",
    "all-reduce",
    "all-gather",
    "all-to-all",
    "collective-permute",
)


def collective_stats(hlo_text: str) -> dict:
    """Per-step collective profile of an optimized HLO module.

    Returns ``{"counts": {op: n}, "bytes_by_dtype": {dtype: B},
    "bytes_by_op": {op: B}}`` — op counts for each collective kind and
    the summed RESULT payload bytes grouped by wire dtype and by op.
    This is what the MULTICHIP dryrun embeds in its record so a
    replicated-update regression (full-gradient all-reduce sneaking
    back in) or a wire-dtype change is visible in the trajectory, not
    just in local tests. ``bytes_by_op`` feeds ``overlap_report``.
    """
    import re

    shape_re = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    counts = {op: 0 for op in _COLLECTIVE_OPS}
    bytes_by_dtype: dict = {}
    bytes_by_op: dict = {}
    for line in hlo_text.splitlines():
        parts = line.split(" = ", 1)
        if len(parts) != 2:
            continue
        rhs = parts[1]
        hit = None
        for op in _COLLECTIVE_OPS:
            k = rhs.find(op + "(")
            if k >= 0:
                hit = (op, k)
                break
        if hit is None:
            continue
        op, k = hit
        counts[op] += 1
        for dt, dims in shape_re.findall(rhs[:k]):
            if dt not in _HLO_DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            b = n * _HLO_DTYPE_BYTES[dt]
            bytes_by_dtype[dt] = bytes_by_dtype.get(dt, 0) + b
            bytes_by_op[op] = bytes_by_op.get(op, 0) + b
    return {
        "counts": {k: v for k, v in counts.items() if v},
        "bytes_by_dtype": bytes_by_dtype,
        "bytes_by_op": bytes_by_op,
    }


# Aggregate per-chip ICI bandwidth (GB/s) by device-kind substring —
# rough planning numbers for the overlap estimate, not spec-sheet
# precision; the report rounds to whole µs anyway. CPU gets a token
# value so virtual-device dryruns produce a structurally-valid report.
_ICI_GBPS = {
    "v4": 300.0,
    "v5 lite": 400.0,
    "v5e": 400.0,
    "v5p": 800.0,
    "v6 lite": 900.0,
    "v6e": 900.0,
    "v7": 1200.0,
    "cpu": 10.0,
}

# which step-phase window each collective class can hide under: the
# gradient wire (reduce-scatter / all-reduce / all-to-all) is issuable
# while the backward pass still computes earlier layers' grads; the
# param return (all-gather) overlaps the next forward. permute is
# pipeline traffic, on the critical path by construction — no window.
_BWD_OPS = ("reduce-scatter", "all-reduce", "all-to-all")
_FWD_OPS = ("all-gather",)

# bytes actually moved per chip, per RESULT byte, in a ring
# implementation at large dp: all-reduce moves ~2x its payload
# (reduce-scatter phase + all-gather phase), the others ~1x
_WIRE_FACTOR = {"all-reduce": 2.0}


def _ici_gbps(device_kind: str) -> float:
    kind = (device_kind or "").lower()
    for key, val in _ICI_GBPS.items():
        if key in kind:
            return val
    return 400.0


def overlap_report(stats, step_us, device_kind="", bwd_frac=2 / 3,
                   grad_accum=1, update_mode=""):
    """Exposed-vs-hidden time estimate for one step's collectives.

    For each collective class, wire time = payload bytes × ring factor
    / ICI bandwidth; the hiding window is the share of the step the
    scheduler can issue it under (backward ≈ ``bwd_frac`` of the step
    for gradient traffic, the rest for the all-gather param return;
    collective-permute gets no window — pipeline traffic is the
    critical path). Classes sharing a window compete for it, so
    exposure is computed per window and attributed to ops pro rata by
    their wire time. An ESTIMATE in the same counterfactual spirit as
    ``_nonmatmul_us_per_step``, not a profile: it exists so the bench
    record shows whether the ZeRO wire is latency we pay or latency
    we hide, and how that moves when bucket size / wire dtype change.

    ``update_mode="zero2"`` with ``grad_accum > 1`` scales the gradient
    wire (reduce-scatter / all-to-all) by ``grad_accum``: ZeRO-2 pays
    the exchange once per MICROBATCH (the scattered accumulator is what
    frees the full-grad buffer), and ``collective_stats`` counts the
    accum scan's body once. ZeRO-1 defers to one exchange per step, so
    its bytes pass through unscaled.

    Returns ``{"per_op": {op: {wire_us, window_us, exposed_us}},
    "exposed_us_total", "hidden_us_total", "assumed_ici_gbps"}``.
    """
    gbps = _ici_gbps(device_kind)
    by_op = dict(stats.get("bytes_by_op", {}))
    if update_mode == "zero2" and grad_accum > 1:
        for op in ("reduce-scatter", "all-to-all"):
            if op in by_op:
                by_op[op] = by_op[op] * grad_accum
    windows = {
        "bwd": step_us * bwd_frac,
        "fwd": step_us * (1 - bwd_frac),
        "none": 0.0,
    }
    wire = {}
    for op, b in by_op.items():
        wire[op] = b * _WIRE_FACTOR.get(op, 1.0) / (gbps * 1e3)
    per_op = {}
    exposed_total = 0.0
    hidden_total = 0.0
    for wname, ops in (
        ("bwd", _BWD_OPS),
        ("fwd", _FWD_OPS),
        ("none", ("collective-permute",)),
    ):
        w_total = sum(wire.get(op, 0.0) for op in ops)
        if w_total <= 0.0:
            continue
        win = windows[wname]
        exposed = max(0.0, w_total - win)
        for op in ops:
            if op not in wire:
                continue
            share = wire[op] / w_total
            per_op[op] = {
                "wire_us": round(wire[op], 1),
                "window_us": round(win, 1),
                "exposed_us": round(exposed * share, 1),
            }
        exposed_total += exposed
        hidden_total += w_total - exposed
    return {
        "per_op": per_op,
        "exposed_us_total": round(exposed_total, 1),
        "hidden_us_total": round(hidden_total, 1),
        "assumed_ici_gbps": gbps,
    }


def suggest_bucket_mb(total_grad_bytes, device_kind="", launch_us=5.0,
                      grad_accum=1, update_mode=""):
    """Bucket size for the ZeRO reduce-scatter wire, from the same
    bandwidth model as ``overlap_report``.

    Two constraints pull against each other: each bucket's wire time
    should dominate its launch latency (≥ ~4× ``launch_us``, else the
    exchange is launch-bound and fewer/bigger buckets win), and there
    should be ≥ 4 buckets so the first reduce-scatters issue while the
    backward tail still computes (one mega-bucket serializes the whole
    wire after the last gradient — see sharding.exchange_buckets'
    reverse issue order). Under ``update_mode="zero2"`` the exchange
    runs once per microbatch, so the launch cost recurs ``grad_accum``
    times per step against the SAME per-exchange payload — the
    launch-bound floor scales with ``grad_accum`` (bigger buckets,
    fewer total launches), while the ≥4-bucket cap still uses the
    per-microbatch bytes. Clamped to [1, 64] MB; the result is a
    starting point for ``CommConfig.bucket_mb``, not an oracle.
    """
    gbps = _ici_gbps(device_kind)
    passes = grad_accum if (update_mode == "zero2" and grad_accum > 1) else 1
    # smallest bucket whose wire time is >= 4x the per-step launch cost
    min_bytes = 4.0 * launch_us * passes * gbps * 1e3
    mb = max(1.0, min_bytes / 2**20)
    # but keep at least 4 buckets in flight per exchange
    mb = min(mb, max(1.0, total_grad_bytes / 4 / 2**20))
    return round(min(mb, 64.0), 2)


def drill_recovery_metric(path=None):
    """The latest eviction drill's ``recovery_s``, for the bench record.

    MFU says how fast training goes; ``recovery_s`` says how long a
    failure stops it. They are produced by different drivers into
    different artifacts (BENCH_*.json vs DRILL_*.json), so the bench
    record embeds the drill's number and the two trajectories share one
    comparable entry. Reads the drill artifact
    (``DLROVER_TPU_DRILL_ARTIFACT``, else the newest ``DRILL_r*.json``
    beside this file); returns ``None`` when no drill has run — the
    record then shows the metric as unmeasured rather than omitting it.
    """
    import glob

    if path is None:
        path = os.environ.get("DLROVER_TPU_DRILL_ARTIFACT")
    if path is None:
        here = os.path.dirname(os.path.abspath(__file__))
        candidates = sorted(glob.glob(os.path.join(here, "DRILL_r*.json")))
        path = candidates[-1] if candidates else None
    if not path:
        return None
    try:
        with open(path) as f:
            artifact = json.load(f)
    except (OSError, ValueError):
        return None
    failures = artifact.get("failures") or []
    if not failures:
        return None
    worst = max(
        (f for f in failures if "recovery_s" in f),
        key=lambda f: float(f["recovery_s"]),
        default=None,
    )
    if worst is None:
        return None
    out = {
        "recovery_s": float(worst["recovery_s"]),
        "kind": worst.get("kind", ""),
        "budget_s": artifact.get("recovery_budget_s"),
        "n_failures": len(failures),
    }
    evict = [
        f for f in failures
        if f.get("kind") == "host_eviction_live_reshard"
    ]
    if evict:
        out["live_reshard_recovery_s"] = float(evict[-1]["recovery_s"])
    return out


def serving_trajectory_metric(path=None):
    """The latest serving bench's headline numbers, for the train record.

    Same cross-artifact embed as ``drill_recovery_metric``: the serving
    bench writes ``SERVE_*.json`` (``bench.py serve`` with
    ``DLROVER_TPU_SERVE_ARTIFACT_OUT``); the train record carries its
    tokens/s-at-p99 so one trajectory file compares training AND serving
    across commits. Reads ``DLROVER_TPU_SERVE_ARTIFACT``, else the
    newest ``SERVE_*.json`` beside this file; None when serving has not
    been benched."""
    import glob

    if path is None:
        path = os.environ.get("DLROVER_TPU_SERVE_ARTIFACT")
    if path is None:
        here = os.path.dirname(os.path.abspath(__file__))
        candidates = sorted(glob.glob(os.path.join(here, "SERVE_*.json")))
        path = candidates[-1] if candidates else None
    if not path:
        return None
    try:
        with open(path) as f:
            artifact = json.load(f)
    except (OSError, ValueError):
        return None
    if artifact.get("serve_tokens_per_s") is None:
        return None
    out = {
        "serve_tokens_per_s": artifact["serve_tokens_per_s"],
        "serve_p99_ms": artifact.get("serve_p99_ms"),
        "p99_target_ms": artifact.get("p99_target_ms"),
        "p99_met": artifact.get("p99_met"),
    }
    # phase-latency axes (histogram-backed benches only — older
    # artifacts predate them, so project only when present)
    for key in (
        "ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms", "tpot_p99_ms",
        "queue_wait_p99_ms",
    ):
        if artifact.get(key) is not None:
            out[key] = artifact[key]
    spec = artifact.get("speculative")
    if spec:
        out["spec_tokens_per_s"] = spec.get("tokens_per_s")
        out["spec_accept_rate"] = spec.get("accept_rate")
        out["spec_speedup_vs_specoff"] = spec.get("speedup_vs_specoff")
    if artifact.get("migration_recovery_s") is not None:
        # serving-tier fault-tolerance headline: kill → first
        # post-migration token, plus the compute migrating saved over
        # the re-prefill failover it replaced
        out["migration_recovery_s"] = artifact["migration_recovery_s"]
        migr = artifact.get("migration") or {}
        out["migration_path"] = migr.get("path")
        out["migration_tokens_saved"] = migr.get(
            "tokens_saved_vs_reprefill"
        )
    pfx = artifact.get("prefix")
    if pfx:
        # prefix-sharing headline: how much of the hot-prefix trace the
        # radix index absorbed, and the one-copy memory win it bought
        out["prefix_hit_rate"] = pfx.get("prefix_hit_rate")
        out["prefill_tokens_saved"] = pfx.get("prefill_tokens_saved")
        out["resident_bytes_dedup_ratio"] = pfx.get(
            "resident_bytes_dedup_ratio"
        )
    asc = artifact.get("autoscale")
    if asc:
        # autoscaling headline: SLO goodput of the scaled fleet, the
        # breach→restored reaction time, and the decision count —
        # pre-autoscaler artifacts simply lack the block (replay via
        # absence, same pattern as the other feature sections)
        out["fleet_tokens_per_s_at_p99"] = asc.get(
            "fleet_tokens_per_s_at_p99"
        )
        out["autoscale_reaction_s"] = asc.get("autoscale_reaction_s")
        out["scale_decisions"] = asc.get("scale_decisions")
        out["autoscale_goodput_win"] = asc.get("goodput_win_vs_pinned1")
    dis = artifact.get("disagg")
    if dis:
        # disaggregation headline: how much the prefill/decode split
        # shields stream decode pace from a concurrent prompt burst
        # (>1 = split is better), plus the handoff tax it pays for it
        out["disagg_interference_win"] = dis.get(
            "tpot_p99_interference_win"
        )
        out["disagg_tpot_burst_p99_ms"] = (dis.get("disagg") or {}).get(
            "tpot_burst_p99_ms"
        )
        out["unified_tpot_burst_p99_ms"] = (
            dis.get("unified") or {}
        ).get("tpot_burst_p99_ms")
        out["disagg_handoff_ms_p99"] = (dis.get("disagg") or {}).get(
            "handoff_ms_p99"
        )
        out["disagg_tokens_per_s"] = (dis.get("disagg") or {}).get(
            "tokens_per_s"
        )
    return out


def sparse_serving_trajectory_metric(path=None):
    """The latest SPARSE serving bench's headline numbers, for the train
    record: QPS at fixed p99 with the tiered hit-rates.

    Same cross-artifact embed as ``serving_trajectory_metric``, but a
    separate artifact family (``SPARSE_SERVE_*.json``, written by
    ``bench.py sparse_serve`` with ``DLROVER_TPU_SPARSE_SERVE_ARTIFACT_OUT``)
    so old ``SERVE_*.json`` artifacts replay byte-for-byte unchanged.
    Reads ``DLROVER_TPU_SPARSE_SERVE_ARTIFACT``, else the newest
    ``SPARSE_SERVE_*.json`` beside this file; None when the sparse arm
    has not been benched."""
    import glob

    if path is None:
        path = os.environ.get("DLROVER_TPU_SPARSE_SERVE_ARTIFACT")
    if path is None:
        here = os.path.dirname(os.path.abspath(__file__))
        candidates = sorted(
            glob.glob(os.path.join(here, "SPARSE_SERVE_*.json"))
        )
        path = candidates[-1] if candidates else None
    if not path:
        return None
    try:
        with open(path) as f:
            artifact = json.load(f)
    except (OSError, ValueError):
        return None
    if artifact.get("sparse_qps") is None:
        return None
    out = {
        "sparse_qps": artifact["sparse_qps"],
        "sparse_p99_ms": artifact.get("sparse_p99_ms"),
        "sparse_p99_target_ms": artifact.get("sparse_p99_target_ms"),
        "sparse_p99_met": artifact.get("sparse_p99_met"),
        "sparse_prefetch_speedup": artifact.get(
            "sparse_prefetch_speedup"
        ),
        "sparse_outputs_exact_equal": artifact.get(
            "sparse_outputs_exact_equal"
        ),
    }
    tiers = (artifact.get("tiers") or {}).get("prefetch_on") or {}
    for key in ("hot_hit_rate", "prefetch_coverage",
                "promote_latency_avg_ms"):
        if tiers.get(key) is not None:
            out[f"sparse_{key}"] = tiers[key]
    return out


# fixed per-step host overhead fraction at the hand-tuned batch, for the
# CPU-side MFU model in the tuned arm: smaller planned batches run more
# (shorter) steps per token, so the fixed dispatch cost is a larger
# fraction of each. ~1% matches the measured host_dispatch_us_per_step
# share at the flagship shape.
_TUNED_DISPATCH_FRAC = 0.01

# the reference chip the cold-start plan is modeled against when the
# bench itself runs on CPU — the flagship _ATTEMPTS ladder was hand-tuned
# for a 16 GiB v5e, so that is the shape the planner must reproduce
_TUNED_REFERENCE_CHIP = "v5e"


def tuned_arm_metric(name, batch, seq, remat, device_kind=""):
    """The brain's cold-start plan vs this hand-tuned config, plus the
    live-refinement reaction time — the ``tuned`` arm of the record.

    Two numbers close the telemetry→config loop into the trajectory
    file:

    - ``cold_start_mfu_frac`` — modeled MFU of the zero-config plan as
      a fraction of the hand-tuned row's, CPU-modeled from the remat
      FLOP-expansion ladder (``_FLOP_EXPANSION``: recompute is executed
      MXU work MFU does not credit) and a fixed per-step dispatch
      overhead that scales inversely with batch. 1.0 when the planner
      reproduces the hand recipe exactly.
    - ``reaction_s`` — wall seconds for a ``BrainTuner`` fed a
      synthetic mid-run overlap-drift regression to emit a versioned
      revision (the changed knob rides along), measured in-process on
      the same plan.

    Never raises: a planner failure records ``{"error": ...}`` so the
    bench row survives a brain regression.
    """
    try:
        from dlrover_tpu.cluster import brain
        from dlrover_tpu.models import get_config

        cfg = get_config(
            name, max_seq=seq, remat=remat, param_dtype="bfloat16"
        )
        kind = device_kind if "TPU" in device_kind.upper() else ""
        kind = kind or _TUNED_REFERENCE_CHIP
        plan = brain.ColdStartPlanner().plan(
            cfg, n_devices=1, seq=seq, device_kind=kind
        )
        exp_hand = _FLOP_EXPANSION.get(remat, 1.0)
        exp_plan = _FLOP_EXPANSION.get(plan.remat or remat, 1.0)
        b_plan = plan.batch_size or batch
        o_hand = _TUNED_DISPATCH_FRAC
        o_plan = _TUNED_DISPATCH_FRAC * batch / max(1, b_plan)
        mfu_frac = (exp_hand * (1.0 + o_hand)) / (
            exp_plan * (1.0 + o_plan)
        )
        tuner = brain.BrainTuner(plan, cooldown_s=0.0)
        t0 = time.perf_counter()
        for _ in range(tuner._drift_patience):
            tuner.on_record(
                brain.telemetry.OverlapDriftRecord(
                    planned_exposed_us=100.0,
                    measured_collective_us=200.0,
                    drift_us=100.0,
                    drift_frac=1.0,
                )
            )
        reaction_s = time.perf_counter() - t0
        rev = tuner.revisions[-1] if tuner.revisions else None
        return {
            "planned": {
                "batch": b_plan,
                "remat": plan.remat or remat,
                "block_k": plan.block_k,
                "comm_bucket_mb": plan.comm_bucket_mb,
                "update_sharding": plan.update_sharding,
                "comm_wire_dtype": plan.comm_wire_dtype,
            },
            "hand": {"batch": batch, "remat": remat},
            "match": (plan.remat or remat) == remat
            and b_plan == batch,
            "cold_start_mfu_frac": round(mfu_frac, 4),
            "modeled_chip": kind,
            "reaction_s": round(reaction_s, 4),
            "reaction_knob": rev.knob if rev else "",
            "reaction_version": rev.version if rev else 0,
        }
    except Exception as e:  # noqa: BLE001
        return {"error": f"{type(e).__name__}: {e}"}


def _measure_migration(params, cfg, *, n_slots, max_len, page_size,
                       mode, prefill_chunk, seed):
    """Serving-tier recovery number: kill 1 of 2 replicas mid-decode
    and time from the kill to the FIRST post-migration token on the
    survivor (the serving analogue of the training drill's
    ``recovery_s``). Rides on the live KV-page migration path
    (serving/migration.py); ``tokens_saved_vs_reprefill`` is the
    prefill+decode compute the migration did NOT redo — the token
    savings of migrating over the old re-prefill failover. Returns
    None when the workload finished before a mid-stream kill landed."""
    import numpy as np

    from dlrover_tpu.serving.migration import ServingMigrator
    from dlrover_tpu.serving.replica import ReplicaRouter, ServingReplica

    kw = dict(
        n_slots=n_slots, max_len=max_len, page_size=page_size, mode=mode,
        prefill_chunk=prefill_chunk, idle_sleep=0.001,
    )
    max_new = max(8, min(16, max_len // 4))
    rng = np.random.default_rng(seed)
    alpha = min(9, cfg.vocab_size)
    prompts = [
        list(rng.integers(1, alpha, int(rng.integers(3, 10))))
        for _ in range(4)
    ]
    r0 = ServingReplica("bench-m0", params, cfg, node_id=0, **kw)
    r1 = ServingReplica("bench-m1", params, cfg, node_id=1, **kw)
    r0.start()
    r1.start()
    try:
        router = ReplicaRouter([r0, r1], migrator=ServingMigrator())

        def mid(rep, want):
            slots = [s for s in rep.server.engine.slots if s is not None]
            return len(slots) == want and all(
                s.phase == "decode" and s.generated
                and not s.req.future.done()
                for s in slots
            )

        # Park the victim's loop from the start and step its engine by
        # hand to a pinned mid-decode state — the warm decode rate is
        # far too fast to catch a mid-stream window by wall clock.
        t_kill = None
        gen_at_kill = {}
        with r1.server.paused() as eng:
            reqs = [router.submit(p, max_new) for p in prompts]
            # the survivor's own half finishes first (warming its jit)
            # so the recovery window times migration, not compilation
            for r in (reqs[0], reqs[2]):
                r.future.result(timeout=300)
            for _ in range(50):
                if mid(r1, 2):
                    break
                eng.step()
            if mid(r1, 2):
                gen_at_kill = {
                    s.req.rid: len(s.generated)
                    for s in eng.slots if s is not None
                }
                t_kill = time.perf_counter()
                r1.kill()
        if t_kill is None:
            return None
        deadline = time.monotonic() + 300
        router.poll()
        report = router.reports[-1]
        t_first = None
        while t_first is None and time.monotonic() < deadline:
            for s in list(r0.server.engine.slots):
                if (
                    s is not None
                    and s.req.rid in gen_at_kill
                    and len(s.generated) > gen_at_kill[s.req.rid]
                ):
                    t_first = time.perf_counter()
                    break
            else:
                if any(
                    r.future.done() for r in reqs if r.rid in gen_at_kill
                ):
                    t_first = time.perf_counter()
                else:
                    time.sleep(0.0005)
        router.wait_all(timeout=600)
        return {
            "migration_recovery_s": (
                round(t_first - t_kill, 4) if t_first else None
            ),
            "path": report.path,
            "migrated": len(report.placements),
            "re_prefilled": len(report.re_prefilled),
            "bytes_moved": report.bytes_moved,
            "tokens_saved_vs_reprefill": report.tokens_saved,
        }
    finally:
        r0.stop()
        r1.kill()


def _measure_hot_prefix(params, cfg, *, n_slots, max_len, page_size,
                        mode, prefill_chunk, seed, k_prompts=3,
                        n_requests=12, max_new=4):
    """Hot-prefix trace: a Zipf-ish mix of ``k_prompts`` shared system
    prompts × unique suffixes, run twice at the same seed — prefix
    sharing on vs off. The sharing-on arm should admit most requests
    through the radix index (prefix_hit_rate), skip the shared pages'
    prefill compute (prefill_tokens_saved, prefill-chunk reduction) and
    hold one physical copy of each hot prefix (resident dedup ratio);
    ``bitwise_equal_vs_sharing_off`` pins that the savings cost zero
    output fidelity. Donor requests (one per system prompt) are kept
    decoding through the trace so their pages stay referenced — the
    index drops a page the moment its last holder evicts."""
    import numpy as np

    from dlrover_tpu.serving.server import GenerationServer

    rng = np.random.default_rng(seed)
    alpha = min(9, cfg.vocab_size)
    sys_len = max_len // 2
    systems = [
        list(rng.integers(1, alpha, sys_len)) for _ in range(k_prompts)
    ]
    # Zipf-ish popularity: system prompt j drawn with p ∝ 1/(j+1)
    w = np.array([1.0 / (j + 1) for j in range(k_prompts)])
    picks = rng.choice(k_prompts, size=n_requests, p=w / w.sum())
    suffixes = [
        list(rng.integers(1, alpha, int(rng.integers(3, page_size + 3))))
        for _ in range(n_requests)
    ]
    # park each donor on a near-max budget and keep a few slots free
    # beyond them, so every donor outlives the whole trace — a donor
    # evicting mid-trace drops its pages from the index and turns the
    # rest of its followers into cold misses
    n_slots = max(n_slots, k_prompts + 3)
    donor_new = max_len - sys_len - 2

    def arm(sharing):
        srv = GenerationServer(
            params, cfg, replica=f"bench-px-{int(sharing)}",
            n_slots=n_slots, max_len=max_len, page_size=page_size,
            mode=mode, prefill_chunk=prefill_chunk,
            prefix_sharing=sharing, idle_sleep=0.001,
        ).start()
        try:
            eng = srv.engine
            srv.generate(list(np.arange(sys_len) % 4 + 1), 2,
                         timeout=600.0)  # eats both jit compiles
            eng._prefill_chunks = 0
            eng._prefix_hits = 0
            eng._prefix_misses = 0
            eng._prefill_tokens_saved = 0
            eng._cow_pages = 0
            eng._peak_dedup = 1.0
            base_prefill = eng.stats()["prefill_tokens"]
            donors = [
                srv.submit(s + [alpha + 1 + j], donor_new)
                for j, s in enumerate(systems)
            ]
            # wait until every donor's prompt is committed (and, with
            # sharing on, interned) before the trace lands — otherwise
            # the first wave of followers admits cold alongside them
            need = sum(sys_len + 1 for _ in systems)
            deadline = time.monotonic() + 300
            while (
                eng.stats()["prefill_tokens"] - base_prefill < need
                and time.monotonic() < deadline
            ):
                time.sleep(0.002)
            futs = [
                srv.submit(systems[p] + suffixes[i], max_new)
                for i, p in enumerate(picks)
            ]
            outs = [f.future.result(timeout=600.0) for f in futs]
            outs += [d.future.result(timeout=600.0) for d in donors]
            st = eng.stats()
        finally:
            srv.stop()
        return outs, st

    outs_on, st_on = arm(True)
    outs_off, st_off = arm(False)
    chunks_on = st_on["prefill_chunks"]
    chunks_off = st_off["prefill_chunks"]
    return {
        "k_prompts": k_prompts,
        "n_requests": n_requests,
        "prefix_hit_rate": round(st_on["prefix_hit_rate"], 4),
        "prefix_hits": st_on["prefix_hits"],
        "prefill_tokens_saved": st_on["prefill_tokens_saved"],
        "cow_pages": st_on["cow_pages"],
        "resident_bytes_dedup_ratio": round(
            st_on["peak_dedup_ratio"], 3
        ),
        "prefill_chunks_sharing_on": chunks_on,
        "prefill_chunks_sharing_off": chunks_off,
        "prefill_chunk_reduction": (
            round(chunks_off / chunks_on, 2) if chunks_on else None
        ),
        "bitwise_equal_vs_sharing_off": outs_on == outs_off,
    }


def _measure_disagg(params, cfg, *, n_slots, max_len, page_size, mode,
                    prefill_chunk, max_new, seed, n_streams=3, n_burst=6):
    """Prompt-burst interference: the same seeded trace served by one
    unified replica vs a 1-prefill + 1-decode split (serving/disagg.py).

    ``n_streams`` short-prompt requests reach steady decode first, then
    ``n_burst`` prompt-heavy requests land at once. On the unified
    engine every burst admission steals ``prefill_chunk``-token steps
    from the streams' decode cadence — their inter-token p99 inflates;
    on the split fleet the decode replica never runs a cold prefill, so
    the streams' pace holds while the prefill pool absorbs the burst.
    ``tpot_burst_p99_ms`` is measured over the STREAM requests only
    (the interference number); ``handoff_ms_p99`` is the decode
    replica's first-fragment→commit latency; fleet tokens/s and e2e
    p99 ride along. ``bitwise_equal_vs_unified`` pins that the split
    changed the transport schedule, not the numerics — both arms run
    the same ``prefill_chunk`` (chunk width changes reduction order)."""
    import numpy as np

    from dlrover_tpu.serving.replica import ReplicaRouter, ServingReplica
    from dlrover_tpu.serving.scheduler import SamplingParams

    rng = np.random.default_rng(seed)
    alpha = min(9, cfg.vocab_size)
    stream_new = max(8, max_new)
    burst_len = max(prefill_chunk * 2, max_len // 2)
    stream_prompts = [
        list(rng.integers(1, alpha, 4)) for _ in range(n_streams)
    ]
    burst_prompts = [
        list(rng.integers(1, alpha, burst_len)) for _ in range(n_burst)
    ]
    sps = [
        SamplingParams(temperature=0.8, top_k=8, seed=31 + i)
        for i in range(n_streams + n_burst)
    ]
    kw = dict(
        n_slots=n_slots, max_len=max_len, page_size=page_size, mode=mode,
        prefill_chunk=prefill_chunk, idle_sleep=0.001,
    )

    def arm(roles):
        reps = [
            ServingReplica(
                f"bench-dg{i}-{role}", params, cfg, node_id=i,
                role=role, **kw,
            ).start()
            for i, role in enumerate(roles)
        ]
        router = ReplicaRouter(reps)
        try:
            # warmup ladder (same idea as one_pass): pays the prefill +
            # decode compiles at EVERY page-walk bucket a timed request
            # can reach, on every engine in the fleet — plus, on the
            # split arm, the staged-import path. A single warmup length
            # leaves bucket recompiles in the timed window, where they
            # stall the coordinator's paused() handshake for seconds.
            n_warm = 0
            for frac in (8, 4, 2, 1):
                warm_len = max(3, (max_len - 3) // frac - 2)
                router.submit(
                    list(np.arange(warm_len) % 4 + 1), 3
                )
                n_warm += 1
            router.wait_all(timeout=600.0)
            for r in reps:
                r.server.scheduler.reset_latencies()
            decode_eng = next(
                (r.server.engine for r in reps if r.role == "decode"),
                reps[0].server.engine,
            )
            t0 = time.perf_counter()
            streams = [
                router.submit(p, stream_new, sampling=sp)
                for p, sp in zip(stream_prompts, sps)
            ]
            # the burst lands only once every stream is PACING — decode
            # slots live, first token out — so the tpot window measures
            # interference, not prefill ordering
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                router.poll()
                pacing = sum(
                    1 for s in decode_eng.slots
                    if s is not None and s.phase == "decode" and s.generated
                )
                if pacing >= n_streams or all(
                    s.future.done() for s in streams
                ):
                    break
                time.sleep(0.002)
            burst = [
                router.submit(p, max_new, sampling=sp)
                for p, sp in zip(burst_prompts, sps[n_streams:])
            ]
            outs = router.wait_all(timeout=600.0)[n_warm:]  # drop warmup
            dt = time.perf_counter() - t0
            tpots = [
                (r.done_t - r.first_token_t) / (stream_new - 1) * 1e3
                for r in streams
                if r.first_token_t and r.done_t and stream_new >= 2
            ]
            hists = router.fleet_histograms()
            stats = [r.server.engine.stats() for r in reps]
            out = {
                "ttft_p50_ms": round(hists["ttft"].percentile(50.0), 2),
                "ttft_p99_ms": round(hists["ttft"].percentile(99.0), 2),
                "tpot_burst_p99_ms": round(
                    float(np.percentile(tpots, 99)), 2
                ) if tpots else None,
                "p99_ms": round(hists["e2e"].percentile(99.0), 2),
                "tokens_per_s": round(
                    (n_streams * stream_new + n_burst * max_new) / dt, 2
                ) if dt > 0 else 0.0,
            }
            if len(roles) > 1:
                out["handoffs"] = sum(s["handoffs_in"] for s in stats)
                out["handoff_bytes"] = sum(
                    s["handoff_bytes"] for s in stats
                )
                if "handoff" in hists and hists["handoff"].n:
                    out["handoff_ms_p99"] = round(
                        hists["handoff"].percentile(99.0), 2
                    )
            return outs, out
        finally:
            router.close()
            for r in reps:
                r.stop()

    outs_uni, uni = arm(["unified"])
    outs_dis, dis = arm(["prefill", "decode"])
    win = None
    if uni.get("tpot_burst_p99_ms") and dis.get("tpot_burst_p99_ms"):
        win = round(
            uni["tpot_burst_p99_ms"] / dis["tpot_burst_p99_ms"], 3
        )
    return {
        "n_streams": n_streams,
        "n_burst": n_burst,
        "burst_prompt_len": burst_len,
        "unified": uni,
        "disagg": dis,
        "tpot_p99_interference_win": win,
        "bitwise_equal_vs_unified": outs_uni == outs_dis,
    }


def _measure_autoscale(params, cfg, *, n_slots, max_len, page_size, mode,
                       prefill_chunk, max_new, seed, n_requests=16):
    """SLO-driven autoscaling headline: the same seeded hot-prefix
    burst trace served three ways — pinned to 1 replica, autoscaled
    1→2 (master/serving_autoscaler.py), and statically provisioned at
    2 (the bitwise reference). The metric is SLO GOODPUT: fleet
    tokens/sec counting only requests that finish inside the p99
    target (``fleet_tokens_per_s_at_p99``) — raw throughput at a blown
    tail is not serving capacity. The target is calibrated from the
    static-2 arm's measured p99 (×1.5 headroom) so the number tracks
    this host's speed instead of a wall-clock constant; the pinned-1
    arm blows it under the burst, the autoscaler's reaction decides
    how much of the trace the scaled fleet saves.

    ``autoscale_reaction_s`` is breach-edge → back-inside-SLO as the
    scaler itself measured it (the clear edge of its latched breach);
    ``scale_decisions`` counts actionable (out/in) decisions. Outputs
    are bitwise-compared across ALL arms: position-indexed sampling
    makes each request's tokens a function of (prompt, seed) only, so
    autoscaling may change WHERE a request runs, never what it says."""
    import numpy as np

    from dlrover_tpu.master.serving_autoscaler import (
        ServingAutoScaler, ServingScalerConfig,
    )
    from dlrover_tpu.serving.replica import ReplicaRouter, ServingReplica
    from dlrover_tpu.serving.scheduler import SamplingParams

    rng = np.random.default_rng(seed)
    alpha = min(9, cfg.vocab_size)
    sys_len = max(prefill_chunk, min(prefill_chunk * 2, max_len // 3))
    systems = [list(rng.integers(1, alpha, sys_len)) for _ in range(2)]
    prompts = [
        systems[i % 2] + list(rng.integers(1, alpha, 4))
        for i in range(n_requests)
    ]
    sps = [
        SamplingParams(temperature=0.8, top_k=8, seed=71 + i)
        for i in range(n_requests)
    ]
    kw = dict(
        n_slots=n_slots, max_len=max_len, page_size=page_size, mode=mode,
        prefill_chunk=prefill_chunk, idle_sleep=0.001,
        # pace every replica like a fixed-rate accelerator host (see
        # GenerationServer.step_period_s): co-located engine loops
        # share this machine's cores, so without pacing a second
        # "replica" adds contention instead of capacity and the whole
        # pinned-vs-scaled comparison inverts
        step_period_s=0.02,
    )

    def arm(n_start, autoscale, target_ms):
        reps = [
            ServingReplica(
                f"bench-as{i}", params, cfg, node_id=i, **kw
            ).start()
            for i in range(n_start)
        ]
        router = ReplicaRouter(reps)
        spare = None
        scaler = None
        try:
            # warmup ladder (same rationale as _measure_disagg): pays
            # every page-walk bucket's compiles before the timed window
            n_warm = 0
            for frac in (8, 4, 2, 1):
                warm_len = max(3, (max_len - 3) // frac - 2)
                router.submit(list(np.arange(warm_len) % 4 + 1), 3)
                n_warm += 1
            router.wait_all(timeout=600.0)
            # the sampled-decode path is a separate per-instance jit
            # wrapper: warm it on EVERY replica or the first timed
            # request pays seconds of compile inside the window
            for r in reps:
                r.server.generate(
                    list(np.arange(prefill_chunk) % 4 + 1), 3,
                    sampling=SamplingParams(
                        temperature=0.8, top_k=8, seed=7
                    ),
                    timeout=600.0,
                )
            if autoscale:
                # the warm spare the provision_fn hands out: started
                # AND ladder-warmed — the engine's jit wrappers are
                # per-instance, so an unwarmed joiner would pay its
                # compiles inside the timed window and a "scale-out"
                # would slow the fleet down
                spare = ServingReplica(
                    "bench-as-spare", params, cfg, node_id=9, **kw
                ).start()
                for frac in (8, 4, 2, 1):
                    warm_len = max(3, (max_len - 3) // frac - 2)
                    spare.server.generate(
                        list(np.arange(warm_len) % 4 + 1), 3,
                        timeout=600.0,
                    )
                spare.server.generate(
                    list(np.arange(prefill_chunk) % 4 + 1), 3,
                    sampling=SamplingParams(
                        temperature=0.8, top_k=8, seed=7
                    ),
                    timeout=600.0,
                )
                spare.server.scheduler.reset_latencies()
                scaler = ServingAutoScaler(
                    router,
                    ServingScalerConfig(
                        p99_target_ms=target_ms,
                        queue_depth_high=n_slots,
                        cooldown_s=1.0,
                        min_replicas=1,
                        max_replicas=2,
                        min_window_n=4,
                        # never shrink inside the bench window — the
                        # scale-in story is the drill's, not this arm's
                        shrink_after_clear=10**6,
                        interval_s=0.02,
                    ),
                    provision_fn=lambda role: spare,
                ).start()
            for r in reps:
                r.server.scheduler.reset_latencies()
            t0 = time.perf_counter()
            reqs = [
                router.submit(p, max_new, sampling=sp)
                for p, sp in zip(prompts, sps)
            ]
            outs = router.wait_all(timeout=600.0)[n_warm:]
            dt = time.perf_counter() - t0
            lats_ms = [
                (r.done_t - r.submit_t) * 1e3 for r in reqs if r.done_t
            ]
            out = {
                "n_replicas_start": n_start,
                "tokens_per_s": round(n_requests * max_new / dt, 2)
                if dt > 0 else 0.0,
                "p99_ms": round(
                    float(np.percentile(lats_ms, 99)), 2
                ) if lats_ms else None,
                "n_requests": n_requests,
                "_lats_ms": lats_ms,
                "_dt": dt,
            }
            if scaler is not None:
                # idle ticks after the trace let the latched breach
                # clear so the restore edge (reaction) is recorded
                deadline = time.monotonic() + 5.0
                while (
                    time.monotonic() < deadline
                    and scaler.last_restore_s <= 0.0
                ):
                    time.sleep(0.02)
                scaler.stop()
                out["scale_decisions"] = sum(
                    1 for d in scaler.decisions if d.direction
                )
                out["autoscale_reaction_s"] = round(
                    scaler.last_restore_s, 3
                ) if scaler.last_restore_s > 0 else None
                out["decision_reaction_s"] = round(
                    scaler.last_reaction_s, 3
                )
                out["n_replicas_final"] = len(router.live_replicas())
            return outs, out
        finally:
            if scaler is not None:
                scaler.stop()
            router.close()
            for r in reps + ([spare] if spare is not None else []):
                r.stop()

    # static-2 first: the bitwise reference AND the target calibration
    outs_static, static2 = arm(2, False, float("inf"))
    target_ms = max(1.0, (static2["p99_ms"] or 1.0) * 1.3)
    outs_pin, pinned1 = arm(1, False, target_ms)
    outs_auto, autoscaled = arm(1, True, target_ms)
    # goodput accounting against the calibrated target, uniformly for
    # every arm (the raw per-request latencies travel out of arm())
    for info in (static2, pinned1, autoscaled):
        lats, dt = info.pop("_lats_ms"), info.pop("_dt")
        within = sum(1 for l in lats if l <= target_ms)
        info["within_target"] = within
        info["goodput_tokens_per_s"] = round(
            within * max_new / dt, 2
        ) if dt > 0 else 0.0
    win = None
    if pinned1["goodput_tokens_per_s"]:
        win = round(
            (autoscaled["goodput_tokens_per_s"] or 0.0)
            / pinned1["goodput_tokens_per_s"], 3,
        )
    return {
        "p99_target_ms": round(target_ms, 2),
        "pinned1": pinned1,
        "autoscaled": autoscaled,
        "static2": static2,
        "fleet_tokens_per_s_at_p99": autoscaled["goodput_tokens_per_s"],
        "autoscale_reaction_s": autoscaled.get("autoscale_reaction_s"),
        "scale_decisions": autoscaled.get("scale_decisions", 0),
        "goodput_win_vs_pinned1": win,
        "bitwise_equal_vs_static2": outs_auto == outs_static,
        "bitwise_equal_pinned_vs_static2": outs_pin == outs_static,
    }


def run_serve(name="tiny", n_requests=8, mode="int8", n_slots=4,
              max_len=64, page_size=8, prefill_chunk=8, max_new=8,
              p99_target_ms=60000.0, seed=0, paged=True,
              compare_gather=True, spec_k=3, compare_spec=True,
              measure_migration=True, measure_prefix=True,
              measure_disagg=True, measure_autoscale=True):
    """Serving throughput: tokens/sec at a fixed p99 latency target.

    Drives the continuous-batching engine (dlrover_tpu/serving/) with
    ``n_requests`` mixed-length concurrent requests through the threaded
    server, after one warmup request that eats both jit compiles
    (prefill chunk + decode batch). The headline is decode tokens/sec
    over the timed window, REPORTED AGAINST the p99 end-to-end latency —
    throughput is only comparable across commits at a fixed tail-latency
    budget, so ``p99_met`` rides along and a p99 regression shows up
    even when tokens/s improves. Also records the paged-KV memory story:
    int8+scales resident bytes vs the bf16 reference geometry (the
    2d/(d+4) reduction the serving docs quote).

    Paged-decode evidence (docs/performance.md): ``decode_kernel`` says
    which attention path ran; ``hbm_traffic_model`` is the analytic
    bytes-touched-per-decode-token model at this geometry (paged ≈ pages
    actually held, gather ≈ the full S_max pool; see
    kv_cache.decode_traffic_bytes); ``phase_split`` divides wall time
    into jitted step vs host scheduling (plus how often the block table
    was re-shipped — the dirty-flag counter). With ``compare_gather``
    a second identically-seeded pass runs the legacy gather engine and
    ``paged_speedup_vs_gather`` records the measured ratio.

    With ``compare_spec`` a speculative-decoding arm
    (``spec_k`` prompt-lookup drafts per slot per step) reruns the
    SAME seeded workload and records its tokens/s-at-p99 plus the
    measured acceptance rate under ``"speculative"``. The prompts
    draw from a small alphabet so n-gram lookup has something to
    match — acceptance on random-token prompts would be ~0 and the
    arm would measure only verify overhead. ``speedup_vs_specoff``
    is reported as measured: on CPU the batched verify step often
    does NOT beat plain decode (the crossover needs accelerator
    batch economics), and the artifact says so honestly.

    With ``measure_prefix`` a hot-prefix trace (Zipf-ish mix of shared
    system prompts × unique suffixes) runs twice at the same seed —
    prefix sharing on vs off — and records the hit rate, the prefill
    compute the radix index absorbed, the resident dedup ratio, and a
    bitwise-equality flag under ``"prefix"``.

    With ``measure_disagg`` the same seeded trace runs unified vs a
    1-prefill + 1-decode split under a concurrent prompt burst and
    records the stream-decode interference number (tpot p99), handoff
    latency/bytes, and a bitwise flag under ``"disagg"``.

    With ``measure_autoscale`` a seeded hot-prefix burst runs pinned-1
    vs autoscaled-1→2 vs static-2 and records the SLO-goodput win,
    ``autoscale_reaction_s``, the decision count, and a bitwise flag
    under ``"autoscale"`` (headlines mirrored at top level)."""
    import numpy as np

    import jax

    from dlrover_tpu.models import decoder, get_config
    from dlrover_tpu.serving import kv_cache as kvc
    from dlrover_tpu.serving.server import GenerationServer

    cfg = get_config(
        name, n_layer=2, d_model=64, d_ff=128, n_head=4,
        vocab_size=128, max_seq=max_len,
    ) if name == "tiny" else get_config(name, max_seq=max_len)
    params = decoder.init(jax.random.key(seed), cfg)

    def one_pass(use_paged, bucketing=True, use_spec_k=0):
        srv = GenerationServer(
            params, cfg, replica="bench", n_slots=n_slots,
            max_len=max_len, page_size=page_size, mode=mode,
            prefill_chunk=prefill_chunk, paged=use_paged,
            page_bucketing=bucketing, spec_k=use_spec_k,
        ).start()
        try:
            # warmup: pays the prefill-chunk + decode-batch compiles.
            # A ladder of prompt lengths (…, half, near-max) runs both
            # jitted steps at every page-walk bucket a timed request
            # can reach, so bucket recompiles land here, not in the
            # timed window. With speculation on, an always-propose
            # draft is installed FOR THE WARMUP ONLY: prompt-lookup
            # over the warmup's (untrained-model) generated tokens can
            # fail to match, silently fall back to plain decode, and
            # leak the verify-step compile — one or more seconds per
            # page bucket — into the timed window. Forcing proposals
            # guarantees the verify jit compiles at every bucket the
            # ladder reaches; the real proposer is restored before
            # timing, so the measured accept rate is the real one.
            warm_new = 2 + (use_spec_k + 1 if use_spec_k else 0)
            real_draft = srv.engine.draft
            if use_spec_k:
                class _WarmDraft:
                    def propose(self, history, k):
                        return [int(history[-1])] * k

                srv.engine.draft = _WarmDraft()
            for frac in (8, 4, 2, 1):
                warm_len = max(3, (max_len - warm_new) // frac - 2)
                warm = list(np.arange(warm_len) % 4 + 1)
                srv.generate(warm, warm_new, timeout=600.0)
            srv.engine.draft = real_draft
            srv.scheduler.reset_latencies()
            srv.engine._tokens = 0
            srv.engine._t0 = None
            srv.engine._step_time = 0.0
            srv.engine._draft_tokens = 0
            srv.engine._accepted_tokens = 0

            rng = np.random.default_rng(seed)
            lens = rng.integers(
                2, max(3, max_len - max_new - 1), n_requests
            )
            # small-alphabet prompts: every arm shares them, and the
            # repetition gives the spec arm's n-gram lookup real
            # structure to match (see docstring)
            alpha = min(9, cfg.vocab_size)
            t0 = time.perf_counter()
            futs = [
                srv.submit(
                    list(rng.integers(1, alpha, int(n))),
                    max_new,
                ).future
                for n in lens
            ]
            for f in futs:
                f.result(timeout=600.0)
            dt = time.perf_counter() - t0
            lat = srv.scheduler.latency_summary()
            stats = srv.engine.stats()
            geom = srv.engine.geom
        finally:
            srv.stop()
        tps = n_requests * max_new / dt if dt > 0 else 0.0
        return tps, dt, lat, stats, geom, lens

    tokens_per_s, dt, lat, eng_stats, geom, lens = one_pass(paged)

    bf16_geom = geom._replace(mode="bf16")
    b_int8 = kvc.resident_bytes(geom._replace(mode="int8"))
    b_bf16 = kvc.resident_bytes(bf16_geom)
    # analytic HBM model at this run's steady state: every slot busy,
    # holding the pages for an average-length finished request
    avg_total = float(np.mean(lens)) + max_new
    pages_held = n_slots * math.ceil(avg_total / page_size)
    paged_step = kvc.decode_traffic_bytes(geom, pages_held, n_slots, True)
    gather_step = kvc.decode_traffic_bytes(
        geom, pages_held, n_slots, False
    )
    record = {
        "metric": f"serve_tokens_per_s[{cfg.name},{mode},{n_slots}slots]",
        "value": round(tokens_per_s, 2),
        "unit": "new_tokens_per_sec",
        "serve_tokens_per_s": round(tokens_per_s, 2),
        "serve_p50_ms": round(lat["p50"], 2),
        "serve_p99_ms": round(lat["p99"], 2),
        "p99_target_ms": p99_target_ms,
        "p99_met": lat["p99"] <= p99_target_ms,
        # per-phase latency from the scheduler's log-bucketed
        # histograms (observability/histogram.py) — TTFT/TPOT are the
        # interactive-serving SLO axes e2e alone can't resolve
        "ttft_p50_ms": round(lat["ttft_p50_ms"], 2),
        "ttft_p99_ms": round(lat["ttft_p99_ms"], 2),
        "tpot_p50_ms": round(lat["tpot_p50_ms"], 2),
        "tpot_p99_ms": round(lat["tpot_p99_ms"], 2),
        "queue_wait_p99_ms": round(lat["queue_wait_p99_ms"], 2),
        "n_requests": n_requests,
        "max_new_tokens": max_new,
        "decode_kernel": eng_stats["decode_kernel"],
        "phase_split": {
            "wall_s": round(dt, 4),
            "step_time_s": round(eng_stats["step_time_s"], 4),
            "host_time_s": round(eng_stats["host_time_s"], 4),
            "table_ships": eng_stats["table_ships"],
        },
        "hbm_traffic_model": {
            "pages_held": pages_held,
            "paged_bytes_per_token": paged_step // n_slots,
            "gather_bytes_per_token": gather_step // n_slots,
            "model_reduction": round(gather_step / paged_step, 2),
        },
        "kv_cache": {
            "mode": mode,
            "page_size": page_size,
            "resident_bytes": kvc.resident_bytes(geom),
            "resident_bytes_int8": b_int8,
            "resident_bytes_bf16": b_bf16,
            "reduction_vs_bf16": round(b_bf16 / b_int8, 3),
        },
    }
    if compare_gather and paged:
        # two baselines: the post-PR gather fallback (pages-held
        # bucketed width) and the pre-PR engine it replaced (full
        # S_max-wide gather+scatter every step)
        g_tps = one_pass(False)[0]
        legacy_tps = one_pass(False, bucketing=False)[0]
        record["gather_tokens_per_s"] = round(g_tps, 2)
        record["legacy_gather_tokens_per_s"] = round(legacy_tps, 2)
        record["paged_speedup_vs_gather"] = (
            round(tokens_per_s / g_tps, 3) if g_tps > 0 else None
        )
        record["paged_speedup_vs_legacy"] = (
            round(tokens_per_s / legacy_tps, 3) if legacy_tps > 0
            else None
        )
    if compare_spec and spec_k:
        s_tps, _, s_lat, s_stats, _, _ = one_pass(
            paged, use_spec_k=spec_k
        )
        record["speculative"] = {
            "spec_k": spec_k,
            "tokens_per_s": round(s_tps, 2),
            "p99_ms": round(s_lat["p99"], 2),
            "p99_met": s_lat["p99"] <= p99_target_ms,
            "draft_tokens": s_stats["draft_tokens"],
            "accepted_tokens": s_stats["accepted_tokens"],
            "accept_rate": round(s_stats["spec_accept_rate"], 4),
            "speedup_vs_specoff": (
                round(s_tps / tokens_per_s, 3)
                if tokens_per_s > 0 else None
            ),
        }
    if measure_migration:
        migr = _measure_migration(
            params, cfg, n_slots=n_slots, max_len=max_len,
            page_size=page_size, mode=mode, prefill_chunk=prefill_chunk,
            seed=seed,
        )
        record["migration"] = migr
        record["migration_recovery_s"] = (
            migr.get("migration_recovery_s") if migr else None
        )
    if measure_prefix:
        record["prefix"] = _measure_hot_prefix(
            params, cfg, n_slots=n_slots, max_len=max_len,
            page_size=page_size, mode=mode, prefill_chunk=prefill_chunk,
            seed=seed,
        )
    if measure_disagg:
        record["disagg"] = _measure_disagg(
            params, cfg, n_slots=n_slots, max_len=max_len,
            page_size=page_size, mode=mode, prefill_chunk=prefill_chunk,
            max_new=max_new, seed=seed,
        )
    if measure_autoscale:
        asc = _measure_autoscale(
            params, cfg, n_slots=n_slots, max_len=max_len,
            page_size=page_size, mode=mode, prefill_chunk=prefill_chunk,
            max_new=max_new, seed=seed,
        )
        record["autoscale"] = asc
        # headline pair: SLO goodput of the scaled fleet + how fast the
        # control loop got the tail back inside the target
        record["fleet_tokens_per_s_at_p99"] = asc[
            "fleet_tokens_per_s_at_p99"
        ]
        record["autoscale_reaction_s"] = asc["autoscale_reaction_s"]
    return record


class _CalibratedColdStore:
    """Cold tier with a calibrated per-multi-get stall, modelling a
    seek-dominated disk / remote store: every batched ``get`` pays one
    fixed latency regardless of batch size (that amortization is
    exactly what the lookahead prefetcher buys). Writes pass through
    unstalled — demotion is off the request path either way."""

    def __init__(self, inner, get_latency_s):
        self.inner = inner
        self.get_latency_s = float(get_latency_s)
        self.width = inner.width

    def get(self, keys):
        if len(keys):
            time.sleep(self.get_latency_s)
        return self.inner.get(keys)

    def put(self, keys, rows, freqs, timestamps):
        self.inner.put(keys, rows, freqs, timestamps)

    def delete(self, keys):
        self.inner.delete(keys)

    def flush(self):
        self.inner.flush()

    def close(self):
        self.inner.close()

    def __len__(self):
        return len(self.inner)


def run_sparse_serve(n_requests=160, n_fields=8, n_dense=6, emb_dim=16,
                     id_space=5000, cold_get_latency_ms=8.0,
                     p99_target_ms=10000.0, seed=0,
                     prefetch_lookahead=16):
    """Tiered sparse-embedding serving: request QPS at a fixed p99.

    The recommender scenario (docs/sparse_serving.md): a DeepFM replica
    scores single requests (``max_batch=1`` — the online-serving
    arrival model where each request has its own latency budget) whose
    embedding rows start ENTIRELY in the cold tier behind a calibrated
    per-multi-get stall. The same seeded trace runs twice — lookahead
    prefetch OFF (every request faults its rows synchronously, two
    stalls per request) then ON (the prefetcher peeks the queue and
    promotes whole lookahead windows off-thread, one stall per window
    per table) — and the artifact records both QPS-at-p99 numbers, the
    measured speedup, the tier hit-rate / prefetch-coverage /
    promotion-latency gauges per arm, and whether the f32 served
    outputs were exactly equal between the arms (they must be: the
    tiers move rows, never values)."""
    import shutil
    import tempfile

    import numpy as np

    from dlrover_tpu.models.deepfm import DeepFM, DeepFMConfig
    from dlrover_tpu.serving.sparse_engine import (
        SparseServingServer,
        merged_tier_snapshot,
        tier_model_tables,
    )
    from dlrover_tpu.sparse import GroupAdam
    from dlrover_tpu.sparse.tiered import TierStats

    far_future = 2**60  # demote-everything cutoff
    cfg = DeepFMConfig(
        n_fields=n_fields, n_dense=n_dense, emb_dim=emb_dim,
        mlp_dims=(32,), seed=seed,
    )
    rng = np.random.default_rng(seed)
    cat = rng.integers(
        0, id_space, size=(n_requests, n_fields)
    ).astype(np.int64)
    dense = rng.normal(size=(n_requests, n_dense)).astype(np.float32)
    labels = (rng.random(n_requests) < 0.3).astype(np.float32)

    model = DeepFM(cfg, optimizer=GroupAdam(lr=5e-3), dense_lr=5e-3)
    tmp = tempfile.mkdtemp(prefix="sparse_serve_bench_")
    try:
        tiered = tier_model_tables(model, tmp)
        for _ in range(2):  # create + train every row the trace touches
            model.train_step(cat, dense, labels)
        demoted = sum(
            t.demote_before_timestamp(far_future) for t in tiered
        )
        for t in tiered:  # calibrate the cold tier AFTER seeding it
            t.cold = _CalibratedColdStore(
                t.cold, cold_get_latency_ms / 1e3
            )

        def one_pass(prefetch):
            srv = SparseServingServer(
                model, cfg, replica="sparse-bench", prefetch=prefetch,
                prefetch_lookahead=prefetch_lookahead,
                max_queue=max(1024, 2 * n_requests), max_batch=1,
            ).start()
            try:
                # warmup: first tracing of the eager forward path
                srv.predict(cat[0], dense[0], timeout=600.0)
                # restore the fully-cold profile and zero the gauges so
                # both arms start from the identical tier state
                for t in tiered:
                    t.demote_before_timestamp(far_future)
                    t.stats = TierStats()
                srv.scheduler.reset_latencies()
                srv.engine._completed = 0
                srv.engine._t0 = 0.0
                t0 = time.perf_counter()
                futs = [
                    srv.submit(cat[i], dense[i]).future
                    for i in range(n_requests)
                ]
                scores = np.array(
                    [f.result(timeout=600.0)[0] for f in futs],
                    np.float32,
                )
                dt = time.perf_counter() - t0
                lat = srv.scheduler.latency_summary()
                tiers = merged_tier_snapshot(tiered)
            finally:
                srv.stop()
            qps = n_requests / dt if dt > 0 else 0.0
            return qps, dt, lat, tiers, scores

        qps_off, dt_off, lat_off, tiers_off, scores_off = one_pass(False)
        qps_on, dt_on, lat_on, tiers_on, scores_on = one_pass(True)
    finally:
        try:
            model.close()
        except Exception:  # noqa: BLE001
            pass
        shutil.rmtree(tmp, ignore_errors=True)

    def _tier_block(t):
        return {
            "hot_hit_rate": round(float(t["hot_hit_rate"]), 4),
            "prefetch_coverage": round(
                float(t["prefetch_coverage"]), 4
            ),
            "promote_latency_avg_ms": round(
                float(t["promote_latency_avg_ms"]), 3
            ),
            "cold_faults": int(t["cold_faults"]),
            "prefetched": int(t["prefetched"]),
            "hot_rows": int(t["hot_rows"]),
            "cold_rows": int(t["cold_rows"]),
        }

    return {
        "metric": (
            f"sparse_serve_qps[deepfm{n_fields}x{emb_dim},f32,"
            f"cold{cold_get_latency_ms:g}ms]"
        ),
        "value": round(qps_on, 2),
        "unit": "requests_per_sec",
        "sparse_qps": round(qps_on, 2),
        "sparse_qps_prefetch_off": round(qps_off, 2),
        "sparse_prefetch_speedup": (
            round(qps_on / qps_off, 3) if qps_off > 0 else None
        ),
        "sparse_p99_ms": round(lat_on["p99"], 2),
        "sparse_p99_ms_prefetch_off": round(lat_off["p99"], 2),
        "sparse_p99_target_ms": p99_target_ms,
        "sparse_p99_met": lat_on["p99"] <= p99_target_ms,
        "sparse_queue_wait_p99_ms": round(
            lat_on["queue_wait_p99_ms"], 2
        ),
        # the correctness half of the comparison: prefetch moves rows
        # across tiers, never values — the served scores must match
        # bitwise between the arms at the same seed
        "sparse_outputs_exact_equal": bool(
            np.array_equal(scores_on, scores_off)
        ),
        "cold_get_latency_ms": cold_get_latency_ms,
        "n_requests": n_requests,
        "demoted_rows": int(demoted),
        "wall_s": {
            "prefetch_on": round(dt_on, 4),
            "prefetch_off": round(dt_off, 4),
        },
        "tiers": {
            "prefetch_on": _tier_block(tiers_on),
            "prefetch_off": _tier_block(tiers_off),
        },
    }


def run_config(name, batch, seq, remat, steps=30, warmup=3,
               state_dtype="bfloat16", block_k=1):
    # steps=30: the one host readback that ends the timed loop is paid
    # once; over 30 steps it is under 1% of the window.
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.common import device
    from dlrover_tpu.models import get_config
    from dlrover_tpu.parallel.mesh import single_device_mesh
    from dlrover_tpu.train import (
        TrainStepBuilder,
        init_train_state,
        make_optimizer,
    )

    info = device.require_tpu()
    cfg = get_config(
        name, max_seq=seq, remat=remat, param_dtype="bfloat16"
    )
    mesh = single_device_mesh()
    opt = make_optimizer(
        learning_rate=1e-4,
        warmup_steps=10,
        decay_steps=1000,
        state_dtype=state_dtype,
    )
    state = init_train_state(jax.random.key(0), cfg, mesh, opt)
    builder = TrainStepBuilder(cfg, mesh, opt)

    tokens = jax.random.randint(jax.random.key(1), (batch, seq), 0, 1000)
    batch_data = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}

    if block_k > 1:
        # fused K-step mode: one dispatch covers block_k steps over a
        # [K, ...]-stacked batch; whole blocks only, so the per-step
        # numbers divide evenly
        step = builder.build_block()
        batch_data = jax.tree.map(
            lambda x: jnp.stack([x] * block_k), batch_data
        )
        n_dispatch = max(steps // block_k, 1)
        n_warm = max(warmup // block_k, 1)
    else:
        step = builder.build()
        n_dispatch = steps
        n_warm = warmup
    total_steps = n_dispatch * block_k

    # AOT-compile so the OPTIMIZED HLO (post-layout, post-fusion — the
    # module the scheduler actually runs) is in hand for the collective
    # profile; the compiled executable then serves as the step, so the
    # timed loop measures exactly the module that was profiled.
    step = step.lower(state, batch_data).compile()
    hlo_text = step.as_text()
    tpu_custom_calls = device.require_kernels(step, "train step")

    # jax returns before the device finishes: each timed window ends in
    # a host readback of the loss, which waits for the value
    for _ in range(n_warm):
        state, metrics = step(state, batch_data)
    warm_loss = float(jnp.ravel(metrics["loss"])[-1])

    # host dispatch time = what the fused loop amortizes: the Python/
    # jit-call overhead per enqueue, measured call-entry to call-return
    # (the device keeps computing after the call returns)
    dispatch_s = 0.0
    t0 = time.perf_counter()
    for _ in range(n_dispatch):
        td = time.perf_counter()
        state, metrics = step(state, batch_data)
        dispatch_s += time.perf_counter() - td
    final_loss = float(jnp.ravel(metrics["loss"])[-1])
    dt = time.perf_counter() - t0
    if not math.isfinite(final_loss):
        raise RuntimeError(
            f"non-finite loss {final_loss} (warmup {warm_loss}): "
            "bench run is numerically invalid"
        )

    tokens_per_s = total_steps * batch * seq / dt
    model_tflops = cfg.flops_per_token(seq) * tokens_per_s / 1e12
    mfu = model_tflops / device.chip_spec(info.device_kind).bf16_tflops
    tag = f",k{block_k}" if block_k > 1 else ""
    overlap = None
    stats = None
    if hlo_text:
        stats = collective_stats(hlo_text)
        if stats["counts"]:
            # per-STEP collective budget: the block HLO carries K steps
            overlap = overlap_report(
                {
                    "bytes_by_op": {
                        op: b / block_k
                        for op, b in stats["bytes_by_op"].items()
                    }
                },
                dt / total_steps * 1e6,
                device_kind=info.device_kind,
            )
    if overlap is not None:
        # compile-time planning numbers become runtime telemetry gauges
        # (plan_* / overlap_* in the metric collectors) so the tuner and
        # brain can compare plan vs measurement without re-running bench
        from dlrover_tpu.observability import telemetry

        hub = telemetry.get_hub()
        if hub.enabled:
            hub.publish(
                telemetry.plan_record_from_overlap(
                    f"{cfg.name},b{batch}x{seq}{tag}",
                    overlap,
                    suggest_bucket_mb(
                        cfg.num_params() * 4,
                        device_kind=info.device_kind,
                    ),
                    getattr(builder, "update_sharding_reason", ""),
                    planned_step_time_s=dt / total_steps,
                )
            )

    # sentinel cost at this shape: a short back-to-back pair (sentinels
    # on vs the already-compiled off step) — the <1% acceptance number
    # the docs' cost model quotes. None when the probe fails or is
    # disabled (the probe pays a second step compile, which smoke tests
    # on tiny hosts opt out of via DLROVER_TPU_SENTINEL_PROBE=0).
    sentinel_overhead_frac = None
    try:
        if os.environ.get("DLROVER_TPU_SENTINEL_PROBE", "1") == "0":
            raise RuntimeError("probe disabled")
        sb = TrainStepBuilder(cfg, mesh, opt, health_sentinels=True)
        s_step = sb.build_block() if block_k > 1 else sb.build()
        s_state = init_train_state(jax.random.key(0), cfg, mesh, opt)
        n_probe = max(min(n_dispatch, 10), 3)
        for _ in range(2):
            s_state, s_metrics = s_step(s_state, batch_data)
        float(jnp.ravel(s_metrics["loss"])[-1])  # sync
        ts = time.perf_counter()
        for _ in range(n_probe):
            s_state, s_metrics = s_step(s_state, batch_data)
        float(jnp.ravel(s_metrics["loss"])[-1])
        t_on = time.perf_counter() - ts
        ts = time.perf_counter()
        for _ in range(n_probe):
            state, metrics = step(state, batch_data)
        float(jnp.ravel(metrics["loss"])[-1])
        t_off = time.perf_counter() - ts
        if t_off > 0:
            sentinel_overhead_frac = round(t_on / t_off - 1.0, 4)
    except Exception:  # noqa: BLE001
        pass
    return {
        "metric": (
            f"train_mfu[{cfg.name},b{batch}x{seq}{tag},{info.device_kind}]"
        ),
        "device": {
            "platform": info.platform, "kind": info.device_kind,
            "count": info.count,
        },
        "tpu_custom_calls": tpu_custom_calls,
        "value": round(mfu, 4),
        "unit": "fraction_of_peak",
        "vs_baseline": round(mfu / _REFERENCE_HFU, 4),
        "tokens_per_sec": round(tokens_per_s, 1),
        "model_tflops_per_sec": round(model_tflops, 2),
        "flop_expansion_est": _FLOP_EXPANSION.get(remat, 1.0),
        "block_k": block_k,
        "host_dispatch_us_per_step": round(
            dispatch_s / total_steps * 1e6, 1
        ),
        "sentinel_overhead_frac": sentinel_overhead_frac,
        "collectives": stats,
        "overlap": overlap,
        # the elastic half of the trajectory: how long the last drilled
        # failure stopped training (None until a drill has run)
        "elastic_recovery": drill_recovery_metric(),
        # the serving half: tokens/s at fixed p99 from the last
        # `bench.py serve` artifact (None until serving has been benched)
        "serving": serving_trajectory_metric(),
        # the recommender half: QPS at fixed p99 with tiered hit-rates
        # from the last `bench.py sparse_serve` artifact (None until the
        # sparse arm has been benched; old SERVE artifacts are untouched)
        "sparse_serving": sparse_serving_trajectory_metric(),
        # the brain's cold-start plan for this shape vs the hand-tuned
        # row above, plus the live-refinement reaction time (in-process
        # drill; see tuned_arm_metric)
        "tuned": tuned_arm_metric(
            name, batch, seq, remat, device_kind=info.device_kind,
        ),
    }


# Executed/counted FLOP ratio by remat tier (fwd+bwd counted as 3×fwd;
# backward re-runs the non-pinned share of the forward): remat recompute
# is real MXU work that MFU deliberately does not credit. Estimates from
# the measured step anatomy (README "Performance notes").
_FLOP_EXPANSION = {
    "full": round((3 + 1.0) / 3, 3),
    "dots_saveable": round((3 + 0.35) / 3, 3),
    "save_attn": round((3 + 0.9) / 3, 3),
    "save_qkv": round((3 + 0.7) / 3, 3),
    # same residual set as save_qkv — the recompute share is identical;
    # the host DMA cost shows up as step time, not as counted flops
    "save_qkv_offload": round((3 + 0.7) / 3, 3),
    "save_qkv_gate": round((3 + 0.5) / 3, 3),
    "save_dots": round((3 + 0.3) / 3, 3),
    "offload_attn": round((3 + 0.9) / 3, 3),
    "none": 1.0,
}


def _classify_failure(returncode, stderr_text: str) -> str:
    """Bucket a failed attempt for the per-attempt JSON line: the
    BENCH_*.json consumer needs to tell a too-small budget (timeout)
    from a config that no longer fits (oom) from a code regression
    (compile_error / error) without digging through driver stderr."""
    txt = stderr_text or ""
    low = txt.lower()
    if any(
        pat in txt
        for pat in ("RESOURCE_EXHAUSTED", "ResourceExhausted")
    ) or "out of memory" in low or "allocation failure" in low:
        return "oom"
    if any(
        pat in txt
        for pat in (
            "Compilation failure",
            "XlaCompile",
            "Mosaic",
            "INVALID_ARGUMENT",
        )
    ) or "lowering" in low or "compilation" in low:
        return "compile_error"
    if returncode is None:
        return "timeout"
    return "error"


def _nonmatmul_us_per_step(record, name, batch, seq, remat):
    """Non-matmul residue per step, from the matmuls-only
    counterfactual: if every EXECUTED flop (counted × remat expansion)
    ran at the measured chained-matmul rate for this shape set, the
    step would take executed/rate seconds — the remainder is
    elementwise/HBM time the MXU never sees (norms, residual adds,
    rope, optimizer). Estimate only: attention flops run through the
    flash kernel, not the matmul chain, so at long seq this reads as a
    LOWER bound (clamped at 0). None when the ceiling wasn't measured
    (CPU smoke runs)."""
    ceiling_key = (
        "mxu_ceiling_frac_gpt2_shapes"
        if name.startswith("gpt2")
        else "mxu_ceiling_frac"
    )
    if not (
        record.get(ceiling_key)
        and record.get("mxu_ceiling_frac")
        and record.get("mxu_tflops")
        and record.get("tokens_per_sec")
    ):
        return None
    step_us = batch * seq / record["tokens_per_sec"] * 1e6
    peak_rate = record["mxu_tflops"] / record["mxu_ceiling_frac"]
    shape_rate = peak_rate * record[ceiling_key]
    executed = record["model_tflops_per_sec"] * _FLOP_EXPANSION.get(
        remat, 1.0
    )
    return round(max(0.0, step_us * (1 - executed / shape_rate)), 1)


def main():
    if len(sys.argv) >= 2:
        # every mode with an argument is a child that compiles; the
        # attempt ladder below is the parent and stays off jax, so its
        # children can have the chip
        from dlrover_tpu.common.compile_cache import enable_compile_cache

        enable_compile_cache()
    if len(sys.argv) >= 2 and sys.argv[1] == "--check":
        print(json.dumps({"kernels_ok": check_kernels()}))
        return
    if len(sys.argv) >= 2 and sys.argv[1] == "--ceiling":
        print(json.dumps(measure_mxu_ceiling()))
        return
    if len(sys.argv) >= 2 and sys.argv[1] in ("serve", "--serve"):
        mode = sys.argv[2] if len(sys.argv) > 2 else "int8"
        n_requests = int(sys.argv[3]) if len(sys.argv) > 3 else 8
        max_len = int(sys.argv[4]) if len(sys.argv) > 4 else 64
        record = run_serve(
            mode=mode, n_requests=n_requests, max_len=max_len
        )
        out = os.environ.get("DLROVER_TPU_SERVE_ARTIFACT_OUT")
        if out:
            with open(out, "w") as f:
                json.dump(record, f)
        print(json.dumps(record))
        return
    if len(sys.argv) >= 2 and sys.argv[1] in (
        "sparse_serve", "--sparse-serve"
    ):
        n_requests = int(sys.argv[2]) if len(sys.argv) > 2 else 160
        cold_ms = float(sys.argv[3]) if len(sys.argv) > 3 else 8.0
        record = run_sparse_serve(
            n_requests=n_requests, cold_get_latency_ms=cold_ms
        )
        out = os.environ.get("DLROVER_TPU_SPARSE_SERVE_ARTIFACT_OUT")
        if out:
            with open(out, "w") as f:
                json.dump(record, f)
        print(json.dumps(record))
        return
    if len(sys.argv) >= 5 and sys.argv[1] == "--single":
        name, batch, seq, remat = (
            sys.argv[2],
            int(sys.argv[3]),
            int(sys.argv[4]),
            sys.argv[5] if len(sys.argv) > 5 else "none",
        )
        state_dtype = sys.argv[6] if len(sys.argv) > 6 else "bfloat16"
        block_k = int(sys.argv[7]) if len(sys.argv) > 7 else 1
        print(
            json.dumps(
                run_config(
                    name, batch, seq, remat,
                    state_dtype=state_dtype, block_k=block_k,
                )
            )
        )
        return

    t0 = time.monotonic()
    failed_attempts = []
    for name, batch, seq, remat, budget_s in _ATTEMPTS:
        attempt_id = f"{name},b{batch}x{seq},{remat}"
        try:
            out = subprocess.run(
                [
                    sys.executable,
                    os.path.abspath(__file__),
                    "--single",
                    name,
                    str(batch),
                    str(seq),
                    remat,
                ],
                capture_output=True,
                timeout=budget_s,
                text=True,
            )
            if out.returncode == 0 and out.stdout.strip():
                line = out.stdout.strip().splitlines()[-1]
                record = json.loads(line)  # validate
                # on-chip kernel numerics gate: runs ONCE, in its own
                # subprocess (a kernel hang cannot eat the bench), and
                # only inside whatever remains of the documented 900s
                # envelope — when attempts already consumed it, the
                # check reports null rather than risking the result
                # line itself
                remaining = _DEADLINE_S - (time.monotonic() - t0)
                if remaining >= 45:
                    record["kernels_ok"] = _run_kernel_check(
                        budget_s=int(min(180, remaining))
                    )
                else:
                    sys.stderr.write(
                        "kernel check skipped: bench budget exhausted\n"
                    )
                    record["kernels_ok"] = None
                # achievable-matmul ceiling at the flagship shapes:
                # contextualizes the MFU (remaining gap = remat
                # recompute vs this, not vs the nominal peak)
                remaining = _DEADLINE_S - (time.monotonic() - t0)
                if remaining >= 45:
                    record.update(
                        _run_aux_json(
                            "--ceiling", int(min(120, remaining))
                        )
                    )
                # how close the schedule runs to the ACHIEVABLE rate:
                # executed flops (counted × remat expansion) against the
                # measured chained-matmul ceiling AT THE WINNING
                # CONFIG'S shapes (gpt2 fallbacks pad d=1600 on the MXU
                # — judging them against the llama-shape ceiling would
                # understate them ~10-15%). ~1.0 means the remaining
                # vs_baseline gap is the remat recompute HBM forces,
                # not scheduling losses.
                ceiling_key = (
                    "mxu_ceiling_frac_gpt2_shapes"
                    if name.startswith("gpt2")
                    else "mxu_ceiling_frac"
                )
                nonmatmul = _nonmatmul_us_per_step(
                    record, name, batch, seq, remat
                )
                if nonmatmul is not None:
                    record["nonmatmul_us_per_step"] = nonmatmul
                # the interpretation only holds while trunk matmuls
                # dominate: at long seq the flash kernel's attention
                # flops (not represented in the matmul-chain ceiling,
                # and with a seq-dependent recompute share) push the
                # ratio past 1.0 — emit nothing rather than a
                # >100%-of-achievable number
                if seq > 4096:
                    record.pop("flop_expansion_est", None)
                elif record.get(ceiling_key):
                    record["schedule_vs_achievable"] = round(
                        record["value"]
                        * record.get("flop_expansion_est", 1.0)
                        / record[ceiling_key],
                        3,
                    )
                # seq-matched companion: when the long-context config
                # wins, also measure at the baseline's own seq (4096)
                # so the record carries the apples-to-apples number
                if seq > _BASELINE_SEQ_COMPANION[2]:
                    remaining = _DEADLINE_S - (time.monotonic() - t0)
                    if remaining >= 120:
                        cn, cb, cs, cr = _BASELINE_SEQ_COMPANION
                        comp = _run_aux_json(
                            [
                                "--single", cn, str(cb), str(cs), cr
                            ],
                            int(min(220, remaining)),
                        )
                        if comp.get("value"):
                            record["mfu_at_baseline_seq4096"] = comp[
                                "value"
                            ]
                            record["vs_baseline_at_seq4096"] = comp[
                                "vs_baseline"
                            ]
                # keep the gpt2 series measured when the llama family
                # wins: one fallback-family run rides along so both
                # shape families carry numbers every round
                if not name.startswith("gpt2") and name != "tiny":
                    remaining = _DEADLINE_S - (time.monotonic() - t0)
                    if remaining >= 130:
                        fn, fb_b, fb_s, fb_r = _GPT2_FALLBACK
                        fb = _run_aux_json(
                            [
                                "--single", fn, str(fb_b), str(fb_s),
                                fb_r,
                            ],
                            int(min(220, remaining)),
                        )
                        if fb.get("value"):
                            record["fallback"] = {
                                "metric": fb["metric"],
                                "value": fb["value"],
                                "vs_baseline": fb["vs_baseline"],
                                "mxu_ceiling_frac": record.get(
                                    "mxu_ceiling_frac_gpt2_shapes"
                                ),
                            }
                    else:
                        sys.stderr.write(
                            "gpt2 fallback skipped: budget exhausted\n"
                        )
                if failed_attempts:
                    # larger configs that died before this one won:
                    # carried in the winning record so BENCH_*.json
                    # alone shows WHY the bench fell through
                    record["failed_attempts"] = failed_attempts
                print(json.dumps(record))
                return
            fail = {
                "attempt": attempt_id,
                "failure": _classify_failure(
                    out.returncode, out.stderr
                ),
            }
            failed_attempts.append(fail)
            print(json.dumps(fail))
            sys.stderr.write(
                f"bench config {name} rc={out.returncode}: "
                f"{out.stderr[-800:]}\n"
            )
        except subprocess.TimeoutExpired as e:
            stderr = e.stderr
            if isinstance(stderr, bytes):
                stderr = stderr.decode("utf-8", "replace")
            fail = {
                "attempt": attempt_id,
                "failure": _classify_failure(None, stderr),
            }
            failed_attempts.append(fail)
            print(json.dumps(fail))
            sys.stderr.write(f"bench config {name} timed out ({budget_s}s)\n")
    raise SystemExit("all bench configs failed")


def _run_aux_json(flag, budget_s: int) -> dict:
    """Run ``bench.py <flag...>`` in a subprocess, parse its JSON line."""
    args = [flag] if isinstance(flag, str) else list(flag)
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), *args],
            capture_output=True,
            timeout=budget_s,
            text=True,
        )
        if out.returncode == 0 and out.stdout.strip():
            return json.loads(out.stdout.strip().splitlines()[-1])
    except (subprocess.TimeoutExpired, json.JSONDecodeError):
        pass
    return {}


def _run_kernel_check(budget_s: int = 180):
    return _run_aux_json("--check", budget_s).get("kernels_ok", False)


if __name__ == "__main__":
    main()
