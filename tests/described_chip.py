"""A described v5e for the compile files (``tests/test_tpu_compile.py``
and ``tests/test_tpu_compile_mixers.py``): the fixtures, the recipes'
train steps and the memo of what a process has compiled.

The TPU's compiler is installed in the sandbox and compiles for a device
that is described, not attached (``jax.experimental.topologies``).
Nothing runs, so nothing here says a kernel is right or fast: only that
the chip would take it.

The two files are ONE suite dealt in two by their seconds, because
xdist hands a file to one worker and a whole file was that worker's
whole run (ROADMAP D10). A case and the step it reads stay in the same
file: the memo is a process's. A new cell's compile goes into the
lighter of the two.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.experimental.compilation_cache import compilation_cache  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from dlrover_tpu.common import device  # noqa: E402
from dlrover_tpu.models.config import get_config  # noqa: E402

BF16 = jnp.bfloat16
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {exc}")


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _as_on_the_chip(monkeypatch):
    """The code under test asks the one probe where it runs and would
    take its CPU branch; the test, not a new option of the program,
    tells it otherwise. A compile for a described device is written to
    the persistent cache but cannot be read back without a chip, so the
    cache is off around these tests."""
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    monkeypatch.setattr(device, "on_cpu", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def compiled_kernel(chip, build, n_kernels):
    """One of a file's ``CASES`` compiled for the described chip:
    (compiled, its text), which holds ``n_kernels`` custom calls."""

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    fn, args = build(struct)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == n_kernels
    return compiled, text


# ---- the whole train step: kernel names and phase scopes ------------------
# What a device trace shows for an operation is its HLO instruction's
# name, and what the program's reducer (observability/runtime_timer.py)
# knows of its place in the step is its ``op_name`` metadata. Both are
# decided by the chip's compiler, so both are pinned here, at the
# benchmark's three recipes cut to two layers.

STEP_CASES = {
    # GPT-2 XL widths: head size 64, so the head-packed kernels
    "gpt2-like": dict(
        model="gpt2-1.5b",
        overrides=dict(n_layer=2, max_seq=1024, remat="full",
                       param_dtype="bfloat16"),
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(8, 1024),
        kernels={"flash_fwd_packed", "flash_bwd_dq_packed",
                 "flash_bwd_dkv_packed", "norm_fwd", "norm_bwd"},
        scopes={"embed", "attn", "mlp", "head_loss", "optimizer"},
        stat_tiles="f32[104,1024,8]",  # [B·slabs, S, 8]
        stat_rows="8,25,1024",  # [B, H, S]: span 512.5, never made
    ),
    # Mistral widths: head size 128, GQA 32/8, the window live
    "mistral-like": dict(
        model="mistral-7b",
        overrides=dict(n_layer=2, max_seq=2048, attn_window=1024,
                       remat="full", param_dtype="bfloat16"),
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(1, 2048),
        kernels={"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "norm_fwd", "norm_bwd"},
        scopes={"embed", "attn", "mlp", "head_loss", "optimizer"},
        stat_tiles="f32[32,2048,8]",  # [B·H, S, 8]
        stat_rows="1,32,2048",  # span 768.25 under the window
        # the forward's band is both blocks of 1024; the backward's tile
        # is a quarter of the window
        band=(2, 256),
    ),
    # OLMoE's published widths, one layer of 16: 64 experts of width
    # 1024 top-8 through ``lax.ragged_dot`` (the compiler's own grouped
    # matmul and its tile-table kernel), QK-norm as two more norm calls
    "olmoe-like": dict(
        model="olmoe-1b-7b",
        overrides=dict(n_layer=1, remat="full", param_dtype="bfloat16"),
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(2, 4096),
        kernels={"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "norm_fwd", "norm_bwd", "ragged-dot-none",
                 "ragged-dot-metadata"},
        scopes={"embed", "attn", "mlp", "head_loss", "optimizer",
                "moe.route", "moe.sort", "moe.experts", "moe.combine"},
        stat_tiles="f32[32,4096,8]",
        kept=True,  # span 2,048.5
    ),
    # GLM-4.7-Flash's published widths, 1 dense + 1 routed layer + the
    # prediction module, 8 of 64 experts held: latent attention through
    # the unpacked flash kernels at head size 256 (whose backward tile
    # is cut to fit VMEM), the rank norms as norm calls, the shared
    # expert and the module under scopes of their own
    "glm-like": dict(
        model="glm-4.7-flash",
        overrides=dict(n_layer=2, n_experts_held=8, vocab_size=19360,
                       max_seq=8192, remat="full", param_dtype="bfloat16"),
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(2, 8192),
        kernels={"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "norm_fwd", "norm_bwd", "ragged-dot-none",
                 "ragged-dot-metadata", "rows_sum", "experts_act",
                 "experts_act_bwd"},
        scopes={"embed", "attn", "attn.latent", "mlp", "head_loss", "mtp",
                "optimizer", "moe.route", "moe.sort", "moe.experts",
                "moe.combine", "moe.shared"},
        stat_tiles="f32[40,8192,8]",
        kept=True,
    ),
    # Keye-VL-2.0's language tower as the benchmark's cell runs it (12
    # layers in one scan, 16 of 128 experts held): the indexer, the
    # selection and the alignment term under scopes of their own, the
    # unpacked flash kernels at head size 128 with the selection
    # operand, under names of their own, and the alignment kernel
    "keye-cell": dict(
        model="keye-vl-2.0",
        overrides=dict(n_layer=12, n_experts_held=16, expert_offset=0,
                       vocab_size=18992, max_seq=8192, remat="full",
                       param_dtype="bfloat16"),
        optimizer=dict(state_dtype="bfloat16"), comm=None, chips=1,
        batch=(1, 8192),
        kernels={"flash_fwd_sel", "flash_bwd_dq_sel", "flash_bwd_dkv_sel",
                 "align_kl", "norm_fwd", "norm_bwd", "ragged-dot-none",
                 "ragged-dot-metadata", "rows_sum", "experts_act",
                 "experts_act_bwd"},
        scopes={"embed", "attn", "attn.index", "attn.select",
                "attn.index_loss", "mlp", "head_loss", "optimizer",
                "moe.route", "moe.sort", "moe.experts", "moe.combine"},
        stat_tiles="f32[32,8192,8]",
        kept=True,
    ),
    # the dp=4 ZeRO-1 recipe: f32 parameters, tied head
    "zero1-dp4": dict(
        model="gpt2-1.5b",
        overrides=dict(n_layer=2, max_seq=1024, remat="full",
                       param_dtype="float32"),
        optimizer={}, comm=dict(update_sharding="zero1"), chips=4,
        batch=(32, 1024),
        kernels={"flash_fwd_packed", "flash_bwd_dq_packed",
                 "flash_bwd_dkv_packed", "norm_fwd", "norm_bwd"},
        scopes={"embed", "attn", "mlp", "head_loss", "zero.pack",
                "zero.exchange", "zero.update", "zero.gather"},
        stat_tiles="f32[104,1024,8]",  # 8 of the 32 sequences a chip
        stat_rows="8,25,1024",
    ),
    # the same under ZeRO-2, two microbatches: an exchange (and the tied
    # head's buckets) inside the accumulation scan. Layout guard only.
    "zero2-dp4": dict(
        model="gpt2-1.5b",
        overrides=dict(n_layer=2, max_seq=1024, remat="full",
                       param_dtype="float32"),
        optimizer={}, comm=dict(update_sharding="zero2"), chips=4,
        grad_accum=2, batch=(32, 1024),
    ),
}


_STEP_TEXT = {}


_STEP_MEMORY = {}  # case -> the compiled step's memory_analysis()


_STEP_LOWERED = {}  # case -> the step's text before XLA, where kept


def _compiled_step(topo, case):
    """(builder, compiled text, counters set while tracing) of one of
    STEP_CASES, compiled for the described chips once a session."""
    if case in _STEP_TEXT:
        return _STEP_TEXT[case]
    from dlrover_tpu.observability import tracing
    from dlrover_tpu.parallel import MeshConfig, build_mesh
    from dlrover_tpu.parallel import sharding as shd
    from dlrover_tpu.train import (
        TrainStepBuilder, batch_sharding, make_optimizer,
    )
    from dlrover_tpu.train.train_step import abstract_train_state

    spec = STEP_CASES[case]
    cfg = get_config(spec["model"], **spec["overrides"])
    mesh = build_mesh(
        MeshConfig(dp=-1), devices=list(topo.devices[: spec["chips"]])
    )
    opt = make_optimizer(
        learning_rate=1e-4, warmup_steps=10, decay_steps=1000,
        **spec["optimizer"],
    )
    comm = shd.CommConfig(**spec["comm"]) if spec["comm"] else None
    builder = TrainStepBuilder(
        cfg, mesh, opt, comm=comm, grad_accum=spec.get("grad_accum", 1)
    )
    assert bool(builder.update_sharding) == bool(comm), (
        builder.update_sharding_reason
    )
    state = abstract_train_state(
        cfg, mesh, opt, comm=builder.comm_resolved
    )
    batch = {
        k: jax.ShapeDtypeStruct(
            spec["batch"], jnp.int32, sharding=batch_sharding(mesh)
        )
        for k in ("tokens", "targets")
    }
    tracing._counters.clear()
    lowered = builder.build().lower(state, batch)
    if spec.get("keep_lowered"):
        _STEP_LOWERED[case] = lowered.as_text()
    compiled = lowered.compile()
    _STEP_MEMORY[case] = compiled.memory_analysis()
    _STEP_TEXT[case] = builder, compiled.as_text(), dict(tracing.counters())
    return _STEP_TEXT[case]


def _kernel_calls(text, kernel):
    """Custom calls of ``kernel`` in a compiled step's text (one traced
    under a derivative's rule is ``jvp_<kernel>_``)."""
    import re

    return sum(
        bool(re.match(
            rf"\s*(?:ROOT )?%(?:jvp_)?{kernel}[_.\d]* = .*tpu_custom_call", ln
        ))
        for ln in text.splitlines()
    )
