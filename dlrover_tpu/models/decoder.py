"""Flagship decoder-only transformer, TPU-first.

One functional model covers the GPT-2 and LLaMA families (configs in
``models/config.py``). Design choices driven by XLA/TPU:

- **scan over layers**: per-layer params are stacked on a leading axis and
  the block is a ``lax.scan`` body — one compilation of the layer regardless
  of depth (the reference re-traces per module; atorch
  modules/distributed_modules/transformer.py builds per-layer graphs).
- **parallelism by PartitionSpec, not module swap**: parameters carry
  logical axes (``dlrover_tpu/parallel/sharding.py``); FSDP/TP/SP are rule
  changes, the model code never branches on parallelism (contrast
  atorch layers.py:239 RowParallelLinear module replacement).
- **mixed precision**: params in fp32, compute in bf16, loss/logits fp32 —
  keeps the MXU on bf16 without loss-scale bookkeeping (the reference needs
  GradScaler, atorch amp_optimization.py:28).
- **remat**: ``jax.checkpoint`` over the scan body trades FLOPs for HBM
  (reference: checkpoint_optimization.py:15).
"""

import contextlib
import dataclasses
import functools
import math
import types
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_policies as cp
from jax.sharding import PartitionSpec as P

from dlrover_tpu.common import device
from dlrover_tpu.models.config import (
    ATTN_KINDS, ModelConfig, lightning_log_decay, pattern_parts,
)
from dlrover_tpu.observability.tracing import set_counter
from dlrover_tpu.ops import gated_delta, pallas_norm, pallas_paged, quant, ssd
from dlrover_tpu.ops.attention import _repeat_kv, mha_reference
from dlrover_tpu.parallel import moe, sharding as shd

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _dense_init(key, shape, in_axis_size, dtype):
    scale = 1.0 / np.sqrt(in_axis_size)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def _stackers(cfg: ModelConfig, lead):
    """(stack(key, shape, fan_in), ones(*shape)): one kind of layer's
    tensors on the leading axes ``lead``, in the parameter dtype."""
    pdt = jnp.dtype(cfg.param_dtype)
    lead = tuple(lead)

    def stack(key, shape, fan_in):
        # one RNG draw for all layers: tiny init graph, fast remote compile
        scale = 1.0 / np.sqrt(fan_in)
        return (jax.random.normal(key, lead + shape) * scale).astype(pdt)

    def ones(*shape):
        return jnp.ones(lead + shape, pdt)

    return stack, ones


def _norm_scale(cfg: ModelConfig, *shape):
    """A trunk norm's learned vector as it starts: ones, or the zeros of
    a zero-centred norm (``cfg.norm_zero_centered``: the multiplier is
    1 + w and w is what is stored)."""
    pdt = jnp.dtype(cfg.param_dtype)
    return (jnp.zeros if cfg.norm_zero_centered else jnp.ones)(shape, pdt)


def _init_attention(keys, cfg: ModelConfig, stack, ones) -> Params:
    """The attention's matrices (latent or plain q/k/v/o), drawn from
    ``keys`` through ``stack(key, shape, fan_in)``."""
    d = cfg.d_model
    hd, nh, nkv = cfg.head_dim, cfg.n_head, cfg.kv_heads
    if cfg.latent_attention:
        rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        nope, rd, vd = (
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        )
        # q through its rank, or one matrix where there is none
        q_mats = {
            "wq_a": stack(keys[1], (d, rq), d),
            "q_a_norm": {"scale": ones(rq)},
            "wq_b": stack(keys[11], (rq, nh * (nope + rd)), rq),
        } if rq else {"wq": stack(keys[1], (d, nh * (nope + rd)), d)}
        attn = {
            **q_mats,
            # [c_kv ‖ k_r]: the latent and the rope channels all heads share
            "wkv_a": stack(keys[2], (d, rkv + rd), d),
            "kv_a_norm": {"scale": ones(rkv)},
            # per head [k_nope ‖ v]
            "wkv_b": stack(keys[3], (rkv, nh * (nope + vd)), rkv),
            "wo": stack(keys[4], (nh * vd, d), nh * vd),
        }
    else:
        attn = {
            "wq": stack(keys[1], (d, nh * hd), d),
            "wk": stack(keys[2], (d, nkv * hd), d),
            "wv": stack(keys[3], (d, nkv * hd), d),
            "wo": stack(keys[4], (nh * hd, d), nh * hd),
        }
        if cfg.attn_gate:
            # the output gate, a channel of every head's output each
            attn["wg"] = stack(
                jax.random.fold_in(keys[1], 1), (d, nh * hd), d
            )
    return attn


def _init_layers(keys, cfg: ModelConfig, lead, routed: bool) -> Params:
    """One kind of layer, its tensors stacked on the leading axes
    ``lead`` (``()`` = one layer): attention, the two norms and either a
    dense MLP of ``d_ff`` or the routed block — never both."""
    d = cfg.d_model
    hd, nh, nkv = cfg.head_dim, cfg.n_head, cfg.kv_heads
    stack, ones = _stackers(cfg, lead)
    attn = _init_attention(keys, cfg, stack, ones)
    layers: Params = {
        "attn": attn,
        "ln1": {"scale": ones(d)},
        "ln2": {"scale": ones(d)},
    }
    if routed:
        layers["moe"] = moe.init_moe_params(keys[10], cfg, lead)
    else:
        layers["mlp"] = _init_mlp(keys, cfg, stack)
    if cfg.qk_norm:
        attn["q_norm"] = {"scale": ones(nh * hd)}
        attn["k_norm"] = {"scale": ones(nkv * hd)}
    if cfg.qk_head_norm:
        attn["q_norm"] = {"scale": ones(hd)}
        attn["k_norm"] = {"scale": ones(hd)}
    if cfg.post_norm:
        # the norms on the two parts' outputs
        layers["ln1_post"] = {"scale": ones(d)}
        layers["ln2_post"] = {"scale": ones(d)}
    if cfg.selects_keys:
        nj, nc = cfg.index_n_heads, cfg.index_head_dim
        ik = jax.random.split(keys[14], 3)
        layers["indexer"] = {
            "wq": stack(ik[0], (d, nj * nc), d),
            "wk": stack(ik[1], (d, nc), d),      # one key head
            "w": stack(ik[2], (d, nj), d),       # a weight per query head
            "k_norm": {"scale": ones(nc)},
        }
    if cfg.norm == "layernorm":
        layers["ln1"]["bias"] = jnp.zeros_like(layers["ln1"]["scale"])
        layers["ln2"]["bias"] = jnp.zeros_like(layers["ln2"]["scale"])
    return layers


def _pattern_runs(pattern: str):
    """``pattern`` cut into runs, in order: [(unit, repeats)], ``unit``
    a string of whole layers (``config.pattern_parts``). A run of
    repeats > 1 goes through ``lax.scan``: at each layer, the SHORTEST
    unit of layers that repeats at least once more at once, all its
    repeats; a layer that starts no such unit runs unrolled (repeats 1).
    A unit with a part that ``rides_out`` (``PARTS``: a routed part,
    whose jitter folds the layer's index in besides, or a block-sparse
    attention) never qualifies."""
    layers = pattern_parts(pattern)
    runs, i = [], 0
    while i < len(layers):
        unit, reps = layers[i:i + 1], 1
        for p in range(1, (len(layers) - i) // 2 + 1):
            cand = layers[i:i + p]
            if any(PARTS[c].rides_out for c in "".join(cand)):
                break
            n = 1
            while layers[i + n * p:i + (n + 1) * p] == cand:
                n += 1
            if n > 1:
                unit, reps = cand, n
                break
        runs.append(("".join(unit), reps))
        i += len(unit) * reps
    return runs


def _scanned_parts(pattern: str) -> int:
    """Parts of ``pattern`` that run inside a scan."""
    return sum(len(u) * n for u, n in _pattern_runs(pattern) if n > 1)


def _pattern_stacks(pattern: str):
    """The stacks ``pattern``'s parameters are kept in, [(name, letter,
    parts)]: each kind by itself under its name (``PARTS``), and a
    kind whose parts lie in several scanned runs, or in a run and
    outside it, a stack a stretch (``mlp``, ``mlp.1``, ``mlp.2``), so
    that a run scans WHOLE stacks. A slice of a stack handed to
    ``lax.scan`` is a copy of it, and the run's gradient a second buffer
    beside the stack's: 1.5 GB over the chip at Jamba2-3B's widths. A
    pattern with no run (``MEMEMEMEM*E``) keeps one stack a kind."""
    stretches = {}  # letter -> [[parts, scanned], ...] in trunk order
    for unit, reps in _pattern_runs(pattern):
        for letter in dict.fromkeys(unit):
            mine = stretches.setdefault(letter, [])
            n = unit.count(letter) * reps
            if reps == 1 and mine and not mine[-1][1]:
                mine[-1][0] += n  # parts outside any run, side by side
            else:
                mine.append([n, reps > 1])
    return [
        (PARTS[letter].stack + (f".{i}" if i else ""), letter, n)
        for letter in sorted(stretches)
        for i, (n, _) in enumerate(stretches[letter])
    ]


def _part_places(pattern: str):
    """letter -> [(stack name, index in it)], a part of the kind each,
    in trunk order."""
    places = {}
    for name, letter, n in _pattern_stacks(pattern):
        places.setdefault(letter, []).extend((name, i) for i in range(n))
    return places


def _init_mamba(key, cfg: ModelConfig, lead) -> Params:
    """A Mamba-2 mixer's parameters as published: ``A`` uniform in
    [1, 16] (kept as its log), the time step log-uniform in
    [time_step_min, time_step_max], floored, through the inverse
    softplus into ``dt_bias``, ``D`` = 1. Normal draws there would give
    decays no model has."""
    d, inner, heads = cfg.d_model, cfg.d_inner, cfg.mamba_num_heads
    taps, conv = cfg.conv_kernel, cfg.conv_dim
    stack, ones = _stackers(cfg, lead)
    pdt = jnp.dtype(cfg.param_dtype)
    lead = tuple(lead)
    k = jax.random.split(key, 6)
    bound = 1.0 / np.sqrt(taps)  # a depthwise tap sees ``taps`` inputs

    def uniform(key, shape):
        return jax.random.uniform(
            key, lead + shape, minval=-bound, maxval=bound
        ).astype(pdt)

    return {
        # [z | x | B | C | dt]
        "w_in": stack(k[0], (d, inner + conv + heads), d),
        "conv_w": uniform(k[3], (taps, conv)),
        "conv_b": uniform(k[4], (conv,)),
        "a_log": jnp.log(
            jax.random.uniform(k[1], lead + (heads,), minval=1.0, maxval=16.0)
        ).astype(pdt),
        "dt_bias": _time_step_bias(k[2], cfg, lead + (heads,)).astype(pdt),
        "d_skip": ones(heads),
        "norm": {"scale": ones(inner)},
        "w_out": stack(k[5], (inner, d), inner),
    }


def _time_step_bias(key, cfg: ModelConfig, shape):
    """float32: the time step log-uniform in [time_step_min,
    time_step_max], floored, through the inverse softplus (softplus of
    the bias IS the step): Mamba's own initialisation."""
    lo, hi = np.log(cfg.time_step_min), np.log(cfg.time_step_max)
    step = jnp.maximum(
        jnp.exp(jax.random.uniform(key, shape) * (hi - lo) + lo),
        cfg.time_step_floor,
    )
    return step + jnp.log(-jnp.expm1(-step))


def _init_mamba1(key, cfg: ModelConfig, lead) -> Params:
    """A Mamba-1 mixer's parameters as published: ``A`` = 1..N in every
    channel (kept as its log), Δ's bias as ``_time_step_bias``, Δ's
    projection uniform in ±rank^-1/2, ``D`` = 1. Normal draws there
    would give decays no model has."""
    d, inner, n = cfg.d_model, cfg.d_inner1, cfg.ssm_state_size
    rank, taps = cfg.mamba_dt_rank, cfg.conv_kernel
    stack, ones = _stackers(cfg, lead)
    pdt = jnp.dtype(cfg.param_dtype)
    lead = tuple(lead)
    k = jax.random.split(key, 7)

    def uniform(key, shape, bound):
        return jax.random.uniform(
            key, lead + shape, minval=-bound, maxval=bound
        ).astype(pdt)

    tap = 1.0 / np.sqrt(taps)  # a depthwise tap sees ``taps`` inputs
    return {
        "w_in": stack(k[0], (d, 2 * inner), d),           # [u | z]
        "conv_w": uniform(k[1], (taps, inner), tap),
        "conv_b": uniform(k[2], (inner,), tap),
        "w_x": stack(k[3], (inner, rank + 2 * n), inner),  # [r | B | C]
        "dt_norm": {"scale": ones(rank)},
        "b_norm": {"scale": ones(n)},
        "c_norm": {"scale": ones(n)},
        "w_dt": uniform(k[4], (rank, inner), rank ** -0.5),
        "dt_bias": _time_step_bias(k[5], cfg, lead + (inner,)).astype(pdt),
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
            lead + (inner, n),
        ).astype(pdt),
        "d_skip": ones(inner),
        "w_out": stack(k[6], (inner, d), inner),
    }


def _init_lightning(key, cfg: ModelConfig, lead) -> Params:
    """A lightning linear attention's parameters: q, k, v, the output
    gate and o at ``n_head`` heads of ``head_dim`` each, a scale of
    ``head_dim`` for each of the two per-head norms (q and k) and one a
    channel for the norm over the whole read-out. The decay is a
    constant (``config.lightning_log_decay``)."""
    d, wide, hd = cfg.d_model, cfg.n_head * cfg.head_dim, cfg.head_dim
    stack, ones = _stackers(cfg, lead)
    k = jax.random.split(key, 5)
    return {
        "wq": stack(k[0], (d, wide), d),
        "wk": stack(k[1], (d, wide), d),
        "wv": stack(k[2], (d, wide), d),
        "wg": stack(k[3], (d, wide), d),
        "wo": stack(k[4], (wide, d), wide),
        "q_norm": {"scale": ones(hd)},
        "k_norm": {"scale": ones(hd)},
        "o_norm": {"scale": ones(wide)},
    }


def _init_gdn(key, cfg: ModelConfig, lead) -> Params:
    """A gated-delta-rule mixer's parameters: ``w_qkvz`` [q | k | v | z]
    and ``w_ba`` [b | a] in blocks (the published checkpoint interleaves
    them a key head: a permutation of columns, the same function), the
    conv's taps over [q | k | v] (no bias), ``A`` uniform in [1, 16]
    (kept as its log), the time step's bias as ``_time_step_bias``, ONE
    output-norm scale of a value head's channels (ones: this norm is
    not zero-centred), and the output matrix."""
    d, taps = cfg.d_model, cfg.conv_kernel
    heads = cfg.gdn_value_heads
    inner = heads * cfg.gdn_value_dim
    stack, ones = _stackers(cfg, lead)
    pdt = jnp.dtype(cfg.param_dtype)
    lead = tuple(lead)
    k = jax.random.split(key, 6)
    bound = 1.0 / np.sqrt(taps)  # a depthwise tap sees ``taps`` inputs
    return {
        "w_qkvz": stack(k[0], (d, cfg.gdn_conv_dim + inner), d),
        "w_ba": stack(k[1], (d, 2 * heads), d),
        "conv_w": jax.random.uniform(
            k[2], lead + (taps, cfg.gdn_conv_dim), minval=-bound,
            maxval=bound,
        ).astype(pdt),
        "a_log": jnp.log(
            jax.random.uniform(k[3], lead + (heads,), minval=1.0, maxval=16.0)
        ).astype(pdt),
        "dt_bias": _time_step_bias(k[4], cfg, lead + (heads,)).astype(pdt),
        "norm": {"scale": ones(cfg.gdn_value_dim)},
        "w_out": stack(k[5], (inner, d), inner),
    }


def _init_kda(key, cfg: ModelConfig, lead) -> Params:
    """A KDA mixer's parameters: ``w_qkv`` [q | k | v] and ``w_gates``
    [f_a | g_a | b] (the decay's and the output gate's low-rank inputs
    and the write strength's, side by side: the published checkpoint
    holds a matrix each, a concatenation of columns is the same
    function), the conv's taps over [q | k | v] (no bias), the two
    low-rank outputs ``w_fb`` and ``w_gb``, ``A`` uniform in [1, 16] a
    head (kept as its log), the time step's bias a head and KEY CHANNEL
    as ``_time_step_bias``, ONE output-norm scale of a head's channels,
    and the output matrix."""
    d, taps, rank = cfg.d_model, cfg.conv_kernel, cfg.kda_gate_rank
    heads = cfg.kda_heads
    inner = heads * cfg.kda_head_dim
    stack, ones = _stackers(cfg, lead)
    pdt = jnp.dtype(cfg.param_dtype)
    lead = tuple(lead)
    k = jax.random.split(key, 8)
    bound = 1.0 / np.sqrt(taps)  # a depthwise tap sees ``taps`` inputs
    return {
        "w_qkv": stack(k[0], (d, 3 * inner), d),
        "w_gates": stack(k[1], (d, 2 * rank + heads), d),
        "conv_w": jax.random.uniform(
            k[2], lead + (taps, 3 * inner), minval=-bound, maxval=bound,
        ).astype(pdt),
        "w_fb": stack(k[3], (rank, inner), rank),
        "w_gb": stack(k[4], (rank, inner), rank),
        "a_log": jnp.log(
            jax.random.uniform(k[5], lead + (heads,), minval=1.0, maxval=16.0)
        ).astype(pdt),
        "dt_bias": _time_step_bias(k[6], cfg, lead + (inner,)).astype(pdt),
        "norm": {"scale": ones(cfg.kda_head_dim)},
        "w_out": stack(k[7], (inner, d), inner),
    }


def _init_conv(key, cfg: ModelConfig, lead) -> Params:
    """A gated short convolution's parameters: ``w_in`` [B | C | x] (the
    published ``in_proj``, d -> 3d), the conv's taps over d_model
    channels (no bias), and the output matrix."""
    d, taps = cfg.d_model, cfg.conv_kernel
    stack, _ = _stackers(cfg, lead)
    k = jax.random.split(key, 3)
    bound = 1.0 / np.sqrt(taps)  # a depthwise tap sees ``taps`` inputs
    return {
        "w_in": stack(k[0], (d, 3 * d), d),
        "conv_w": jax.random.uniform(
            k[1], tuple(lead) + (taps, d), minval=-bound, maxval=bound,
        ).astype(jnp.dtype(cfg.param_dtype)),
        "w_out": stack(k[2], (d, d), d),
    }


def _init_mlp(keys, cfg: ModelConfig, stack) -> Params:
    """The dense MLP's matrices: SwiGLU's three, or two."""
    d, f = cfg.d_model, cfg.d_ff
    mlp = {
        "w_up": stack(keys[6], (d, f), d),
        "w_down": stack(keys[7], (f, d), f),
    }
    if cfg.act == "swiglu":
        mlp["w_gate"] = stack(keys[5], (d, f), d)
    return mlp


def _init_attention_part(key, cfg: ModelConfig, lead) -> Params:
    """An attention part's matrices and its q and k norms a head."""
    keys = jax.random.split(key, 16)
    attn = _init_attention(keys, cfg, *_stackers(cfg, lead))
    if cfg.qk_head_norm:
        for which in ("q_norm", "k_norm"):
            attn[which] = {"scale": _norm_scale(cfg, *lead, cfg.head_dim)}
    return attn


def _init_pattern(key, cfg: ModelConfig, pattern: str) -> Params:
    """The parts ``pattern`` names, stacked kind by kind
    (``_pattern_stacks``): one norm and one part (``PARTS``) each."""
    return {
        name: {
            "ln": {"scale": _norm_scale(cfg, n, cfg.d_model)},
            PARTS[c].key: PARTS[c].init(jax.random.fold_in(key, i), cfg, (n,)),
        }
        for i, (name, c, n) in enumerate(_pattern_stacks(pattern))
    }


def init(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Initialise parameters; per-layer tensors stacked on axis 0, each
    kind of layer by itself: ``layers`` (every layer of a model of one
    kind; the routed layers of a model with a dense prefix) and
    ``dense_layers`` (that prefix). A routed layer has no dense ``mlp``.
    A ``layer_pattern`` model's ``layers`` (and its module's ``block``)
    hold one stack a kind of part (``_init_pattern``)."""
    pdt = jnp.dtype(cfg.param_dtype)
    d, v = cfg.d_model, cfg.vocab_size
    keys = jax.random.split(rng, 16)
    n_dense = cfg.n_dense_layer
    params: Params = {
        "embed": {
            "tokens": (
                jax.random.normal(keys[0], (v, d)) * cfg.embed_init_std
            ).astype(pdt)
        },
        "layers": (
            _init_pattern(keys[15], cfg, cfg.layer_pattern)
            if cfg.layer_pattern
            else _init_layers(
                keys, cfg, (cfg.n_layer - n_dense,), routed=cfg.n_experts > 0
            )
        ),
        "final_norm": {"scale": _norm_scale(cfg, d)},
    }
    if n_dense:
        params["dense_layers"] = _init_layers(
            jax.random.split(keys[12], 16), cfg, (n_dense,), routed=False
        )
    if cfg.norm == "layernorm":
        params["final_norm"]["bias"] = jnp.zeros((d,), pdt)
    if cfg.pos == "learned":
        params["pos_embed"] = {
            "table": (
                jax.random.normal(keys[8], (cfg.max_seq, d)) * 0.01
            ).astype(pdt)
        }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": _dense_init(keys[9], (d, v), d, pdt)}
    if cfg.n_mtp_module:
        mk = jax.random.split(keys[13], 17)
        norm = {"scale": jnp.ones((d,), pdt)}
        if cfg.norm == "layernorm":
            norm["bias"] = jnp.zeros((d,), pdt)
        params["mtp"] = {
            "enorm": dict(norm),
            "hnorm": dict(norm),
            # [norm(emb(t_{i+1})) ‖ norm(h_i)] -> d
            "eh_proj": _dense_init(mk[16], (2 * d, d), 2 * d, pdt),
            "block": (
                _init_pattern(mk[15], cfg, cfg.mtp_pattern)
                if cfg.layer_pattern
                else _init_layers(mk, cfg, (), routed=cfg.n_experts > 0)
            ),
            "norm": dict(norm),
        }
    return params


def _attention_axes(cfg: ModelConfig, lead) -> Params:
    """Logical axes of an attention's matrices and its q and k norms."""
    if cfg.latent_attention:
        q_mats = {
            "wq_a": lead + ("embed", None),
            "q_a_norm": {"scale": lead + ("norm",)},
            "wq_b": lead + (None, "heads"),
        } if cfg.q_lora_rank else {"wq": lead + ("embed", "heads")}
        attn = {
            **q_mats,
            "wkv_a": lead + ("embed", None),
            "kv_a_norm": {"scale": lead + ("norm",)},
            "wkv_b": lead + (None, "heads"),
            "wo": lead + ("heads", "embed"),
        }
    else:
        attn = {
            "wq": lead + ("embed", "heads"),
            "wk": lead + ("embed", "kv"),
            "wv": lead + ("embed", "kv"),
            "wo": lead + ("heads", "embed"),
        }
        if cfg.attn_gate:
            attn["wg"] = lead + ("embed", "heads")
    if cfg.qk_norm or cfg.qk_head_norm:
        attn["q_norm"] = {"scale": lead + ("norm",)}
        attn["k_norm"] = {"scale": lead + ("norm",)}
    return attn


def _mlp_axes(cfg: ModelConfig, lead) -> Params:
    """Logical axes of ``_init_mlp``'s matrices."""
    ax = {
        "w_up": lead + ("embed", "mlp"),
        "w_down": lead + ("mlp", "embed"),
    }
    if cfg.act == "swiglu":
        ax["w_gate"] = lead + ("embed", "mlp")
    return ax


def _mamba_axes(cfg: ModelConfig, lead) -> Params:
    """Logical axes of ``_init_mamba``'s tree."""
    return {
        "w_in": lead + ("embed", "mlp"),
        "conv_w": lead + (None, "mlp"),
        "conv_b": lead + ("mlp",),
        **{k: lead + (None,) for k in ("a_log", "dt_bias", "d_skip")},
        "norm": {"scale": lead + ("norm",)},
        "w_out": lead + ("mlp", "embed"),
    }


def _mamba1_axes(cfg: ModelConfig, lead) -> Params:
    """Logical axes of ``_init_mamba1``'s tree."""
    return {
        "w_in": lead + ("embed", "mlp"),
        "conv_w": lead + (None, "mlp"),
        "conv_b": lead + ("mlp",),
        "w_x": lead + ("mlp", None),
        "dt_norm": {"scale": lead + ("norm",)},
        "b_norm": {"scale": lead + ("norm",)},
        "c_norm": {"scale": lead + ("norm",)},
        "w_dt": lead + (None, "mlp"),
        "dt_bias": lead + ("mlp",),
        "a_log": lead + ("mlp", None),
        "d_skip": lead + ("mlp",),
        "w_out": lead + ("mlp", "embed"),
    }


def _lightning_axes(cfg: ModelConfig, lead) -> Params:
    """Logical axes of ``_init_lightning``'s tree."""
    return {
        **{w: lead + ("embed", "heads") for w in ("wq", "wk", "wv", "wg")},
        "wo": lead + ("heads", "embed"),
        **{
            n: {"scale": lead + ("norm",)}
            for n in ("q_norm", "k_norm", "o_norm")
        },
    }


def _gdn_axes(cfg: ModelConfig, lead) -> Params:
    """Logical axes of ``_init_gdn``'s tree."""
    return {
        "w_qkvz": lead + ("embed", "mlp"),
        "w_ba": lead + ("embed", None),
        "conv_w": lead + (None, "mlp"),
        "a_log": lead + (None,),
        "dt_bias": lead + (None,),
        "norm": {"scale": lead + ("norm",)},
        "w_out": lead + ("mlp", "embed"),
    }


def _kda_axes(cfg: ModelConfig, lead) -> Params:
    """Logical axes of ``_init_kda``'s tree."""
    return {
        "w_qkv": lead + ("embed", "mlp"),
        "w_gates": lead + ("embed", None),
        "conv_w": lead + (None, "mlp"),
        "w_fb": lead + (None, "mlp"),
        "w_gb": lead + (None, "mlp"),
        "a_log": lead + (None,),
        "dt_bias": lead + ("mlp",),
        "norm": {"scale": lead + ("norm",)},
        "w_out": lead + ("mlp", "embed"),
    }


def _conv_axes(cfg: ModelConfig, lead) -> Params:
    """Logical axes of ``_init_conv``'s tree."""
    return {
        "w_in": lead + ("embed", "mlp"),
        "conv_w": lead + (None, "mlp"),
        "w_out": lead + ("mlp", "embed"),
    }


@dataclasses.dataclass(frozen=True)
class PartKind:
    """One kind of ``layer_pattern`` part: all the trunk knows of a
    letter (what ``ModelConfig`` asks of it: ``config.PART_RULES``; what
    it counts: ``ModelConfig._part_counts``)."""

    stack: str  # the name its stack of parts goes by in ``layers``
    key: str  # its subtree of a layer, beside the norm's ``ln``
    init: Callable  # (key, cfg, lead) -> the subtree, stacked on ``lead``
    axes: Callable  # (cfg, lead) -> the subtree's logical axes
    # (h, the layer, c: what ``_part_body`` was given) -> (out, aux). It
    # NAMES its block: one patched on the module (a planted defect) runs
    run: Callable
    scope: Optional[str] = None  # what it is traced under, where not ``key``
    read: Optional[str] = None  # the number in its aux the trunk reports
    # (cfg, mesh, n: the trunk's parts of the kind, s: the tokens of a
    # sequence) -> {counter: value}
    counters: Optional[Callable] = None
    rides_out: bool = False  # its aux rides out part by part: never scanned
    wants_rope: bool = False  # turns q and k by the trunk's rope tables
    selects: bool = False  # its aux is a selection (``attn_selected``)


def _rule_layers(name: str, n: int, mesh, *dims, **how):
    """A delta-rule kind's layers and, all or none, its kernels' layers:
    the rule's, and those whose ``_l2_heads`` norms q and k by the
    kernel."""
    kernels = gated_delta.in_kernels(*dims, mesh=mesh, **how)
    return {
        f"{name}.layers": n, f"{name}.kernel_layers": n * int(kernels),
        # (``dims`` start with the key channels of a head)
        f"{name}.norm_kernel_layers": n * int(_l2_in_kernel(dims[0])),
    }


_ROUTED = PartKind(
    stack="experts", key="moe", scope="mlp",
    init=moe.init_moe_params, axes=moe.moe_logical_axes,
    run=lambda h, p, c: moe.moe_block(
        h, p["moe"], c.cfg, c.mesh, rng=c.rng, return_aux=True
    ),
    rides_out=True,
)
# a ``layer_pattern`` letter -> its kind, in the order the counters are
# set and the reads handed out; the routed experts are a layer by
# themselves or (e) its second part
PARTS = {
    "M": PartKind(
        stack="mamba", key="ssm", init=_init_mamba, axes=_mamba_axes,
        run=lambda h, p, c: (_mamba_block(h, p["ssm"], c.cfg, c.mesh), {}),
    ),
    "m": PartKind(
        stack="mamba1", key="ssm1", init=_init_mamba1, axes=_mamba1_axes,
        run=lambda h, p, c: (_mamba1_block(h, p["ssm1"], c.cfg, c.mesh), {}),
        counters=lambda cfg, mesh, n, s: {"ssm1.layers": n},
    ),
    "C": PartKind(
        stack="conv", key="conv", init=_init_conv, axes=_conv_axes,
        run=lambda h, p, c: (
            _gated_conv_block(h, p["conv"], c.cfg, c.mesh), {}
        ),
        # a trunk whose conv fell back to the XLA body says so
        counters=lambda cfg, mesh, n, s: {
            "conv.layers": n,
            "conv.kernel_layers": n * int(ssd.gated_conv_in_kernel(
                s, cfg.conv_kernel, cfg.d_model, cfg.dtype, mesh
            ) is not None),
        },
    ),
    "*": PartKind(
        stack="attention", key="attn",
        init=_init_attention_part, axes=_attention_axes,
        run=lambda h, p, c: (_attention_block(
            h, p, c.cfg, c.mesh, c.positions, c.attn_fn, rope=c.rope
        ), {}),
        wants_rope=True,
    ),
    "L": PartKind(
        stack="lightning", key="lin",
        init=_init_lightning, axes=_lightning_axes,
        run=lambda h, p, c: _lightning_block(h, p["lin"], c.cfg, c.mesh, c.rope),
        read="lightning_fast_out_ms", wants_rope=True,
        counters=lambda cfg, mesh, n, s: {"lin.layers": n},
    ),
    "G": PartKind(
        stack="gdn", key="gdn", init=_init_gdn, axes=_gdn_axes,
        run=lambda h, p, c: _gdn_block(h, p["gdn"], c.cfg, c.mesh),
        read="gdn_readout_ms",
        counters=lambda cfg, mesh, n, s: _rule_layers(
            "gdn", n, mesh, cfg.gdn_key_dim, cfg.gdn_value_dim
        ),
    ),
    "K": PartKind(
        stack="kda", key="kda", init=_init_kda, axes=_kda_axes,
        run=lambda h, p, c: _kda_block(h, p["kda"], c.cfg, c.mesh),
        read="kda_readout_ms",
        counters=lambda cfg, mesh, n, s: _rule_layers(
            "kda", n, mesh, cfg.kda_head_dim, cfg.kda_head_dim,
            per_channel=True,
        ),
    ),
    "S": PartKind(
        stack="sparse", key="attn",
        init=_init_attention_part, axes=_attention_axes,
        run=lambda h, p, c: _block_sparse_attention(
            h, p, c.cfg, c.mesh, c.positions, c.attn_fn, c.return_selected
        ),
        rides_out=True, selects=True,
        counters=lambda cfg, mesh, n, s: {
            "attn.sparse_layers": n, "attn.select_block": cfg.sparse_block,
            "attn.select_groups": cfg.kv_heads,
        },
    ),
    "E": _ROUTED,
    "e": _ROUTED,
    "-": PartKind(
        stack="mlp", key="mlp",
        init=lambda key, cfg, lead: _init_mlp(
            jax.random.split(key, 16), cfg, _stackers(cfg, lead)[0]
        ),
        axes=_mlp_axes,
        run=lambda h, p, c: (
            _mlp_block(h, p, c.cfg, c.mesh, interior=jnp.float32), {}
        ),
    ),
}


def _pattern_axes(cfg: ModelConfig, pattern: str, lead) -> Params:
    """Logical axes of ``_init_pattern``'s tree."""
    lead = tuple(lead)
    return {
        name: {
            "ln": {"scale": lead + ("norm",)},
            PARTS[c].key: PARTS[c].axes(cfg, lead),
        }
        for name, c, _ in _pattern_stacks(pattern)
    }


def _layer_axes(cfg: ModelConfig, lead, routed: bool) -> Params:
    """Logical axes of ``_init_layers``' tree."""
    lead = tuple(lead)
    attn = _attention_axes(cfg, lead)
    ax: Params = {
        "attn": attn,
        "ln1": {"scale": lead + ("norm",)},
        "ln2": {"scale": lead + ("norm",)},
    }
    if routed:
        ax["moe"] = moe.moe_logical_axes(cfg, lead)
    else:
        ax["mlp"] = _mlp_axes(cfg, lead)
    if cfg.post_norm:
        ax["ln1_post"] = {"scale": lead + ("norm",)}
        ax["ln2_post"] = {"scale": lead + ("norm",)}
    if cfg.selects_keys:
        ax["indexer"] = {
            "wq": lead + ("embed", None),
            "wk": lead + ("embed", None),
            "w": lead + ("embed", None),
            "k_norm": {"scale": lead + ("norm",)},
        }
    if cfg.norm == "layernorm":
        ax["ln1"]["bias"] = lead + ("norm",)
        ax["ln2"]["bias"] = lead + ("norm",)
    return ax


def logical_axes(cfg: ModelConfig) -> Params:
    """Pytree of logical-axis tuples, same structure as ``init``'s output."""
    routed = cfg.n_experts > 0
    ax: Params = {
        "embed": {"tokens": ("vocab", "embed")},
        "layers": (
            _pattern_axes(cfg, cfg.layer_pattern, ("layers",))
            if cfg.layer_pattern
            else _layer_axes(cfg, ("layers",), routed)
        ),
        "final_norm": {"scale": ("norm",)},
    }
    if cfg.n_dense_layer:
        ax["dense_layers"] = _layer_axes(cfg, ("layers",), routed=False)
    if cfg.norm == "layernorm":
        ax["final_norm"]["bias"] = ("norm",)
    if cfg.pos == "learned":
        ax["pos_embed"] = {"table": ("seq", "embed")}
    if not cfg.tie_embeddings:
        ax["lm_head"] = {"w": ("embed", "vocab")}
    if cfg.n_mtp_module:
        norm = {"scale": ("norm",)}
        if cfg.norm == "layernorm":
            norm["bias"] = ("norm",)
        ax["mtp"] = {
            "enorm": dict(norm),
            "hnorm": dict(norm),
            "eh_proj": (None, "embed"),
            "block": (
                _pattern_axes(cfg, cfg.mtp_pattern, ("layers",))
                if cfg.layer_pattern
                else _layer_axes(cfg, (), routed)
            ),
            "norm": dict(norm),
        }
    return ax


def _embed_lookup_hostile(mesh, table_shape, tokens_shape) -> bool:
    """True when XLA's gather cannot be trusted on this mesh.

    The table rests ZeRO-sharded ("vocab"→tp, "embed"→fsdp). When fsdp>1
    the gather's output inherits the fsdp-sharded embed dim, which cannot
    be cheaply resharded to the batch-sharded activation layout (fsdp on
    dim 2 vs dp·fsdp on dim 0 is a transposed device order) — the SPMD
    partitioner falls back to "involuntary full rematerialization", a
    replicate-then-repartition of a [B,S,D] tensor every microbatch.
    Constraint-based fixes are off the table: a sharding constraint on
    the table inside the grad-accumulation scan miscompiles the
    cotangent scatter on this XLA version (accumulated embed grads come
    back wrong), and out-of-scan anchors lose to propagation from the
    optimizer side. Manual sharding (shard_map) is the reliable path.
    Skipped inside partial-manual regions (the pipeline's pp shard_map):
    those meshes pipeline with fsdp=1 in practice and the nested-mesh
    bookkeeping isn't worth it.
    """
    if mesh is None or mesh.shape.get("fsdp", 1) <= 1:
        return False
    # shard_map needs exact divisibility where GSPMD would pad; the
    # fallback take is correct (just reshard-slow) for ragged shapes
    vocab, _ = table_shape
    b, s = tokens_shape
    if (
        vocab % mesh.shape.get("tp", 1)
        or b % (mesh.shape.get("dp", 1) * mesh.shape["fsdp"])
        or s % mesh.shape.get("sp", 1)
    ):
        return False
    return not shd.manual_axis_names()


def _vocab_parallel_embed(table: jax.Array, tokens: jax.Array, mesh):
    """Megatron-style vocab-parallel embedding lookup under shard_map.

    Each tp shard holds a contiguous vocab slice (the resting "vocab"→tp
    sharding); out-of-shard tokens are masked to zero and one psum over
    tp assembles the rows — the same masked-gather + all-reduce XLA
    synthesizes for a vocab-sharded gather, but with every collective
    explicit so the partitioner has no resharding decisions to make (and
    none to get wrong; see _embed_lookup_hostile). The in_spec
    P("tp", None) is the ZeRO gather-on-use: shard_map all-gathers the
    table's fsdp-sharded embed dim at entry, and the transpose psums the
    table cotangent back over (dp, fsdp, sp) before re-slicing — both on
    table-sized tensors, never on [B,S,D] activations.

    Reference parity: atorch's VocabParallelEmbedding
    (atorch/modules/distributed_modules/layers.py) does the same
    masked-lookup + all-reduce with torch collectives.
    """
    def body(rank, tbl, tok):
        vs = tbl.shape[0]
        # tp rank from a tp-sharded iota input, not lax.axis_index:
        # partial-manual shard_map on jax 0.4.x lowers axis_index to a
        # PartitionId the SPMD partitioner rejects
        off = rank[0] * vs
        idx = tok - off
        inb = (idx >= 0) & (idx < vs)
        x = jnp.take(tbl, jnp.where(inb, idx, 0), axis=0)
        x = jnp.where(inb[..., None], x, jnp.zeros([], x.dtype))
        return jax.lax.psum(x, "tp")

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("tp"), P("tp", None), P(("dp", "fsdp"), "sp")),
        out_specs=P(("dp", "fsdp"), "sp", None),
        check_vma=False,
    )(jnp.arange(mesh.shape["tp"], dtype=jnp.int32), table, tokens)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _embed_tokens(params: Params, tokens, mesh, dt):
    """Rows of the token table for ``tokens`` [B, S]."""
    table = params["embed"]["tokens"]
    if _embed_lookup_hostile(mesh, table.shape, tokens.shape):
        return _vocab_parallel_embed(table, tokens, mesh).astype(dt)
    return jnp.take(table, tokens, axis=0).astype(dt)


def _norm(x, scale, bias, kind: str, eps=None):
    """``eps``: ``cfg.norm_eps``; None = the program's own (1e-6
    RMSNorm, 1e-5 LayerNorm: ``pallas_norm.RMS_EPS`` / ``LN_EPS``)."""
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        rms = jax.lax.rsqrt(
            jnp.mean(x32 * x32, -1, keepdims=True) + (eps or 1e-6)
        )
        out = x32 * rms * scale.astype(jnp.float32)
    else:
        # single pass over the f32 upcast: E[x] and E[x²] share one
        # reduction sweep (jnp.var would re-read the activations);
        # var clamped at 0 against catastrophic cancellation
        mean = jnp.mean(x32, -1, keepdims=True)
        ex2 = jnp.mean(x32 * x32, -1, keepdims=True)
        var = jnp.maximum(ex2 - mean * mean, 0.0)
        out = (x32 - mean) * jax.lax.rsqrt(var + (eps or 1e-5))
        out = out * scale.astype(jnp.float32)
        if bias is not None:
            out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def _fused_norm_enabled(cfg: ModelConfig) -> bool:
    if cfg.fused_norm is not None:
        return cfg.fused_norm
    from dlrover_tpu.accelerate.device_context import kernel_capabilities

    return kernel_capabilities().fused_norm


def _norm_block(x, ln, cfg: ModelConfig, residual=None):
    """The layer-body norm: Pallas fused kernel when enabled
    (``cfg.fused_norm``; auto = TPU/interpret only), jnp ``_norm``
    otherwise — the fallback keeps untouched configs on the exact
    prior program. With ``residual``, returns
    ``(norm(x + residual), x + residual)`` — on the kernel path the
    summed stream comes out of the same HBM visit."""
    scale = _multiplier(ln["scale"], cfg)
    if _fused_norm_enabled(cfg):
        return pallas_norm.norm(
            x, scale, ln.get("bias"), cfg.norm, residual=residual,
            eps=cfg.norm_eps,
        )
    if residual is not None:
        h = x + residual
        return _norm(h, scale, ln.get("bias"), cfg.norm, cfg.norm_eps), h
    return _norm(x, scale, ln.get("bias"), cfg.norm, cfg.norm_eps)


def _multiplier(scale, cfg: ModelConfig):
    """What a trunk norm multiplies by: its learned vector, or 1 + it
    in float32 where the model's norms are zero-centred."""
    if not cfg.norm_zero_centered:
        return scale
    return 1.0 + scale.astype(jnp.float32)


def _rope_frequencies(head_dim: int, theta: float, scaling=None):
    """A head's rope frequencies [D/2] f32, pair i at theta^(-2i/D);
    under ``scaling`` (``cfg.rope_scaling``: factor, original length,
    beta_fast, beta_slow, amplitude) YaRN's blend, the ``transformers``
    reading with ``truncate``: the pair whose wavelength fits beta times
    into the original length is c(beta) = D ln(L / (2 pi beta)) /
    (2 ln theta); pairs up to low = floor(c(beta_fast)) keep their
    frequency, pairs from high = ceil(c(beta_slow)) turn ``factor``
    times slower, those between are blended linearly by index."""
    freqs = theta ** (
        -jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    )
    if scaling is None:
        return freqs
    factor, original, beta_fast, beta_slow, _ = scaling

    def pair(beta):
        return head_dim * math.log(original / (2 * math.pi * beta)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), head_dim - 1)
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
        / max(high - low, 0.001),
        0.0, 1.0,
    )
    return freqs * (1.0 - ramp) + freqs / factor * ramp


def _rope_tables(positions: jax.Array, head_dim: int, theta: float,
                 scaling=None):
    """cos/sin rope tables [B,S,1,D/2] f32 from positions [B,S] —
    computed ONCE per forward (run_trunk / prefill / decode_step), one
    a rope kind the model's layers use, and threaded to the layers;
    rebuilding them per layer costs a transcendental sweep per call
    that XLA does not hoist out of the scan body. ``scaling``: the
    scaled table (``_rope_frequencies``), cos and sin times its
    amplitude, so a score of q and k both turned carries its square."""
    with jax.named_scope("attn.rope"):
        freqs = _rope_frequencies(head_dim, theta, scaling)
        angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,D/2]

        def table(wave):
            t = wave(angles)
            if scaling is not None:
                t = t * scaling[4]
            return t[:, :, None, :]

        return table(jnp.cos), table(jnp.sin)


def _rope(x: jax.Array, rope) -> jax.Array:
    """Apply rotary embedding. x:[B,S,H,D], rope: (cos, sin) tables
    from ``_rope_tables``, of D channels or of a head's first few
    (``cfg.rope_dim``). Rotate-half via strided reshape — the f32
    view [..., 2, D/2] pairs lane i with i+D/2 exactly like the old
    split+concatenate, without materializing two half-width
    temporaries, and is bitwise-identical to it (pinned in
    tests/test_model.py)."""
    d = x.shape[-1]
    cos, sin = rope
    turned = 2 * cos.shape[-1]
    if turned < d:
        # a partial rotary factor: the first channels of a head are
        # turned (rotate-half inside them), the rest pass as they are
        return jnp.concatenate(
            [_rope(x[..., :turned], rope), x[..., turned:]], axis=-1
        )
    xr = x.astype(jnp.float32).reshape(x.shape[:-1] + (2, d // 2))
    x1, x2 = xr[..., 0, :], xr[..., 1, :]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-2)
    return out.reshape(x.shape).astype(x.dtype)


def _fp8_gemm(x, w, fp8, name):
    """One fp8 GEMM: delayed scaling against the per-projection state,
    or stateless current scaling when ``fp8`` is the "current" sentinel
    (pipeline meshes — see run_trunk)."""
    from dlrover_tpu.ops.fp8 import fp8_dot, fp8_dot_current

    if fp8 == "current":
        return fp8_dot_current(x, w)
    return fp8_dot(x, w, fp8[name])


def _project_qkv(
    x,
    layer,
    cfg: ModelConfig,
    positions,
    *,
    mup_full_scale: bool = False,
    fp8=None,
    rope=None,
):
    """QKV projection + rope + muP q-scaling — the ONE place this math
    lives; the batch forward (_attention_block), prefill and decode_step
    all call it so they cannot drift apart.

    muP wants 1/d_head TOTAL attention scaling. The batch path's attn
    impls apply 1/sqrt(d_head) themselves, so q carries the other half;
    the cache paths run their attention with scale=1 and set
    ``mup_full_scale`` so q carries all of it.

    ``fp8``: per-layer delayed-scaling states for the q/k/v GEMMs
    (keys "wq"/"wk"/"wv"; cfg.fp8 training only — the cache paths pass
    None and stay bf16).

    ``rope``: this layer's precomputed (cos, sin) tables from
    ``_rope_tables`` — the trunk/prefill/decode loops build them once,
    one a rope kind, and hand each layer its own; None builds the table
    of the model's one kind here (external callers, pp bodies); False:
    this layer has no rope (a kind of ``cfg.layer_types`` without one).

    ``cfg.qk_norm``: q and k are normed over their WHOLE projection
    (all heads at once; ``attn.q_norm`` / ``attn.k_norm``, scale only)
    before the head split, the remat tags and rope, whichever GEMM made
    them. The statistic spans the heads axis, which ``tp`` shards:
    ``forward`` refuses such a mesh rather than hand the norm kernel
    one shard of the heads. ``cfg.qk_head_norm``: an RMSNorm over each
    head's channels instead, after the split."""
    b, s, _ = x.shape
    nh, nkv, hd = cfg.n_head, cfg.kv_heads, cfg.head_dim
    attn = layer["attn"]
    if fp8 is not None:
        q = _fp8_gemm(x, attn["wq"].astype(x.dtype), fp8, "wq")
        k = _fp8_gemm(x, attn["wk"].astype(x.dtype), fp8, "wk")
        v = _fp8_gemm(x, attn["wv"].astype(x.dtype), fp8, "wv")
    else:
        q = x @ attn["wq"].astype(x.dtype)
        k = x @ attn["wk"].astype(x.dtype)
        v = x @ attn["wv"].astype(x.dtype)
    if cfg.qk_norm:
        q = _norm_block(q, attn["q_norm"], cfg)
        k = _norm_block(k, attn["k_norm"], cfg)
    q = q.reshape(b, s, nh, hd)
    k = k.reshape(b, s, nkv, hd)
    v = v.reshape(b, s, nkv, hd)
    if cfg.qk_head_norm:
        # RMSNorm per head, one scale of head_dim for all heads; in jnp,
        # so that it fuses with rope's pass over the same values
        q, k = (
            _norm(
                t, _multiplier(attn[name]["scale"], cfg), None, "rmsnorm",
                cfg.norm_eps,
            )
            for t, name in ((q, "q_norm"), (k, "k_norm"))
        )
    if cfg.pos == "rope" and rope is not False:
        if rope is None:
            rope = _rope_tables(
                positions, cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling
            )
        with jax.named_scope("attn.rope"):
            q = _rope(q, rope)
            k = _rope(k, rope)
    if cfg.mup_base_width:
        q = q * (hd ** (-1.0 if mup_full_scale else -0.5))
    return q, k, v


def _cache_layer_tail(x, attn_out, layer, cfg: ModelConfig):
    """Residual + MLP/MoE wiring shared by prefill and decode_step
    (mirrors _layer_body minus mesh constraints, aux and rng)."""
    ln2 = layer["ln2"]
    if cfg.parallel_residual:
        h2 = _norm(x, ln2["scale"], ln2.get("bias"), cfg.norm, cfg.norm_eps)
    else:
        x = x + attn_out
        h2 = _norm(x, ln2["scale"], ln2.get("bias"), cfg.norm, cfg.norm_eps)
    if cfg.n_experts > 0:
        mlp_out = moe.moe_block(h2, layer["moe"], cfg, None)
    else:
        mlp_out = _mlp_block(h2, layer, cfg, None)
    return x + attn_out + mlp_out if cfg.parallel_residual else x + mlp_out


def _latent_qkv(x, attn, cfg: ModelConfig, positions, rope=None):
    """Latent attention's q, k, v in the EXPANDED form: q and k
    [B, S, H, D] with D = qk_nope + qk_rope, v [B, S, H, v_head_dim]
    (as wide as D, or narrower), so that what follows is MHA through
    the flash kernels:

        c_q = norm(x W_dq);  q_h = c_q W_uq  -> [q_nope,h ‖ q_rope,h]
                (``q_lora_rank`` 0: q_h = x W_q, one matrix, no norm)
        [c_kv ‖ k_r] = x W_dkv;  c_kv = norm(c_kv)
        [k_nope,h ‖ v_h] = c_kv W_ukv
        q_h = [q_nope,h ‖ rope(q_rope,h)];  k_h = [k_nope,h ‖ rope(k_r)]
                (``pos`` none: neither is turned, ``mla_use_nope``)

    ``k_r`` is one set of rope channels that every head shares. The
    up-projection's weight is cut into its k and v columns (4.6 M
    parameters) rather than its output (tokens × H × 448 activations).
    Everything here is the scope ``attn.latent``: what of the block is
    neither a flash call nor W_o. The weight-absorbed form, which never
    expands k and v, belongs to a latent cache and is not built."""
    b, s, _ = x.shape
    nh, rkv = cfg.n_head, cfg.kv_lora_rank
    nope, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = x.dtype
    with jax.named_scope("attn.latent"):
        if cfg.q_lora_rank:
            c_q = _norm_block(
                x @ attn["wq_a"].astype(dt), attn["q_a_norm"], cfg
            )
            q = c_q @ attn["wq_b"].astype(dt)
        else:
            q = x @ attn["wq"].astype(dt)
        q = q.reshape(b, s, nh, nope + rd)
        kv = x @ attn["wkv_a"].astype(dt)
        c_kv = _norm_block(kv[..., :rkv], attn["kv_a_norm"], cfg)
        k_r = kv[..., rkv:].reshape(b, s, 1, rd)
        w_kv = attn["wkv_b"].astype(dt).reshape(rkv, nh, nope + vd)
        k_nope = jnp.einsum("bsr,rhc->bshc", c_kv, w_kv[..., :nope])
        v = jnp.einsum("bsr,rhc->bshc", c_kv, w_kv[..., nope:])
        if cfg.pos == "rope":
            if rope is None:
                rope = _rope_tables(positions, rd, cfg.rope_theta)
            q = jnp.concatenate(
                [q[..., :nope], _rope(q[..., nope:], rope)], axis=-1
            )
            k_r = _rope(k_r, rope)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r, (b, s, nh, rd))], axis=-1
        )
    return q, k, v


def _constrain_qkv(q, k, v, mesh):
    """q, k, v [B, S, heads, D] pinned to the mesh's batch, sequence and
    head axes (as they are without a mesh)."""
    if mesh is None:
        return q, k, v
    return (
        shd.constrain(q, mesh, "batch", "seq", "heads", None),
        shd.constrain(k, mesh, "batch", "seq", "kv", None),
        shd.constrain(v, mesh, "batch", "seq", "kv", None),
    )


def _gate_output(out, x, w_gate):
    """``out * sigmoid(x W_g)``, a gate a channel of the attention's
    output [B, S, H·D] from the layer's normed input ``x``: the sigmoid
    and the multiply in float32, one rounding to the compute dtype."""
    with jax.named_scope("attn.gate"):
        gate = x @ w_gate.astype(x.dtype)
        return (
            out * jax.nn.sigmoid(gate.astype(jnp.float32))
        ).astype(x.dtype)


def _attention_block(
    x, layer, cfg: ModelConfig, mesh, positions, attn_fn, fp8=None,
    rope=None,
):
    b, s, d = x.shape
    nh, hd, vd = cfg.n_head, cfg.head_dim, cfg.value_dim
    if cfg.latent_attention:
        q, k, v = _latent_qkv(x, layer["attn"], cfg, positions, rope=rope)
    else:
        q, k, v = _project_qkv(x, layer, cfg, positions, fp8=fp8, rope=rope)
    if vd < hd:
        # values narrower than the scores (latent attention at 192 / 128):
        # the kernels have one width for q, k and v, so v is padded with
        # zeros to the scores' and the output cut again. Exact: a zero
        # channel of v is a zero channel of p v, and of dv
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, hd - vd),))
    q, k, v = _constrain_qkv(q, k, v, mesh)
    out = attn_fn(q, k, v)
    if vd < hd:
        out = out[..., :vd]
    out = out.reshape(b, s, nh * vd)
    if cfg.attn_gate:
        out = _gate_output(out, x, layer["attn"]["wg"])
    if fp8 is not None:
        return _fp8_gemm(out, layer["attn"]["wo"].astype(x.dtype), fp8, "wo")
    return out @ layer["attn"]["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# A learned selection of keys (DeepSeek-Sparse-Attention)
# ---------------------------------------------------------------------------


def _index_inputs(h, idx, cfg: ModelConfig, positions):
    """The indexer's operands from the layer's normed input ``h``
    [B, S, D]: qI [B, S, J, C] and kI [B, S, C] (one key head, RMSNorm
    before rope; rope over all C channels) in the compute dtype, and the
    head weights w [B, S, J] float32, scaled by (J·C)^-1/2."""
    b, s, _ = h.shape
    nj, nc = cfg.index_n_heads, cfg.index_head_dim
    dt = h.dtype
    rope = _rope_tables(positions, nc, cfg.rope_theta)
    qi = _rope((h @ idx["wq"].astype(dt)).reshape(b, s, nj, nc), rope)
    ki = _norm(
        h @ idx["wk"].astype(dt), idx["k_norm"]["scale"], None, "rmsnorm"
    )
    ki = _rope(ki[:, :, None, :], rope)[:, :, 0]
    w = jnp.matmul(
        h, idx["w"].astype(dt), preferred_element_type=jnp.float32
    ) * (nj * nc) ** -0.5
    return qi, ki, w


def _f32_dot(spec, a, b):
    """einsum on the operands as they are, summed in float32 (XLA:CPU
    runs no bf16 x bf16 = f32 dot behind a ``name`` barrier: see
    ``mha_reference``)."""
    if device.on_cpu():
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _index_scores(qi, ki, w):
    """I_ts = sum_j w_tj relu(qI_tj . kI_s): qi [B, Q, J, C], ki
    [B, Sk, C], w [B, Q, J] -> float32 [B, Q, Sk]. The products run on
    the operands' dtype and sum in float32; the ReLU and the weighting
    are float32 and elementwise (no second pass over the MXU)."""
    dots = jax.nn.relu(_f32_dot("bqjc,bsc->bjqs", qi, ki))
    return jnp.sum(dots * jnp.moveaxis(w, 1, 2)[..., None], axis=1)


def _select_keys(index, qpos, topk: int):
    """bool [B, Q, Sk]: for the query at position ``qpos[i]`` the
    min(qpos + 1, topk) visible keys (s <= qpos) of largest ``index``
    [B, Q, Sk] float32, ties to the lower s. EXACT, by bisection and
    without a sort: the float32 scores map monotonically to uint32
    keys, the k-th largest key is built bit by bit from counts of
    ``keys >= candidate`` (32 fused compare-and-count passes), and the
    ties at it are cut at the position that fills the room, found the
    same way over the bits of s. ``lax.top_k`` and a sort give the same
    set and cost a sort; ``approx_max_k`` gives another set."""
    kpos = jnp.arange(index.shape[-1], dtype=jnp.int32)
    visible = kpos[None, :] <= qpos[:, None]
    keys = _sortable_keys(index, visible)
    want = jnp.minimum(qpos + 1, topk).astype(jnp.int32)[None, :]
    return _top_of(keys, want, kpos)


def _sortable_keys(index, live):
    """uint32 like ``index`` [..., Q, U] float32: keys that order as the
    scores do, 0 — under every float's key, -inf's too — at the places
    that are not ``live`` (bool [Q, U], or with ``index``'s leading
    axes)."""
    index = jnp.where(index == 0, 0.0, index)  # -0.0 ranks as +0.0
    bits = jax.lax.bitcast_convert_type(index, jnp.uint32)
    keys = jnp.where(
        bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000)
    )
    live = jnp.expand_dims(live, tuple(range(index.ndim - live.ndim)))
    return jnp.where(live, keys, jnp.uint32(0))


def _top_of(keys, want, place):
    """bool like ``keys`` [..., Q, U] (``_sortable_keys``): in each row
    the ``want`` [..., Q] (broadcast; at most the row's live places)
    places of largest key, ties to the lower place (``place``: arange
    U); none where ``want`` is 0. ``_select_keys``' bisection."""
    lead, n = keys.shape[:-1], keys.shape[-1]

    def value_bit(i, kth):
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        count = jnp.sum(keys >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(count >= want, cand, kth)

    kth = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros(lead, jnp.uint32))
    above, ties = keys > kth[..., None], keys == kth[..., None]
    room = want - jnp.sum(above, axis=-1, dtype=jnp.int32)  # >= 1
    n_bits = max(1, (n - 1).bit_length())

    def place_bit(i, last):
        # the largest position with fewer than ``room`` ties before it
        cand = last | (jnp.int32(1) << (n_bits - 1 - i))
        before = jnp.sum(
            ties & (place < cand[..., None]), axis=-1, dtype=jnp.int32
        )
        return jnp.where(before < room, cand, last)

    last = jax.lax.fori_loop(
        0, n_bits, place_bit, jnp.zeros(lead, jnp.int32)
    )
    return above | (ties & (place <= last[..., None]))


def _index_chunks(cfg: ModelConfig, s: int):
    """(start, end) of the query chunks the indexer works by; chunk
    [start, end) sees keys [0, end) and no more."""
    c = min(cfg.index_chunk, s)
    if s % c:
        raise ValueError(
            f"sequence {s} is not a multiple of index_chunk {c}"
        )
    return [(start, start + c) for start in range(0, s, c)]


def _select(qi, ki, w, cfg: ModelConfig):
    """The selection of one layer, int8 [B, S, S]: 1 where query t
    attends to key s. Scored and cut a chunk of queries at a time
    against the keys the chunk can see; a chunk whose queries all see
    no more than ``index_topk`` keys takes every visible one unscored."""
    b, s = w.shape[:2]
    rows = []
    for start, end in _index_chunks(cfg, s):
        qpos = jnp.arange(start, end, dtype=jnp.int32)
        if end <= cfg.index_topk:
            chosen = jnp.broadcast_to(
                jnp.arange(end)[None, :] <= qpos[:, None],
                (b, end - start, end),
            )
        else:
            with jax.named_scope("attn.index"):
                index = _index_scores(
                    qi[:, start:end], ki[:, :end], w[:, start:end]
                )
            with jax.named_scope("attn.select"):
                chosen = _select_keys(index, qpos, cfg.index_topk)
        rows.append(
            jnp.pad(chosen.astype(jnp.int8), ((0, 0), (0, 0), (0, s - end)))
        )
    with jax.named_scope("attn.select"):
        return jnp.concatenate(rows, axis=1)


def _chunk_target(q, k, lse, sel, scale):
    """p_t of a chunk's queries, float32 [B, Q, Sk]: the head-mean of
    the attention's probabilities exp(q.k * scale - lse) on the
    selection ``sel`` [B, Q, Sk], zero off it (from the attention's own
    lse [B, H, Q]; one kv group's [B, G, Q, Sk] scores at a time)."""
    hkv = k.shape[2]
    groups = q.shape[2] // hkv
    p = jnp.zeros(sel.shape, jnp.float32)
    for g in range(hkv):
        scores = _f32_dot(
            "bqrd,bkd->brqk",
            q[:, :, g * groups:(g + 1) * groups], k[:, :, g],
        ) * scale
        p = p + jnp.sum(
            jnp.exp(scores - lse[:, g * groups:(g + 1) * groups, :, None]),
            axis=1,
        )
    return jnp.where(sel != 0, p / q.shape[2], 0.0)


def _chunk_kl(qi, ki, w, q, k, lse, sel, scale):
    """Sum over a chunk's queries of KL(p_t || softmax_{S_t} I_t): p_t
    the attention's side (``_chunk_target``), I the index scores on the
    selection ``sel``; 0 log 0 = 0."""
    p = _chunk_target(q, k, lse, sel, scale)
    log_i = jax.nn.log_softmax(
        jnp.where(sel != 0, _index_scores(qi, ki, w), -jnp.inf), axis=-1
    )
    live = p > 0
    return jnp.sum(jnp.where(
        live,
        p * (jnp.log(jnp.where(live, p, 1.0)) - jnp.where(live, log_i, 0.0)),
        0.0,
    ))


def _alignment_chunks(operands, cfg: ModelConfig, scale):
    """(this chunk's operands, [start, end)) for ``_chunk_kl``."""
    qi, ki, w, q, k, lse, mask = operands
    for start, end in _index_chunks(cfg, w.shape[1]):
        yield (
            qi[:, start:end], ki[:, :end], w[:, start:end],
            q[:, start:end], k[:, :end], lse[:, :, start:end],
            mask[:, start:end, :end], scale,
        ), (start, end)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _alignment_kl(qi, ki, w, q, k, lse, mask, cfg, scale, tiles=None):
    """Sum over every query of KL(p_t || softmax_{S_t} I_t), chunk by
    chunk. The attention's side (``q``, ``k``, ``lse``) is the target
    and carries no gradient; the derivative for ``qi``, ``ki`` and ``w``
    is taken in the rule below, in the pass that makes the value, so
    that a chunk's [B, G, Q, Sk] scores never outlive it.

    One algorithm on two back ends: where the attention runs the Pallas
    kernels and the shapes fit (``tiles``: ``alignment_tiles``) the rule
    is the kernel ``ops/pallas_align.py``; on the CPU, under
    ``attn_impl == "reference"`` and at odd shapes it is the jnp below.

    The rule tags that derivative as the named residual
    ``attn_align_grad`` (qi's, ki's and w's shapes and dtypes). Under
    ``remat: full`` a selecting model's policy keeps the name
    (``_remat_body``), so the forward runs the rule once, value and
    derivative, and the recomputed forward scores no pair for this term
    again; under ``remat: none`` nothing is recomputed and the tag is
    inert. Either way the rule runs once a layer a step."""
    return sum(
        _chunk_kl(*args)
        for args, _ in _alignment_chunks((qi, ki, w, q, k, lse, mask), cfg, scale)
    )


def _alignment_kl_fwd(qi, ki, w, q, k, lse, mask, cfg, scale, tiles=None):
    operands = (qi, ki, w, q, k, lse, mask)
    if tiles is not None:
        from dlrover_tpu.ops import pallas_align

        # value and derivative in one kernel a layer: the attention's
        # side and the indexer's tile by tile, nothing of either whole
        kl, *grads = pallas_align.alignment_kl_and_grads(
            qi, ki, w, mask, q, k, lse, scale, *tiles
        )
        total, grads = jnp.sum(kl), tuple(grads)
    else:
        total = jnp.zeros([], jnp.float32)
        d_qi, d_w = [], []
        d_ki = jnp.zeros(ki.shape, jnp.float32)
        for args, (_start, end) in _alignment_chunks(operands, cfg, scale):
            value, grads = jax.value_and_grad(
                _chunk_kl, argnums=(0, 1, 2)
            )(*args)
            total = total + value
            d_qi.append(grads[0])
            d_ki = d_ki.at[:, :end].add(grads[1].astype(jnp.float32))
            d_w.append(grads[2])
        grads = (
            jnp.concatenate(d_qi, axis=1), d_ki.astype(ki.dtype),
            jnp.concatenate(d_w, axis=1),
        )
    grads = jax.ad_checkpoint.checkpoint_name(grads, "attn_align_grad")
    return total, (grads, q, k, lse, mask)


def _alignment_kl_bwd(cfg, scale, tiles, residuals, g):
    (d_qi, d_ki, d_w), q, k, lse, mask = residuals
    return (
        (g * d_qi).astype(d_qi.dtype), (g * d_ki).astype(d_ki.dtype),
        (g * d_w).astype(d_w.dtype),
        jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(lse),
        np.zeros(mask.shape, dtype=jax.dtypes.float0),
    )


_alignment_kl.defvjp(_alignment_kl_fwd, _alignment_kl_bwd)


def _selecting_attention_block(
    x, layer, cfg: ModelConfig, mesh, positions, attn_fn, rope=None,
    return_selected: bool = False,
):
    """``_attention_block`` for a model that selects its keys
    (``cfg.index_topk``). Returns (block output [B, S, D], aux) with
    ``aux["indexer_loss"]`` the layer's mean KL before its coefficient
    and, where ``return_selected``, ``aux["attn_selected"]`` bool
    [B, S, S].

    The indexer reads ``x`` DETACHED and the alignment term's target
    (the attention's probabilities) is detached too, so the indexer's
    matrices get their gradient from ``indexer_loss`` alone and nothing
    else gets any from it. The selection and the alignment term are
    made once a step: the selection (``attn_selected``, int8 [B, S, S])
    and the term's derivative for the indexer (``attn_align_grad``:
    ``qi``'s, ``ki``'s and ``w``'s shapes, 17.5 MB a layer at 1 x 8192)
    are named residuals that ``remat: full`` keeps, so its recomputed
    forward loads the selection for the flash kernels and runs neither
    ``_select`` nor ``_alignment_kl``, and of ``_index_inputs`` only
    what the projections' own backward reads."""
    b, s, _ = x.shape
    nh, hd = cfg.n_head, cfg.head_dim
    q, k, v = _constrain_qkv(
        *_project_qkv(x, layer, cfg, positions, rope=rope), mesh
    )
    with jax.named_scope("attn.index"):
        qi, ki, w = _index_inputs(
            jax.lax.stop_gradient(x), layer["indexer"], cfg, positions
        )
    mask = jax.ad_checkpoint.checkpoint_name(
        _select(*jax.lax.stop_gradient((qi, ki, w)), cfg), "attn_selected"
    )
    out, lse, in_kernel = attn_fn(q, k, v, selected=mask)
    tiles = alignment_tiles(cfg, s) if in_kernel else None
    set_counter("attn.align_in_kernel", int(tiles is not None))
    with jax.named_scope("attn.index_loss"):
        kl = _alignment_kl(
            qi, ki, w,
            *jax.lax.stop_gradient((q, k, lse)), mask, cfg, hd ** -0.5,
            tiles,
        ) / (b * s)
    aux = {"indexer_loss": kl}
    if return_selected:
        aux["attn_selected"] = mask != 0
    out = out.reshape(b, s, nh * hd)
    return out @ layer["attn"]["wo"].astype(x.dtype), aux


# ---------------------------------------------------------------------------
# A selection of blocks of keys (InfLLM-v2) and lightning linear attention
# ---------------------------------------------------------------------------


def _pooled_keys(k, window: int, stride: int):
    """k [B, S, KV, D] -> [B, P, KV, D] in k's dtype: pooled key j the
    float32 mean of keys [stride j, stride j + window), P whole
    windows; sums of ``stride`` keys first, then ``window // stride`` of
    those side by side."""
    b, s, kv, d = k.shape
    count = (s - window) // stride + 1
    parts = k.astype(jnp.float32).reshape(b, s // stride, stride, kv, d)
    parts = jnp.sum(parts, axis=2)
    total = sum(parts[:, o:o + count] for o in range(window // stride))
    return (total / window).astype(k.dtype)


def _head_sum(p):
    """[B, G, heads of a KV head, Q, P] -> [B, G, Q, P]."""
    return jnp.sum(p, axis=2)


def _block_reduce(p):
    """[..., U, the pooled keys that overlap a block] -> [..., U]."""
    return jnp.max(p, axis=-1)


def _forced_blocks(qpos, n_units: int, cfg: ModelConfig):
    """bool [Q, U]: the blocks the query at ``qpos`` takes whatever
    their score, among those it sees: the initial ones and its local
    window's."""
    own = (qpos // cfg.sparse_block)[:, None]
    unit = jnp.arange(n_units, dtype=jnp.int32)[None, :]
    near = own - unit < cfg.select_local // cfg.sparse_block
    return (near | (unit < cfg.select_init_blocks)) & (unit <= own)


def _block_scores(q, pooled, qpos, cfg: ModelConfig, n_units: int):
    """q [B, Q, H, D] at positions ``qpos`` [Q], pooled [B, P, KV, D]
    -> float32 [B, KV, Q, U]: a block's score for each KV head, the max
    over the pooled keys that overlap it of the KV head's query heads'
    summed probabilities; 0 where no pooled key has ended. The product
    on the operands' dtype summed in float32, everything behind it
    float32."""
    b, nq, h, d = q.shape
    n_pooled, kv = pooled.shape[1:3]
    window, stride, block = cfg.pool_window, cfg.pool_stride, cfg.sparse_block
    ended = (
        stride * jnp.arange(n_pooled) + window - 1
    )[None, :] <= qpos[:, None]
    dots = _f32_dot(
        "bqgrd,bpgd->bgrqp", q.reshape(b, nq, kv, h // kv, d), pooled
    ) * d ** -0.5
    p = jax.nn.softmax(jnp.where(ended, dots, -1e30), axis=-1)
    p = _head_sum(jnp.where(ended, p, 0.0))  # none ended: zeros
    # pooled keys r u - extra .. r u + r - 1 overlap block u
    r, extra = block // stride, window // stride - 1
    p = jnp.pad(p, [(0, 0)] * 3 + [(extra, r * n_units - n_pooled)])
    over = jnp.stack(
        [p[..., o:o + r * n_units:r] for o in range(r + extra)], axis=-1
    )
    return _block_reduce(over)


def _select_blocks(q, k, cfg: ModelConfig):
    """The selection of one ``S`` part, int8 [B, KV, S, U] (U = S /
    ``sparse_block``): 1 where query t of a KV head's query heads
    attends to block u. ``index_chunk`` queries at a time against every
    pooled key (no float [H, S, P] is ever whole). No parameter of its
    own and nothing differentiated: the caller detaches q and k."""
    b, s, _, _ = q.shape
    block, topk = cfg.sparse_block, cfg.index_topk
    if s % block or s % cfg.pool_stride:
        raise ValueError(
            f"sequence {s} is no whole number of blocks of {block} keys"
        )
    n_units = s // block
    pooled = _pooled_keys(k, cfg.pool_window, cfg.pool_stride)
    chunk = min(cfg.index_chunk, s)
    if s % chunk:
        raise ValueError(f"sequence {s} is not a multiple of {chunk}")
    place = jnp.arange(n_units, dtype=jnp.int32)
    unit = place[None, :]

    def rows(start):
        qpos = start + jnp.arange(chunk, dtype=jnp.int32)
        scores = _block_scores(
            jax.lax.dynamic_slice_in_dim(q, start, chunk, 1), pooled, qpos,
            cfg, n_units,
        )
        seen = unit <= (qpos // block)[:, None]
        forced = _forced_blocks(qpos, n_units, cfg) & seen
        free = seen & ~forced
        want = jnp.minimum(
            jnp.sum(free, -1), jnp.maximum(topk - jnp.sum(forced, -1), 0)
        ).astype(jnp.int32)
        best = _top_of(_sortable_keys(scores, free), want, place)
        return (forced | best).astype(jnp.int8)

    chosen = jax.lax.map(rows, jnp.arange(0, s, chunk, dtype=jnp.int32))
    # [chunks, B, KV, Q, U] -> [B, KV, S, U]
    return jnp.moveaxis(chosen, 0, 2).reshape(b, -1, s, n_units)


def _block_sparse_attention(
    x, layer, cfg: ModelConfig, mesh, positions, attn_fn,
    return_selected: bool = False,
):
    """An ``S`` part on the layer's normed input ``x`` [B, S, D]: GQA
    with a per-head RMSNorm on q and k and NO rope; past
    ``select_dense_len`` tokens each KV head's query heads attend to the
    keys up to themselves inside the blocks ``_select_blocks`` chose
    (scope ``attn.block_select``; the named residual ``attn_selected``,
    which ``remat: full`` keeps: the recomputed forward runs neither the
    scorer nor the top-k), else to every earlier key; a sigmoid gate,
    then ``W_o`` into float32. Returns (output, aux):
    ``aux["sparse_attn_out_ms"]`` the mean square of the attention's
    output before gate and ``W_o`` (float32; the part's one health
    number), ``aux["attn_selected"]`` bool [KV, B, S, U] where
    ``return_selected`` and the queries choose."""
    b, s, _ = x.shape
    f32 = jnp.float32
    q, k, v = _constrain_qkv(
        *_project_qkv(x, layer, cfg, positions, rope=False), mesh
    )
    aux = {}
    if cfg.selects_at(s):
        with jax.named_scope("attn.block_select"):
            units = jax.ad_checkpoint.checkpoint_name(
                _select_blocks(*jax.lax.stop_gradient((q, k)), cfg),
                "attn_selected",
            )
        # units to keys, int8 [B, KV, S, S]; the causal mask inside a
        # query's own block is the kernels'
        keys = jnp.repeat(units, cfg.sparse_block, axis=-1)
        out = attn_fn(q, k, v, selected=keys)[0]
        if return_selected:
            aux["attn_selected"] = jnp.moveaxis(units != 0, 1, 0)
    else:
        out = attn_fn(q, k, v)
    out = out.reshape(b, s, -1)
    aux["sparse_attn_out_ms"] = jax.lax.stop_gradient(
        jnp.mean(jnp.square(out.astype(f32)))
    )
    attn = layer["attn"]
    if cfg.attn_gate:
        out = _gate_output(out, x, attn["wg"])
    return jnp.matmul(
        out, attn["wo"].astype(x.dtype), preferred_element_type=f32
    ), aux


def _lightning_decay(cfg: ModelConfig):
    """float32 [n_head]: log λ_h, a constant."""
    return jnp.asarray(lightning_log_decay(cfg.n_head), jnp.float32)


def _lightning_block(h, lin, cfg: ModelConfig, mesh, rope):
    """A lightning linear attention on the layer's normed input ``h``
    [B, S, D] (scope ``lin``; inside it ``ssm.scan`` from ``ssd_scan``):

        q, k, v = h W_q, h W_k, h W_v        (n_head heads of head_dim)
        q, k = rope(rms_head(q)), rope(rms_head(k))
        S_t = λ_h S_{t-1} + k_tᵀ v_t;  o_t = q_t S_t / sqrt(head_dim)
        out = (rms(o) ⊙ sigmoid(h W_g)) W_o

    The recurrence is ``ops/ssd.py``'s chunked scan at one head a group
    with x = v, Δ ≡ 1, A = log λ, B = k, C = q / sqrt(head_dim): the
    state and the cumulative log-decays float32. q and k are normed,
    turned (``rope`` None: no positions) and scaled in float32 and
    rounded once; the output norm, the gate and the product with it
    float32, ``W_o`` into float32. The output norm's statistic is over
    the WHOLE read-out, every head's channels together (Lightning
    Attention's own form): over one head's alone, a token's output would
    be a unit vector however small its read-out, and at the first tokens,
    whose read-out is (q_0 . k_0) v_0 and little more, rounding near
    q_0 . k_0 = 0 decides its sign (PERF.md section 6, PR 57: one seed
    in thirteen failed ``logits_vs_reference`` at position 0).

    Returns (output, aux): ``aux["lightning_fast_out_ms"]`` the mean
    square of the read-out ``o`` of the quarter of the heads that forget
    fastest (the first ``n_head // 4``, at least one), before the norm
    (float32). Normed whole, those heads hold about a hundredth of the
    read-out's energy, so the logits do not see what garbles them alone
    (a running log-decay kept in eight bits: its sum passes 200 inside a
    chunk there); this number does."""
    b, s, _ = h.shape
    nh, hd = cfg.n_head, cfg.head_dim
    dt_, f32 = h.dtype, jnp.float32

    def heads(w, norm=None, scale=1.0):
        t = (h @ w.astype(dt_)).reshape(b, s, nh, hd)
        if norm is None:
            return t
        t = _head_norm(t.astype(f32), norm["scale"], cfg)
        if rope is not None:
            t = _rope(t, rope)
        return (t * scale).astype(dt_)

    q = heads(lin["wq"], lin["q_norm"], hd ** -0.5)
    k = heads(lin["wk"], lin["k_norm"])
    v = heads(lin["wv"])
    # (the mesh only where it rules the kernels out: ``_mamba_block``)
    several = {"mesh": mesh} if mesh is not None and mesh.size > 1 else {}
    o = ssd.ssd_scan(
        v, jnp.ones((b, s, nh), f32), _lightning_decay(cfg), k, q,
        cfg.ssm_chunk, 0, **several,
    )
    o = o.astype(f32)
    aux = {"lightning_fast_out_ms": jax.lax.stop_gradient(
        jnp.mean(jnp.square(o[:, :, :max(1, nh // 4)]))
    )}
    o = _head_norm(o.reshape(b, s, nh * hd), lin["o_norm"]["scale"], cfg)
    o = _lightning_gate(o, h, lin["wg"])
    return jnp.matmul(
        o.astype(dt_), lin["wo"].astype(dt_), preferred_element_type=f32
    ), aux


def _head_norm(t, scale, cfg: ModelConfig):
    """RMSNorm over the last axis: each head's channels of a lightning
    part's q and k [B, S, H, D] (one learned scale of D for all heads),
    the whole of its read-out [B, S, H·D]."""
    return _norm(t, scale, None, "rmsnorm", cfg.norm_eps)


def _lightning_gate(o, h, w_gate):
    """``o ⊙ sigmoid(h W_g)``, float32 [B, S, H·D]."""
    gate = jnp.matmul(
        h, w_gate.astype(h.dtype), preferred_element_type=jnp.float32
    )
    return o * jax.nn.sigmoid(gate)


def _mlp_block(x, layer, cfg: ModelConfig, mesh, fp8=None, interior=None):
    """``interior``: the dtype of what lies between the matmuls and of
    the output (None = ``x``'s): a ``layer_pattern`` model's ``-`` part
    keeps float32 there, for ``_mamba1_block``'s reason."""
    mlp = layer["mlp"]
    if fp8 is not None:
        # fp8 GEMMs (cfg.fp8): delayed scaling against per-projection
        # states — fp8_dot's "grad" w.r.t. each state dict is the
        # UPDATED amax history, harvested from the gradient tree by the
        # train step (ops/fp8.py state-on-cotangent convention) — or
        # stateless current scaling under pipeline meshes
        if cfg.act == "swiglu":
            gate = _fp8_gemm(x, mlp["w_gate"].astype(x.dtype), fp8, "gate")
            up = _fp8_gemm(x, mlp["w_up"].astype(x.dtype), fp8, "up")
            h = jax.nn.silu(gate) * up
        else:
            h = jax.nn.gelu(
                _fp8_gemm(x, mlp["w_up"].astype(x.dtype), fp8, "up")
            )
        if mesh is not None:
            h = shd.constrain(h, mesh, "batch", "seq", "mlp")
        return _fp8_gemm(h, mlp["w_down"].astype(x.dtype), fp8, "down")
    matmul = functools.partial(jnp.matmul, preferred_element_type=interior)
    if cfg.act == "swiglu":
        gate = matmul(x, mlp["w_gate"].astype(x.dtype))
        up = matmul(x, mlp["w_up"].astype(x.dtype))
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(matmul(x, mlp["w_up"].astype(x.dtype)))
    if mesh is not None:
        h = shd.constrain(h, mesh, "batch", "seq", "mlp")
    return matmul(h.astype(x.dtype), mlp["w_down"].astype(x.dtype))


def _kind_scope(kind: str):
    """A kind's whole attention part, kernels included, under ``attn``."""
    if not kind:
        return contextlib.nullcontext()
    return jax.named_scope(ATTN_KINDS[kind].scope)


def _layer_body(
    x,
    layer,
    positions,
    cfg: ModelConfig,
    mesh,
    attn_fn,
    rng=None,
    fp8=None,
    rope=None,
    return_selected: bool = False,
    kind: str = "",
):
    """``kind``: the layer's letter of ``cfg.layer_types`` ("" = the
    model's one kind); ``attn_fn`` is then called with it. ``rope``:
    that kind's tables (``_project_qkv``)."""
    ln1, ln2 = layer["ln1"], layer["ln2"]
    attn_aux = {}
    if kind:
        attn_fn = functools.partial(attn_fn, kind=kind)
        if not cfg.kind_rope(kind):
            # no positional term (said here: a False handed through the
            # remat wrapper would arrive as an array)
            rope = False
    with jax.named_scope("attn"), _kind_scope(kind):
        h = _norm_block(x, ln1, cfg)
        if cfg.selects_keys:
            attn, attn_aux = _selecting_attention_block(
                h, layer, cfg, mesh, positions, attn_fn, rope=rope,
                return_selected=return_selected,
            )
        else:
            attn = _attention_block(
                h, layer, cfg, mesh, positions, attn_fn, fp8=fp8, rope=rope
            )
        if cfg.post_norm:
            attn = _norm_block(attn, layer["ln1_post"], cfg)
    aux = {
        "moe_lb_loss": jnp.zeros([], jnp.float32),
        "moe_z_loss": jnp.zeros([], jnp.float32),
    }
    with jax.named_scope("mlp"):
        if cfg.parallel_residual:
            # GPTNeoX-style: both branches read the LAYER INPUT —
            # x + attn(ln1 x) + mlp(ln2 x); the attn and mlp matmul
            # chains have no data dependence, so XLA can overlap them
            h2 = _norm_block(x, ln2, cfg)
        else:
            # fused path: the residual add rides in the norm kernel —
            # x + attn is written once, from the same VMEM visit that
            # computes the statistics
            h2, x = _norm_block(x, ln2, cfg, residual=attn)
        if "moe" in layer:
            from dlrover_tpu.parallel.moe import moe_block

            # fp8 reaches the experts as stateless current scaling (the
            # dense/all-to-all paths; ragged stays bf16 — see moe.py);
            # delayed-scaling state dicts cover only the attention
            # projections in MoE layers (init_fp8_states)
            mlp_out, aux = moe_block(
                h2, layer["moe"], cfg, mesh, rng=rng, return_aux=True,
                fp8=fp8,
            )
        else:
            mlp_out = _mlp_block(h2, layer, cfg, mesh, fp8=fp8)
        if cfg.post_norm:
            mlp_out = _norm_block(mlp_out, layer["ln2_post"], cfg)
        x = x + attn + mlp_out if cfg.parallel_residual else x + mlp_out
        if mesh is not None:
            x = shd.constrain(x, mesh, "batch", "seq", None)
    return x, {**aux, **attn_aux}


def _mamba_block(h, ssm, cfg: ModelConfig, mesh):
    """A Mamba-2 mixer on the layer's normed input ``h`` [B, S, D]
    (scope ``ssm``; inside it ``ssm.conv`` and ``ssm.scan``):

        [z | xBC | dt] = h W_in;  xBC = silu(conv(xBC)) = [x | B | C]
        Δ = softplus(dt + dt_bias);  A = -exp(A_log)        (float32)
        y = scan(x, Δ, A, B, C) + D x                       (ops/ssd.py)
        out = group_norm(y ⊙ silu(z)) W_out
    """
    b, s, _ = h.shape
    dt_ = h.dtype
    inner, heads, hd = cfg.d_inner, cfg.mamba_num_heads, cfg.mamba_head_dim
    g, n = cfg.n_groups, cfg.ssm_state_size
    f32 = jnp.float32
    proj = h @ ssm["w_in"].astype(dt_)
    if mesh is not None:
        proj = shd.constrain(proj, mesh, "batch", "seq", "mlp")
    z = proj[..., :inner]
    # the mesh goes along only where it rules the conv's and the scan's
    # kernels out (several devices: ROADMAP S6): the benchmark's planted
    # defects stand in for ``causal_conv`` by its three parameters and
    # for ``ssd_scan`` by its own up to ``head_block``. The conv takes
    # its columns of the projection where they lie (``ssd.Columns``)
    several = {"mesh": mesh} if mesh is not None and mesh.size > 1 else {}
    xbc = jax.nn.silu(ssd.causal_conv(
        ssd.Columns(proj, inner), ssm["conv_w"], ssm["conv_b"], **several
    ))
    step = jax.nn.softplus(
        proj[..., inner + cfg.conv_dim:].astype(f32)
        + ssm["dt_bias"].astype(f32)
    )
    x = xbc[..., :inner].reshape(b, s, heads, hd)
    y = ssd.ssd_scan(
        x, step, -jnp.exp(ssm["a_log"].astype(f32)),
        xbc[..., inner:inner + g * n].reshape(b, s, g, n),
        xbc[..., inner + g * n:].reshape(b, s, g, n),
        cfg.ssm_chunk, cfg.ssm_head_block, **several,
    )
    y = y + (ssm["d_skip"].astype(f32)[:, None] * x.astype(f32)).astype(dt_)
    y = ssd.gated_group_norm(
        y.reshape(b, s, inner), z, ssm["norm"]["scale"], g, cfg.ssm_norm_eps
    )
    return y @ ssm["w_out"].astype(dt_)


def _mamba1_block(h, ssm, cfg: ModelConfig, mesh):
    """A Mamba-1 mixer on the layer's normed input ``h`` [B, S, D]
    (scope ``ssm1``; inside it ``ssm1.conv``, ``ssm1.dbc`` and
    ``ssm1.scan``):

        [u | z] = h W_in;  u = silu(conv(u) + b)
        [r | B | C] = u W_x, each RMS-normed;  Δ = softplus(r W_dt + b_dt)
        A = -exp(A_log)                                     (float32)
        y = scan(u, Δ, A, B, C) + D u          (ops/selective_scan.py)
        out = (y ⊙ silu(z)) W_out

    The matmuls multiply operands of the compute dtype and everything
    between them is float32, the output too (``_run_pattern`` rounds
    the stream, once a scanned unit): thirteen of these and fourteen
    MLPs in a row amplify each other's rounding (a relative change of
    the stream grows 2-5 x on its way down, PERF.md section 4), and
    with bf16 between the matmuls the logits stand 2.9e-2 from the
    float32 reference where a dense cell may stand 2.5e-2."""
    from dlrover_tpu.ops.selective_scan import selective_scan

    dt_ = jnp.dtype(cfg.dtype)
    inner, rank, n = cfg.d_inner1, cfg.mamba_dt_rank, cfg.ssm_state_size
    f32 = jnp.float32

    def matmul(x, w):
        return jnp.matmul(
            x.astype(dt_), w.astype(dt_), preferred_element_type=f32
        )

    proj = matmul(h, ssm["w_in"])
    if mesh is not None:
        proj = shd.constrain(proj, mesh, "batch", "seq", "mlp")
    z = proj[..., inner:]
    # (the mesh only where it rules the kernels out: ``_mamba_block``)
    several = {"mesh": mesh} if mesh is not None and mesh.size > 1 else {}
    with jax.named_scope("ssm1.conv"):
        u = jax.nn.silu(ssd.causal_conv(
            ssd.Columns(proj, 0), ssm["conv_w"], ssm["conv_b"], **several
        ))
    with jax.named_scope("ssm1.dbc"):
        r, b_mat, c_mat = (
            _norm(t, ssm[name]["scale"], None, "rmsnorm", cfg.norm_eps)
            for t, name in zip(
                jnp.split(matmul(u, ssm["w_x"]), [rank, rank + n], axis=-1),
                ("dt_norm", "b_norm", "c_norm"),
            )
        )
        step = jax.nn.softplus(
            matmul(r, ssm["w_dt"]) + ssm["dt_bias"].astype(f32)
        )
    y = selective_scan(
        u, step, -jnp.exp(ssm["a_log"].astype(f32)), b_mat, c_mat, mesh=mesh
    )
    y = (y + ssm["d_skip"].astype(f32) * u) * jax.nn.silu(z)
    return matmul(y, ssm["w_out"])


def _gated_conv_block(h, conv, cfg: ModelConfig, mesh):
    """A gated short convolution (LFM2's conv mixer) on the layer's
    normed input ``h`` [B, S, D] (scope ``conv``; inside it
    ``conv.in_proj``, ``conv.gate`` and ``conv.out_proj``):

        [B | C | x] = h W_in                                (D -> 3 D)
        z = B ⊙ x;  c_t = Σ_j w_j ⊙ z_{t-K+1+j}     (ops/ssd.py::gated_conv)
        out = (C ⊙ c) W_out

    a depthwise causal conv of ``conv_kernel`` taps a channel, z before
    a sequence's first token 0, no bias and NO activation. The
    in-projection is written once, in the compute dtype, and read once
    where it lies by the gated conv's one pass (float32 inside, one
    rounding of ``C ⊙ c``); the output is float32 as the ``-`` part's
    is (``_run_pattern`` rounds the stream)."""
    dt_ = h.dtype
    with jax.named_scope("conv.in_proj"):
        proj = h @ conv["w_in"].astype(dt_)
        if mesh is not None:
            proj = shd.constrain(proj, mesh, "batch", "seq", "mlp")
    # (the mesh only where it rules the kernels out: ``_mamba_block``)
    several = {"mesh": mesh} if mesh is not None and mesh.size > 1 else {}
    y = ssd.gated_conv(proj, conv["conv_w"], **several)
    with jax.named_scope("conv.out_proj"):
        return jnp.matmul(
            y, conv["w_out"].astype(dt_), preferred_element_type=jnp.float32
        )


def _l2_in_kernel(d: int) -> bool:
    """Whether ``_l2_heads`` norms heads of ``d`` channels by
    ``pallas_norm.l2_heads``: a TPU (or interpreted) and a head whole
    lanes. What ``gdn.norm_kernel_layers`` / ``kda.norm_kernel_layers``
    count by."""
    return pallas_norm.kernels_available() and d % 128 == 0


def _l2_heads(t, scale=1.0, eps=1e-6):
    """Each head's channels of t [B, S, H, D] over their L2 norm, times
    ``scale``: float32 inside, one rounding. One formula, two bodies,
    by the device and D (``_l2_in_kernel``). On the chip at heads of
    whole lanes the 4-D form is a VIEW on both sides and the arithmetic
    is done on ``[B, S, H * D]``, a head a run of columns
    (``pallas_norm.l2_heads``, one Pallas pass forward and one back):
    the layout the delta rules' kernels take (``gated_delta._flat``), so
    the reshapes here cancel against the caller's and theirs and no
    ``[S, H, D]``-tiled copy of q or k is made. Elsewhere the ``jnp``
    body, in the 4-D form."""
    if _l2_in_kernel(t.shape[-1]):
        b, s, h, d = t.shape
        return pallas_norm.l2_heads(
            t.reshape(b, s, h * d), d, scale, eps
        ).reshape(t.shape)
    t32 = t.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.sum(t32 * t32, -1, keepdims=True) + eps)
    return (t32 * (inv * scale)).astype(t.dtype)


def _gdn_block(h, gdn, cfg: ModelConfig, mesh):
    """A gated-delta-rule mixer on the layer's normed input ``h``
    [B, S, D] (scope ``gdn``; inside it ``gdn.conv`` around ``ssm.conv``,
    ``gdn.rule`` and ``gdn.gate``):

        [q | k | v | z] = h W_qkvz;  [b | a] = h W_ba       (float32)
        [q | k | v] = silu(conv([q | k | v]))               (no bias)
        β = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
        q, k = q / |q|, k / |k| a head;  q = q / sqrt(key channels)
        o = gated_delta_rule(q, k, v, g, β)      (ops/gated_delta.py)
        out = (rms_head(o) w ⊙ silu(z)) W_out

    Key head j serves value heads R j .. R j + R - 1. The norm comes
    BEFORE the gate (``ssd.gated_group_norm(norm_before_gate=True)``),
    with one learned scale of a head's channels.

    The three matrices multiply operands of the compute dtype and
    EVERYTHING between them is float32, the output too (``_run_pattern``
    rounds the stream), and the rule multiplies float32 operands in
    three bf16 passes (``gated_delta._products``) — ``_mamba1_block``'s
    reason, more so: a mixer between norms (its input's, q's and k's L2
    norms, the read-out's) maps a relative change of its input into one
    1.6 times as large in its output, every part behind it does so
    again, and with bf16 between the matmuls each mixer adds 0.8% of its
    own (its projection's rounding 0.6, q, k and v into the rule 0.25,
    the rule's twelve roundings 0.35) where this form adds 0.17: at
    eight layers the stream then stands 4.6e-2 from the float32
    reference by rms, each mixer adding the same 0.7e-2, where the cell
    may stand 2.5e-2 (my chip runs, PR 63: PERF.md section 6). Returns
    (output, aux):
    ``aux["gdn_readout_ms"]`` the mean square of the read-out ``o``
    before the norm (float32): a uniform scale of ``o`` — q's
    1 / sqrt(channels), a missing L2 norm on q — is invisible behind
    the per-head norm, and this number is what sees it.

    Which form is computed in: q, k and v leave the conv a head a run
    of columns (``[B, S, H * D]``), the form the rule's kernels take
    (``gated_delta._flat``). ``_l2_heads`` and ``gated_delta_rule``
    keep their 4-D doors (``[B, S, H, D]``), and on the chip that form
    is a VIEW: the norms are computed flat (``_l2_heads``), g and β are
    ``[B, S, Hv]`` either way, and no array of a whole sequence is
    copied between the two tilings."""
    b, s, _ = h.shape
    dt_, f32 = jnp.dtype(cfg.dtype), jnp.float32
    hk, hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
    keys, wide, inner = hk * dk, cfg.gdn_conv_dim, hv * dv

    def matmul(x, w):
        return jnp.matmul(
            x.astype(dt_), w.astype(dt_), preferred_element_type=f32
        )

    proj = matmul(h, gdn["w_qkvz"])
    if mesh is not None:
        proj = shd.constrain(proj, mesh, "batch", "seq", "mlp")
    ba = matmul(h, gdn["w_ba"])
    # (the mesh only where it rules the kernels out: ``_mamba_block``)
    several = {"mesh": mesh} if mesh is not None and mesh.size > 1 else {}
    with jax.named_scope("gdn.conv"):
        qkv = jax.nn.silu(ssd.causal_conv(
            ssd.Columns(proj, 0), gdn["conv_w"], jnp.zeros((wide,), f32),
            **several,
        ))
    with jax.named_scope("gdn.rule"):
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(gdn["a_log"].astype(f32)) * jax.nn.softplus(
            ba[..., hv:] + gdn["dt_bias"].astype(f32)
        )
        q = _l2_heads(qkv[..., :keys].reshape(b, s, hk, dk), dk ** -0.5)
        k = _l2_heads(qkv[..., keys:2 * keys].reshape(b, s, hk, dk))
        v = qkv[..., 2 * keys:].reshape(b, s, hv, dv)
        o = gated_delta.gated_delta_rule(q, k, v, g, beta, **several)
    aux = {"gdn_readout_ms": jax.lax.stop_gradient(jnp.mean(jnp.square(o)))}
    with jax.named_scope("gdn.gate"):
        y = ssd.gated_group_norm(
            o.reshape(b, s, inner), proj[..., wide:], gdn["norm"]["scale"],
            hv, cfg.norm_eps or 1e-6, norm_before_gate=True,
        )
    return matmul(y, gdn["w_out"]), aux


def _kda_block(h, kda, cfg: ModelConfig, mesh):
    """A delta-rule mixer whose decay is a vector over the key channels
    (Kimi Delta Attention) on the layer's normed input ``h`` [B, S, D]
    (scope ``kda``; inside it ``kda.conv`` around ``ssm.conv``,
    ``kda.rule`` and ``kda.gate``):

        [q | k | v] = h W_qkv;  [f | z | b] = h W_gates     (float32)
        [q | k | v] = silu(conv([q | k | v]))               (no bias)
        β = sigmoid(b);  g = -exp(A_log) softplus(f W_fb + dt_bias)
                (one decay a head AND key channel, through a low rank)
        q, k = q / |q|, k / |k| a head;  q = q / sqrt(key channels)
        o = gated_delta_rule(q, k, v, g, β)      (ops/gated_delta.py)
        out = (rms_head(o) w ⊙ sigmoid(z W_gb)) W_out

    H heads of D key and D value channels, each head its own q, k and v.
    ``_gdn_block`` with three differences: the decay is [B, S, H, D] and
    so the vector rule (``ops/pallas_kda.py``'s kernels where
    ``gated_delta.in_kernels`` says so, else its XLA body); both gates
    come through a low rank; the
    output gate is a sigmoid. The interior is ``_gdn_block``'s for
    ``_gdn_block``'s reason: the matrices multiply operands of the
    compute dtype, EVERYTHING between them is float32 (the output too),
    and the rule multiplies float32 operands in three bf16 passes.
    Returns (output, aux): ``aux["kda_readout_ms"]`` the mean square of
    the read-out ``o`` before the norm, as ``gdn_readout_ms``.

    Which form is computed in, as in ``_gdn_block``: flat (``[B, S,
    H * D]``, a head a run of columns) from the conv to the rule's
    kernels — the L2 norms by ``_l2_heads``' flat body, g as ``A_log``
    repeated over a head's columns times the softplus of ``[B, S,
    H * D]`` — and ``[B, S, H, D]`` a view of it at ``_l2_heads``' and
    ``gated_delta_rule``'s doors."""
    b, s, _ = h.shape
    dt_, f32 = jnp.dtype(cfg.dtype), jnp.float32
    heads, dh, rank = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_gate_rank
    inner = heads * dh

    def matmul(x, w):
        return jnp.matmul(
            x.astype(dt_), w.astype(dt_), preferred_element_type=f32
        )

    proj = matmul(h, kda["w_qkv"])
    if mesh is not None:
        proj = shd.constrain(proj, mesh, "batch", "seq", "mlp")
    gates = matmul(h, kda["w_gates"])
    decay_in = matmul(gates[..., :rank], kda["w_fb"])
    gate_in = matmul(gates[..., rank:2 * rank], kda["w_gb"])
    # (the mesh only where it rules the kernels out: ``_mamba_block``)
    several = {"mesh": mesh} if mesh is not None and mesh.size > 1 else {}
    with jax.named_scope("kda.conv"):
        qkv = jax.nn.silu(ssd.causal_conv(
            ssd.Columns(proj, 0), kda["conv_w"],
            jnp.zeros((3 * inner,), f32), **several,
        ))
    with jax.named_scope("kda.rule"):
        beta = jax.nn.sigmoid(gates[..., 2 * rank:])
        # made FLAT (a head's decay repeated over its channels' columns)
        # and viewed by head at the rule's door: see ``_l2_heads``
        g = (
            -jnp.repeat(jnp.exp(kda["a_log"].astype(f32)), dh)
            * jax.nn.softplus(decay_in + kda["dt_bias"].astype(f32))
        ).reshape(b, s, heads, dh)
        q, k, v = (
            qkv[..., i * inner:(i + 1) * inner].reshape(b, s, heads, dh)
            for i in range(3)
        )
        o = gated_delta.gated_delta_rule(
            _l2_heads(q, dh ** -0.5), _l2_heads(k), v, g, beta, **several
        )
    aux = {"kda_readout_ms": jax.lax.stop_gradient(jnp.mean(jnp.square(o)))}
    with jax.named_scope("kda.gate"):
        y = ssd.gated_group_norm(
            o.reshape(b, s, inner), gate_in, kda["norm"]["scale"], heads,
            cfg.norm_eps or 1e-6, norm_before_gate=True,
            gate=jax.nn.sigmoid,
        )
    return matmul(y, kda["w_out"]), aux


def _part_body(
    x, layer, positions, *, letter, cfg: ModelConfig, mesh, attn_fn,
    rng=None, rope=None, return_selected: bool = False,
):
    """One part of a ``layer_pattern`` model, ``x + s part(norm(x))``
    (s = ``cfg.residual_scale``), ``part`` the block of the letter's
    kind (``PARTS``). Returns (x, the block's aux, or {})."""
    kind = PARTS[letter]
    with jax.named_scope(kind.scope or kind.key):
        # (``x`` is float32 behind a part whose output is, until
        # ``_run_pattern`` rounds it)
        h = _norm_block(x, layer["ln"], cfg).astype(cfg.dtype)
        out, aux = kind.run(h, layer, types.SimpleNamespace(
            cfg=cfg, mesh=mesh, positions=positions, attn_fn=attn_fn,
            rng=rng, rope=rope, return_selected=return_selected,
        ))
        x = x + _residual_scaled(out, cfg)
        if mesh is not None:
            x = shd.constrain(x, mesh, "batch", "seq", None)
    return x, aux


def _residual_scaled(out, cfg: ModelConfig):
    """A part's output times ``cfg.residual_scale``, in float32 where
    there is one."""
    if cfg.residual_scale == 1.0:
        return out
    return out.astype(jnp.float32) * cfg.residual_scale


def _run_pattern(
    x, layers, pattern: str, positions, cfg: ModelConfig, mesh, attn_fn,
    rng, first: int = 0, keep_attn: bool = False,
    return_selected: bool = False,
):
    """The parts ``pattern`` names, in its order, each taken as the
    next of its kind in ``layers`` (``_init_pattern``) and run through
    ``_part_body``. A run of one repeated unit (``_pattern_runs``) is a
    ``lax.scan`` over the stacks it owns (``_pattern_stacks``), the
    unit's parts unrolled inside on ONE value of the stream (float32
    behind a part whose output is, rounded at the unit's end) and the
    UNIT under the configured remat (one kept input a repeat); the rest
    is unrolled, each part under the remat and the stream rounded
    behind it: neighbours differ in kind. Returns (x, aux): the
    routed layers' scalars summed, their ``moe_choices`` stacked [E
    layers, B, S, k] in trunk order ({} where no layer routes); the
    block-sparse attentions' ``sparse_attn_out_ms`` as their mean and,
    where ``return_selected``, their selections as ``attn_selected``
    bool [S parts x KV, B, S, U], layer-major and group-minor; every
    kind's ``read`` (``PARTS``) as the mean over its parts, those of a
    scanned run among them (the one thing a run hands out beside x).
    ``first``: the index of the pattern's first part, folded into
    ``rng``."""
    parts = {
        letter: functools.partial(
            _part_body, letter=letter, cfg=cfg, mesh=mesh, attn_fn=attn_fn,
            return_selected=return_selected,
        )
        for letter in set(pattern)
    }
    bodies = {
        letter: _remat(part, cfg, keep_attn) for letter, part in parts.items()
    }
    rope = (
        _rope_tables(positions, cfg.rope_dim, cfg.rope_theta)
        if cfg.pos == "rope" and any(PARTS[c].wants_rope for c in pattern)
        else None
    )
    places = _part_places(pattern)
    seen = dict.fromkeys(bodies, 0)
    auxs, sparse = [], []
    reads = {kind.read: [] for kind in PARTS.values() if kind.read}
    i = 0
    for unit, reps in _pattern_runs(pattern):
        if reps > 1:
            # the run's stacks whole: [repeats, the unit's parts of the
            # kind, ...] a kind
            stacks = {}
            # (sorted: a set's order changes with the process's hash
            # seed, and with it the order of the step's text — a warm
            # start would miss the compile cache every other time)
            for letter in sorted(set(unit)):
                n = unit.count(letter)
                name, _ = places[letter][seen[letter]]
                stacks[letter] = jax.tree.map(
                    lambda t: t.reshape((reps, n) + t.shape[1:]),
                    layers[name],
                )
                seen[letter] += n * reps

            def repeat(x, stacks):
                at = dict.fromkeys(stacks, 0)
                read = {}
                for letter in unit:
                    layer = jax.tree.map(
                        lambda t: t[at[letter]], stacks[letter]
                    )
                    at[letter] += 1
                    x, aux = parts[letter](x, layer, positions, rope=rope)
                    name = PARTS[letter].read
                    if name:
                        read.setdefault(name, []).append(aux[name])
                return x.astype(cfg.dtype), {
                    name: jnp.stack(r) for name, r in read.items()
                }

            x, read = _scan_run(_remat(repeat, cfg, keep_attn), x, stacks)
            for name, r in (read or {}).items():
                reads[name].append(r.reshape(-1))
            i += len(unit) * reps
            continue
        for letter in unit:
            name, at = places[letter][seen[letter]]
            layer = jax.tree.map(lambda t: t[at], layers[name])
            seen[letter] += 1
            r = jax.random.fold_in(rng, first + i) if rng is not None else None
            x, aux = bodies[letter](x, layer, positions, rng=r, rope=rope)
            x = x.astype(cfg.dtype)
            i += 1
            kind = PARTS[letter]
            if kind.read:
                reads[kind.read].append(aux[kind.read][None])
            elif aux:
                (sparse if kind.selects else auxs).append(aux)
    out = {
        name: jnp.mean(jnp.concatenate(r)) for name, r in reads.items() if r
    }
    if sparse:
        out["sparse_attn_out_ms"] = jnp.mean(
            jnp.stack([a["sparse_attn_out_ms"] for a in sparse])
        )
        if "attn_selected" in sparse[0]:
            out["attn_selected"] = jnp.concatenate(
                [a["attn_selected"] for a in sparse]
            )
    if not auxs:
        return x, out
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *auxs)
    choices = stacked.pop("moe_choices")
    return x, {
        **jax.tree.map(lambda a: a.sum(0), stacked), "moe_choices": choices,
        **out,
    }


def _scan_run(repeat, x, stacks):
    """A run of repeats of one unit: ``repeat(x, a repeat's slices of
    the stacks) -> (x, what the repeat's mixers hand out, by name)``
    over the stacks' leading axis. Returns (x, those stacked, or
    None)."""
    return jax.lax.scan(repeat, x, stacks)


def _remat_body(cfg: ModelConfig, mesh, attn_fn, fp8_layers,
                return_selected: bool = False, keep_attn: bool = False,
                kind: str = ""):
    """``_layer_body`` bound to the model (and to one ``kind`` of
    ``cfg.layer_types``) and wrapped in the configured rematerialisation
    policy: what every layer of the trunk, dense or routed, and the
    prediction module's block run through."""
    body = functools.partial(
        _layer_body,
        cfg=cfg,
        mesh=mesh,
        attn_fn=attn_fn,
        return_selected=return_selected,
        kind=kind,
        # the "current" sentinel must be BAKED into the partial, not
        # passed at call time: jax.checkpoint (below) treats call-time
        # args as traceable values and a str is not a valid JAX type
        **({"fp8": "current"} if fp8_layers == "current" else {}),
    )
    return _remat(body, cfg, keep_attn)


def _kept_names(cfg: ModelConfig, keep_attn: bool):
    """The named residuals ``remat: full`` keeps. It recomputes the
    layer but what is quadratic to remake and linear to hold: a
    selecting model's selection (int8 [B, S, S] a layer, against scoring
    and cutting every query's keys again) and its alignment term's
    derivative (qi's, ki's and w's shapes, against scoring every
    attention pair again), and, where ``keep_attn``
    (``keeps_attention_output``), the flash kernel's output and row
    statistics (``flash_lse`` as numbers, [B, H, S] float32)."""
    names = ()
    if cfg.selects_keys:
        names += ("attn_selected", "attn_align_grad")
    if cfg.selects_blocks:
        names += ("attn_selected",)
    if keep_attn:
        names += ("flash_out", "flash_lse")
    return names


def _remat(body, cfg: ModelConfig, keep_attn: bool = False):
    """``body`` (a layer: ``_layer_body`` or ``_part_body``, bound to
    its model) under the configured rematerialisation policy."""
    if cfg.remat != "full":
        return body
    names = _kept_names(cfg, keep_attn)
    if names:
        return jax.checkpoint(body, policy=cp.save_only_these_names(*names))
    return jax.checkpoint(body)


# the keys a query's forward kernel EXECUTES (``pallas_attention.
# forward_keys``: whole key tiles, the diagonal's and a window's edge
# blocks entire — not the span the mask lets through) from which ``full``
# keeps the flash kernel's output: remaking costs 4 · keys · D operations
# a (query, head), keeping 2 · D + 4 bytes, so the time bought per byte
# grows with the keys executed. The chip says, a gigabyte of residuals:
# 28 ms at 1,024 executed keys (GPT-2 XL, 15.3 of 16.9 GB in use: no
# room), 29 at 2,560 (OLMoE), 46-72 at 3,840 and 4,608 (PERF.md section
# 6, PR 42) and 37 at 2,880 (Trinity-Mini's window layers at 16,384
# tokens, a band of three tiles of 1,024 for 1,920 keys attended to:
# four calls of 6.13 ms gone, 3.9 ms of passes over the kept output and
# its statistics come, 0.545 GB; PERF.md section 6, PR 61); no cell
# lies between 1,024 and 2,560, so where the line is between them is
# not measured.
KEEP_ATTN_SPAN = 2048


def keeps_attention_output(cfg: ModelConfig, s: int, attn_impl: str = "auto",
                           mesh=None, kind: str = "") -> bool:
    """Whether ``remat: full`` keeps the attention kernel's output
    (``flash_out``, and ``flash_lse`` as numbers) at sequence length
    ``s`` in a layer of ``kind`` (``cfg.layer_types``' letter; the
    decision is per kind inside one step) and does not run the kernel
    again in the recomputed forward: the attention runs the Pallas
    kernels, and the keys a query's forward kernel executes at the
    model's tile and that kind's window (``forward_keys``, the kernels'
    own count; a selecting model's ``_sel`` kernels run every causal
    block) are at least ``KEEP_ATTN_SPAN``. Decided from the shape and
    not from the memory the chip has left: what a step needs is known
    only once it is compiled."""
    from dlrover_tpu.ops.pallas_attention import forward_keys

    return (
        cfg.remat == "full"
        and _resolve_attn_impl(attn_impl, mesh) == "flash"
        and s % 128 == 0  # the kernels' tiling (``_fit_block``)
        and forward_keys(
            s, s, cfg.attn_block_q, cfg.attn_block_k, cfg.causal,
            cfg.kind_window(kind),
        ) >= KEEP_ATTN_SPAN
    )


def attention_kinds(cfg: ModelConfig):
    """The kinds of attention layer a model has: the letters of
    ``cfg.layer_types``, or the one kind ""."""
    return tuple(sorted(set(cfg.layer_types))) or ("",)


def kept_attention_layers(cfg: ModelConfig, s: int, attn_impl: str = "auto",
                          mesh=None) -> int:
    """How many layers' attention output a step at sequence length
    ``s`` keeps (``keeps_attention_output``, kind by kind; the
    prediction module's layer among them)."""
    if not cfg.layer_types:
        return cfg.n_attention_layers * keeps_attention_output(
            cfg, s, attn_impl, mesh
        )
    return sum(
        cfg.layer_types.count(kind)
        for kind in attention_kinds(cfg)
        if keeps_attention_output(cfg, s, attn_impl, mesh, kind)
    )


def _resolve_attn_impl(attn_impl: str, mesh) -> str:
    """What ``attn_impl == "auto"`` stands for on this mesh and host."""
    if attn_impl != "auto":
        return attn_impl
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        # a sequence-parallel mesh MUST use the shard_map sp paths:
        # letting GSPMD partition a plain attention over seq-sharded
        # q/k/v ends in "involuntary full rematerialization" (a
        # replicate-then-repartition of the score matmul operands)
        return "ulysses"
    # flash (pallas) on real accelerators; the kernel's interpret path
    # is far slower than plain jnp on CPU
    return "reference" if device.on_cpu() else "flash"


def alignment_tiles(cfg: ModelConfig, s: int):
    """The alignment kernel's (chunk, query rows, key rows) for a
    sequence of ``s`` where a model whose attention runs the Pallas
    kernels takes the term through ``ops/pallas_align.py``, None where
    it takes the jnp rule: off the TPU, or shapes the tiles do not
    fit."""
    from dlrover_tpu.ops import pallas_align

    chunk = min(cfg.index_chunk, s)
    tiles = pallas_align.tiles(
        s, chunk, cfg.index_n_heads, cfg.index_head_dim
    )
    return None if tiles is None else (chunk, *tiles)


def _train_only_guard(cfg: ModelConfig, fn: str):
    """The cache, paged, pipeline and generate paths scan ONE stack of
    plain-attention layers; a model they would run wrongly is refused
    by name."""
    if cfg.train_only:
        raise ValueError(
            f"{fn} cannot run {cfg.name}: {cfg.train_only}. Only the "
            "training path (forward, loss_fn, the train step) does"
        )


def run_trunk(
    x: jax.Array,          # [B, S, D] embedded inputs
    layers: Params,        # stacked per-layer params (leading axis L)
    positions: jax.Array,  # [B, S]
    cfg: ModelConfig,
    mesh=None,
    attn_fn=None,
    rng: Optional[jax.Array] = None,
    fp8_layers=None,
    dense_layers: Optional[Params] = None,
    return_selected: bool = False,
    keep_attn: Tuple[str, ...] = (),
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Run the stacked transformer layers: remat policy, pp pipelining,
    MoE aux-loss accumulation. Shared by the decoder and the ViT trunk
    (models/vision.py) so policies stay in one place.

    ``dense_layers``: the dense prefix of a routed model, stacked by
    itself; it runs first, then the scan over ``layers``, both through
    the same ``_layer_body`` under the same remat.

    ``fp8_layers``: stacked per-layer fp8 delayed-scaling states
    (init_fp8_states; leading axis L) — scanned alongside the layer
    params — or the string "current" for stateless current scaling
    (the only sound fp8 mode under pp; see the pp guard below). Dense
    layers only (MoE experts stay bf16).

    ``return_selected`` (a model that selects its keys): every layer's
    selection rides out in the aux, stacked bool [L, B, S, S].

    ``keep_attn``: the KINDS of attention layer (``attention_kinds``:
    "" in a model of one kind, else ``layer_types``' letters) whose
    flash output ``remat: full`` keeps (``keeps_attention_output``;
    ``attn_fn`` was told, ``lse_rows``). A ``layer_types`` model's
    ``attn_fn`` takes ``kind=``, each kind runs its own remat-wrapped
    body, and a stack is scanned a period of kinds at a time
    (``_run_periods``).

    A ``layer_pattern`` model's ``layers`` are its stacks kind by kind,
    visited in the pattern's order (``_run_pattern``).

    Returns (hidden states [B,S,D] — pre-final-norm, aux losses).
    """
    if cfg.layer_pattern:
        if mesh is not None and mesh.shape.get("pp", 1) > 1:
            _train_only_guard(cfg, "the pipeline")
        # which parts the trunk runs, and how. Trace time, values
        set_counter(
            "pattern.scanned_parts", _scanned_parts(cfg.layer_pattern)
        )
        for letter, kind in PARTS.items():
            n = cfg.layer_pattern.count(letter)
            if n and kind.counters:
                for name, value in kind.counters(
                    cfg, mesh, n, x.shape[1]
                ).items():
                    set_counter(name, value)
        x, aux = _run_pattern(
            x, layers, cfg.layer_pattern, positions, cfg, mesh, attn_fn,
            rng, keep_attn="" in keep_attn, return_selected=return_selected,
        )
        zero = jnp.zeros([], jnp.float32)
        return x, {"moe_lb_loss": zero, "moe_z_loss": zero, **aux}
    # one remat-wrapped body a kind of attention layer; a model of one
    # kind has the one, ``_run_periods`` picks by kind
    bodies = {
        kind: _remat_body(
            cfg, mesh, attn_fn, fp8_layers, return_selected,
            kind in keep_attn, kind,
        )
        for kind in attention_kinds(cfg)
    }
    body = bodies.get("")

    zero_aux = {
        "moe_lb_loss": jnp.zeros([], jnp.float32),
        "moe_z_loss": jnp.zeros([], jnp.float32),
    }
    pp = mesh.shape.get("pp", 1) if mesh is not None else 1
    v = max(1, getattr(cfg, "pp_interleave", 1))
    if pp > 1 and fp8_layers is not None and fp8_layers != "current":
        # delayed-scaling state CANNOT thread a pipeline schedule: the
        # pipeline runs every microbatch through the same layer inside
        # one forward, so the state's cotangent is the SUM of m updated
        # amax histories (plus bubble-tick pushes) — not a state. The
        # train step passes the "current" sentinel on pp meshes instead
        # (stateless per-tensor scaling, TE's Float8CurrentScaling).
        raise ValueError(
            "pipeline meshes use current-scaling fp8 (pass "
            "fp8_states='current'); delayed-scaling state dicts cannot "
            "thread a pipeline schedule"
        )
    if pp > 1:
        from dlrover_tpu.parallel.pipeline import pipeline_apply

        _train_only_guard(cfg, "the pipeline")
        # router aux losses are not collected across pipeline stages
        # (fp8="current" rides inside the body partial when set)
        aux = zero_aux
        x = pipeline_apply(
            lambda c, layer, pos: body(c, layer, pos)[0],
            layers,
            x,
            positions,
            mesh,
            num_microbatches=cfg.pp_microbatches or None,
            interleave=v,
            boundary_dtype=cfg.pp_boundary_dtype,
        )
    else:
        n_layers = jax.tree.leaves(layers)[0].shape[0]
        if v > 1:
            # an interleave-stacked checkpoint: storage order is the
            # pipeline's chunk layout — apply layers in semantic order
            # so this is the SAME network the pp mesh trains
            from dlrover_tpu.parallel.pipeline import semantic_layer_perm

            if not cfg.pp_stages:
                raise ValueError(
                    "pp_interleave>1 needs cfg.pp_stages to recover the "
                    "layer order off the pipeline mesh"
                )
            if n_layers % (cfg.pp_stages * v):
                raise ValueError(
                    f"n_layer={n_layers} not divisible by "
                    f"pp_stages·pp_interleave={cfg.pp_stages}·{v}: the "
                    "interleaved layer layout is undefined (jnp.take "
                    "would silently truncate the stack)"
                )
            perm = jnp.asarray(
                semantic_layer_perm(n_layers, cfg.pp_stages, v)
            )
            layers = jax.tree.map(lambda t: jnp.take(t, perm, 0), layers)

        # rope tables hoisted out of the layer scan: one [B,S,1,D/2]
        # cos/sin build per forward instead of one per layer, and one a
        # rope kind that some layer is turned by (``cfg.rope_kinds``: a
        # model of one kind builds one). Passed as a call-time kwarg
        # (tracers through jax.checkpoint, like rng) so the
        # remat-wrapped body needn't close over them.
        tables = {
            name: _rope_tables(
                positions, cfg.rope_dim, cfg.rope_theta,
                cfg.rope_scaling if name == "scaled" else None,
            )
            for name in cfg.rope_kinds
        }
        set_counter("attn.rope_tables", len(tables))
        first = 0
        if dense_layers is not None:
            # the prefix's aux is zeros: a dense layer routes nothing
            first = jax.tree.leaves(dense_layers)[0].shape[0]
        if cfg.layer_types:
            kinds = cfg.layer_types
            # each kind of layer its own (None: a kind without positions)
            ropes = {
                kind: tables.get(cfg.kind_rope(kind)) for kind in bodies
            }
            if first:
                x, _ = _run_periods(
                    bodies, x, dense_layers, kinds[:first], positions, rng,
                    ropes, 0,
                )
            x, auxs = _run_periods(
                bodies, x, layers, kinds[first:], positions, rng, ropes,
                first,
            )
        else:
            rope = tables.get(cfg.kind_rope())
            if first:
                x, _ = _run_stack(
                    body, x, dense_layers, positions, rng, rope, 0, first
                )
            x, auxs = _run_stack(
                body, x, layers, positions, rng, rope, first, n_layers,
                fp8_layers=fp8_layers,
            )
        # the expert ids and the selected keys are per layer, not a sum:
        # stacked [L, B, S, k] and [L, B, S, S]
        per_layer = {
            name: auxs.pop(name)
            for name in ("moe_choices", "attn_selected") if name in auxs
        }
        aux = {**jax.tree.map(lambda a: a.sum(), auxs), **per_layer}
    return x, aux


def _run_stack(body, x, layers, positions, rng, rope, first, n_layers,
               fp8_layers=None):
    """``body`` over one stack of ``n_layers`` equal layers, the
    ``first``-th of the trunk onward (the index folds into ``rng``).
    Returns (x, every layer's aux stacked on axis 0)."""
    index = jnp.arange(first, first + n_layers)
    if fp8_layers is not None and fp8_layers != "current":

        def scan_fn8(carry, inp):
            layer, fp8, idx = inp
            r = (
                jax.random.fold_in(rng, idx)
                if rng is not None
                else None
            )
            out, aux = body(
                carry, layer, positions, rng=r, fp8=fp8, rope=rope
            )
            return out, aux

        return jax.lax.scan(scan_fn8, x, (layers, fp8_layers, index))
    if shd.unroll_layer_scans():
        # hybrid-mesh update-sharding region: the stacked layer
        # params are auto-axis-sharded (fsdp/tp) and the 0.4.x
        # partitioner check-fails on a scan over them inside a
        # partial-manual region — unroll the layer loop instead
        aux_list = []
        for i in range(n_layers):
            layer = jax.tree.map(lambda t: t[i], layers)
            r = (
                jax.random.fold_in(rng, first + i)
                if rng is not None
                else None
            )
            x, a_i = body(x, layer, positions, rng=r, rope=rope)
            aux_list.append(a_i)
        return x, jax.tree.map(lambda *ls: jnp.stack(ls), *aux_list)

    # fp8="current" (when set) is baked into the body partial
    def scan_fn(carry, inp):
        layer, idx = inp
        r = (
            jax.random.fold_in(rng, idx)
            if rng is not None
            else None
        )
        out, aux = body(carry, layer, positions, rng=r, rope=rope)
        return out, aux

    return jax.lax.scan(scan_fn, x, (layers, index))


def _period(kinds: str) -> int:
    """Length of the shortest prefix that ``kinds`` repeats whole."""
    n = len(kinds)
    return next(
        p for p in range(1, n + 1)
        if n % p == 0 and kinds[:p] * (n // p) == kinds
    )


def _run_periods(bodies, x, layers, kinds: str, positions, rng, ropes, first):
    """One stack of layers that differ in attention KIND and in nothing
    else (``cfg.layer_types``; ``kinds`` this stack's letters,
    ``bodies`` kind -> its remat-wrapped body, ``ropes`` kind -> its
    rope tables, None where it has none): the parameters stay one
    stack, viewed as [periods, period, ...], and the scan runs a whole
    period of ``kinds`` a step, its layers unrolled. Returns what
    ``_run_stack`` does."""
    n, p = len(kinds), _period(kinds)
    index = jnp.arange(first, first + n).reshape(n // p, p)
    grouped = jax.tree.map(
        lambda t: t.reshape((n // p, p) + t.shape[1:]), layers
    )

    def scan_fn(carry, inp):
        group, idx = inp
        auxs = []
        for j, kind in enumerate(kinds[:p]):
            layer = jax.tree.map(lambda t: t[j], group)
            r = jax.random.fold_in(rng, idx[j]) if rng is not None else None
            carry, aux = bodies[kind](
                carry, layer, positions, rng=r, rope=ropes[kind]
            )
            auxs.append(aux)
        return carry, jax.tree.map(lambda *ls: jnp.stack(ls), *auxs)

    x, auxs = jax.lax.scan(scan_fn, x, (grouped, index))
    return x, jax.tree.map(lambda a: a.reshape((n,) + a.shape[2:]), auxs)


def init_fp8_states(cfg: ModelConfig):
    """Stacked per-layer fp8 delayed-scaling states for every linear in
    the layer body: the attention q/k/v/o projections AND the MLP GEMMs
    (the reference wires TE fp8 through its linears generally —
    atorch/auto/opt_lib/amp_optimization.py:197).

    One {amax_x, amax_w, amax_g} history set per projection per layer
    (leading axis L), matching run_trunk's scan and the pipeline's
    per-layer stacking. Lives in the train state under ``state["fp8"]``;
    the step's gradient w.r.t. it IS the updated state (ops/fp8.py
    convention).

    MoE configs: the delayed states cover the attention projections
    only — the expert FFN GEMMs run stateless CURRENT scaling
    (ops/fp8.py:fp8_batched_dot_current via moe.py), because per-expert
    token routing changes which tokens each weight sees every step,
    and a routing-dependent amax history is exactly the stale-scale
    hazard delayed scaling is supposed to avoid.
    """
    from dlrover_tpu.ops.fp8 import init_fp8_state

    if cfg.n_experts > 0:
        mlp_names = ()
    elif cfg.act == "swiglu":
        mlp_names = ("gate", "up", "down")
    else:
        mlp_names = ("up", "down")
    names = ("wq", "wk", "wv", "wo") + mlp_names
    one = init_fp8_state()
    return {
        name: jax.tree.map(
            lambda h: jnp.tile(h[None], (cfg.n_layer, 1)), one
        )
        for name in names
    }


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: ModelConfig,
    mesh=None,
    positions: Optional[jax.Array] = None,
    attn_impl: str = "auto",
    rng: Optional[jax.Array] = None,
    return_aux: bool = False,
    features_only: bool = False,
    prefix_len: Optional[jax.Array] = None,
    fp8_states=None,
    return_selected: Optional[bool] = None,
):
    """tokens:[B,S] int32 → logits:[B,S,vocab] float32.

    ``return_aux=True`` additionally returns per-model MoE router losses
    summed over layers ({moe_lb_loss, moe_z_loss}) and, for a routed
    model, ``moe_choices``: the expert ids every token was sent to,
    int32 [n_layer, B, S, k] (not under pp); for a model that selects
    its keys ``indexer_loss`` (the layers' mean KL summed, before its
    coefficient) and ``attn_selected``, bool [n_layer, B, S, S], true
    where query t attended to key s (``return_selected=False`` leaves
    the masks out: ``loss_fn`` does, a train step stacks none); for a
    model that selects BLOCKS past ``select_dense_len`` tokens
    ``attn_selected`` is bool [S parts x KV heads, B, S, S /
    sparse_block], a row a KV head's selection, layer-major and
    group-minor, true at the blocks query t attended into, and
    ``sparse_attn_out_ms`` (always) the S parts' mean square output,
    and for one with lightning parts ``lightning_fast_out_ms``, the mean
    square read-out of their fastest quarter of heads;
    ``rng`` enables switch-gating jitter during training. ``features_only=True`` returns
    the final-norm hidden states [B,S,D] instead of logits (value/reward
    heads attach here). ``prefix_len`` [B] int32 (prefix-LM configs):
    keys before prefix_len[b] are bidirectionally visible — GLM-style
    blank infilling; supported on every attention path (flash,
    reference, ring, ulysses).
    """
    dt = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    # named scopes mark the step's layers in every HLO op_name, so a
    # device trace can be split by layer (observability/runtime_timer)
    with jax.named_scope("embed"):
        x = _embed_tokens(params, tokens, mesh, dt)
        if cfg.pos == "learned":
            x = x + jnp.take(
                params["pos_embed"]["table"], positions, axis=0
            ).astype(dt)
        if cfg.scale_embedding:
            # in float32: sqrt(d) has no exact bf16
            x = (x.astype(jnp.float32) * cfg.d_model ** 0.5).astype(dt)
        if cfg.scale_emb != 1.0:
            x = (x.astype(jnp.float32) * cfg.scale_emb).astype(dt)
        if mesh is not None:
            x = shd.constrain(x, mesh, "batch", "seq", None)

    attn_impl = _resolve_attn_impl(attn_impl, mesh)

    if cfg.qk_norm and mesh is not None and mesh.shape.get("tp", 1) > 1:
        raise ValueError(
            "qk_norm takes its statistic over all heads of the q and k "
            "projections, and tp shards the heads axis: run this model "
            "with tp=1 (dp/fsdp/sp/ep meshes are fine)"
        )

    if fp8_states is not None and cfg.train_only:
        raise ValueError(
            f"fp8 states are stacked for one kind of plain-attention "
            f"layer; {cfg.name} has {cfg.train_only}"
        )

    if cfg.prefix_lm and prefix_len is None:
        # a GLM-family model silently training fully-causal is the worst
        # failure mode (looks healthy, learns the wrong objective) —
        # callers wanting causal behavior pass explicit zeros
        raise ValueError(
            "cfg.prefix_lm is set but no prefix_len was provided "
            "(loss_fn reads batch['prefix_len']); pass "
            "jnp.zeros([batch], int32) for fully-causal behavior"
        )

    # the kinds of layer whose remat policy keeps the kernel's output,
    # and with it its statistics as numbers (``lse_rows``)
    keep_attn = tuple(
        kind for kind in attention_kinds(cfg)
        if keeps_attention_output(cfg, s, attn_impl, mesh, kind)
    )
    # which path the program took. Trace time, values: a retrace, or the
    # forward-only program of the same configuration, sets the same
    # numbers. How many layers' attention output is kept (the prediction
    # module's among them), and how many layers of each kind there are
    set_counter(
        "attn.output_kept", kept_attention_layers(cfg, s, attn_impl, mesh)
    )
    if cfg.layer_types:
        rules = [ATTN_KINDS[kind] for kind in cfg.layer_types]
        set_counter("attn.window_layers", sum(r.window for r in rules))
        set_counter("attn.full_layers", sum(not r.window for r in rules))
        set_counter(
            "attn.scaled_rope_layers", sum(r.rope == "scaled" for r in rules)
        )

    def attn_fn(q, k, v, selected=None, kind=""):
        lse_rows = kind in keep_attn
        window = cfg.kind_window(kind)
        if selected is not None:
            # (out, lse [B, H, S] detached, whether the Pallas kernels
            # are this model's attention) over each query's selection
            if attn_impl == "reference":
                out, lse = mha_reference(
                    q, k, v, causal=True, selected=selected,
                    return_lse=True,
                )
                return out, jax.lax.stop_gradient(lse), False
            if attn_impl != "flash":
                raise ValueError(
                    f"attn_impl {attn_impl!r} takes no selection of keys "
                    "(flash and reference do)"
                )
            from dlrover_tpu.ops.pallas_attention import flash_attention

            return *flash_attention(
                q, k, v, causal=True, block_q=cfg.attn_block_q,
                block_k=cfg.attn_block_k, selected=selected,
                lse_rows=lse_rows,
            ), True
        if attn_impl == "ring":
            from dlrover_tpu.parallel.sequence import ring_attention

            return ring_attention(
                q,
                k,
                v,
                mesh,
                causal=cfg.causal,
                block_q=cfg.attn_block_q,
                block_k=cfg.attn_block_k,
                prefix_len=prefix_len,
                window=window,
            )
        if attn_impl == "ulysses":
            from dlrover_tpu.ops.pallas_attention import flash_attention
            from dlrover_tpu.parallel.sequence import ulysses_attention

            # the head-sharded inner attention is ordinary full attention
            # — run it through the flash kernel (falls back off-TPU)
            return ulysses_attention(
                q,
                k,
                v,
                mesh,
                causal=cfg.causal,
                attn_fn=functools.partial(
                    flash_attention,
                    causal=cfg.causal,
                    block_q=cfg.attn_block_q,
                    block_k=cfg.attn_block_k,
                    lse_rows=lse_rows,
                ),
                prefix_len=prefix_len,
                window=window,
            )
        if attn_impl == "reference":
            return mha_reference(
                q, k, v, causal=cfg.causal, prefix_len=prefix_len,
                window=window,
            )
        from dlrover_tpu.ops.pallas_attention import flash_attention

        return flash_attention(
            q,
            k,
            v,
            causal=cfg.causal,
            block_q=cfg.attn_block_q,
            block_k=cfg.attn_block_k,
            prefix_len=prefix_len,
            window=window,
            lse_rows=lse_rows,
        )

    x, aux = run_trunk(
        x,
        params["layers"],
        positions,
        cfg,
        mesh=mesh,
        attn_fn=attn_fn,
        rng=rng,
        fp8_layers=fp8_states,
        dense_layers=params.get("dense_layers"),
        return_selected=(cfg.selects_keys or cfg.selects_blocks) and (
            return_aux if return_selected is None else return_selected
        ),
        keep_attn=keep_attn,
    )
    if cfg.n_mtp_module and return_aux:
        # the module reads the trunk's output BEFORE the final norm
        aux = _mtp_module(
            params, x, tokens, positions, cfg, mesh, attn_fn, rng, aux,
            "" in keep_attn,
        )

    with jax.named_scope("head_loss"):
        x = _norm_block(x, params["final_norm"], cfg)
        if features_only:
            return (x, aux) if return_aux else x
        w_out, head_scale = head_weight_scale(params, cfg)
        logits = jnp.einsum(
            "bsd,dv->bsv", x, w_out.astype(dt),
            preferred_element_type=jnp.float32,
        )
        if head_scale != 1.0:
            logits = logits * head_scale
    return (logits, aux) if return_aux else logits


def next_tokens(tokens: jax.Array) -> jax.Array:
    """``tokens`` [B, S] one place on: position i holds t_{i+1}. The last
    position has no successor among the tokens and repeats
    ``tokens[:, -1]``; whoever uses it masks that position out."""
    return jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)


def _mtp_module(
    params, h, tokens, positions, cfg: ModelConfig, mesh, attn_fn, rng,
    aux, keep_attn: bool = False,
):
    """The multi-token-prediction module (DeepSeek-V3 §2.2; the layout
    of ``glm4_moe_lite``'s ``num_nextn_predict_layers`` weights):

        h'_i = W_eh [norm(emb(t_{i+1})) ‖ norm(h_i)]
        one block of the trunk's routed kind, or the layers
        ``mtp_pattern`` names where the trunk's are one part each

    with ``h`` the trunk's output before the final norm, the SHARED
    token table, and no position table added (the block has rope). The
    block's output goes through the module's own norm
    (``shared_head.norm``) and the shared head in ``_loss_from_head``,
    against t_{i+2}. Returns ``aux`` with ``mtp_features`` [B, S, D]
    added, the block's router terms summed in and its expert ids as the
    last row of ``moe_choices``."""
    m = params["mtp"]
    with jax.named_scope("mtp"):
        dt = h.dtype
        e = _embed_tokens(params, next_tokens(tokens), mesh, dt)
        z = jnp.concatenate(
            [_norm_block(e, m["enorm"], cfg), _norm_block(h, m["hnorm"], cfg)],
            axis=-1,
        ) @ m["eh_proj"].astype(dt)
        if mesh is not None:
            z = shd.constrain(z, mesh, "batch", "seq", None)
        if cfg.layer_pattern:
            z, block_aux = _run_pattern(
                z, m["block"], cfg.mtp_pattern, positions, cfg, mesh,
                attn_fn, rng, first=cfg.n_layer, keep_attn=keep_attn,
            )
        else:
            body = _remat_body(cfg, mesh, attn_fn, None, keep_attn=keep_attn)
            rope = (
                _rope_tables(positions, cfg.rope_dim, cfg.rope_theta)
                if cfg.pos == "rope"
                else None
            )
            r = (
                jax.random.fold_in(rng, cfg.n_layer)
                if rng is not None else None
            )
            z, block_aux = body(z, m["block"], positions, rng=r, rope=rope)
            if "moe_choices" in block_aux:
                block_aux["moe_choices"] = block_aux["moe_choices"][None]
    aux = dict(aux)
    choices = block_aux.pop("moe_choices", None)
    for name, value in block_aux.items():
        aux[name] = aux[name] + value
    if choices is not None:
        aux["moe_choices"] = jnp.concatenate(
            [aux["moe_choices"], choices], axis=0
        )
    aux["mtp_features"] = z
    return aux


def head_weight_scale(params: Params, cfg: ModelConfig):
    """(lm-head weight [D, V], static logit multiplier).

    The muP MuReadout multiplier applies ONLY for tied embeddings, where
    the readout weight is the (input-class) embedding and cannot carry
    the output-class init/lr scaling itself. An untied lm_head gets that
    scaling from rescale_init + mu_adam instead; giving it the
    multiplier too would doubly suppress the logits.
    """
    if cfg.tie_embeddings:
        # tied_head_table is the table itself except inside the
        # update-sharding shard_map, where it splits the head cotangent
        # off the lookup's (see parallel/sharding.py)
        w = shd.tied_head_table(params["embed"]["tokens"]).T
    else:
        w = params["lm_head"]["w"]
    scale = cfg.logit_scale
    if cfg.mup_base_width and cfg.tie_embeddings:
        scale *= cfg.mup_base_width / cfg.d_model
    return w, scale


def loss_fn(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
    mesh=None,
    z_loss: float = 0.0,
    attn_impl: str = "auto",
    rng: Optional[jax.Array] = None,
    fp8_states=None,
    denom: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """batch: {"tokens": [B,S], "targets": [B,S], optional "mask": [B,S],
    optional "prefix_len": [B] (prefix-LM; mask usually zeroes the prefix
    targets so loss falls only on the causal tail)}.

    ``denom`` overrides the loss normalizer (default: this batch's mask
    sum). The update-sharding step passes the psum'd GLOBAL token count
    so per-rank cotangents match the data-parallel program exactly."""
    use_fused = cfg.fused_ce and not (
        mesh is not None and mesh.shape.get("tp", 1) > 1
    )
    if use_fused:
        feats, moe_aux = forward(
            params,
            batch["tokens"],
            cfg,
            mesh=mesh,
            attn_impl=attn_impl,
            rng=rng,
            return_aux=True,
            features_only=True,
            prefix_len=batch.get("prefix_len"),
            fp8_states=fp8_states,
            return_selected=False,
        )
    else:
        logits, moe_aux = forward(
            params,
            batch["tokens"],
            cfg,
            mesh=mesh,
            attn_impl=attn_impl,
            rng=rng,
            return_aux=True,
            prefix_len=batch.get("prefix_len"),
            fp8_states=fp8_states,
            return_selected=False,
        )
    with jax.named_scope("head_loss"):
        return _loss_from_head(
            params, batch, cfg, z_loss, denom, moe_aux,
            feats=feats if use_fused else None,
            logits=None if use_fused else logits,
        )


def _token_nll(params, cfg: ModelConfig, targets, feats=None, logits=None):
    """(log-partition, target logit, argmax) per position through the
    output head: fused linear cross-entropy over ``feats``, or plain
    log-softmax over ``logits``."""
    if feats is not None:
        from dlrover_tpu.ops.fused_ce import fused_linear_ce

        w_out, head_scale = head_weight_scale(params, cfg)
        bv = min(
            cfg.ce_block_v, (cfg.vocab_size + 127) // 128 * 128
        )
        return fused_linear_ce(feats, w_out, targets, head_scale, bv)
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt_logit = jnp.take_along_axis(
        logits, targets[..., None], axis=-1
    )[..., 0]
    return logz, tgt_logit, jnp.argmax(logits, -1)


def _loss_from_head(
    params, batch, cfg: ModelConfig, z_loss, denom, moe_aux,
    feats=None, logits=None,
):
    """The head and the loss of ``loss_fn``: fused linear
    cross-entropy over ``feats``, or plain log-softmax over ``logits``."""
    targets = batch["targets"]
    logz, tgt_logit, amax = _token_nll(params, cfg, targets, feats, logits)

    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(targets, dtype=jnp.float32)
    mask = mask.astype(jnp.float32)
    nll = (logz - tgt_logit) * mask
    if denom is None:
        denom = jnp.maximum(mask.sum(), 1.0)
    loss = nll.sum() / denom
    metrics = {"loss": loss, "tokens": mask.sum()}
    if z_loss > 0.0:
        zl = z_loss * jnp.sum((logz * mask) ** 2) / denom
        loss = loss + zl
        metrics["z_loss"] = zl
    if cfg.n_experts > 0 and (cfg.moe_aux_coef or cfg.moe_z_coef):
        lb = cfg.moe_aux_coef * moe_aux["moe_lb_loss"]
        rz = cfg.moe_z_coef * moe_aux["moe_z_loss"]
        loss = loss + lb + rz
        metrics["moe_lb_loss"] = lb
        metrics["moe_z_loss"] = rz
    if cfg.selects_keys:
        # the indexer's own term: it alone trains the indexer, and
        # trains nothing else
        il = cfg.indexer_loss_coef * moe_aux["indexer_loss"]
        loss = loss + il
        metrics["indexer_loss"] = il
    if "sparse_attn_out_ms" in moe_aux:
        # no term of the objective: the block-sparse attentions' mean
        # square output, what a selection the attention ignores moves
        metrics["sparse_attn_out_ms"] = moe_aux["sparse_attn_out_ms"]
    if "lightning_fast_out_ms" in moe_aux:
        # nor this: the lightning parts' fast heads' mean square
        # read-out, what a running log-decay of too few bits moves
        metrics["lightning_fast_out_ms"] = moe_aux["lightning_fast_out_ms"]
    if "gdn_readout_ms" in moe_aux:
        # nor this: the delta-rule mixers' mean square read-out before
        # the per-head norm, what a uniform scale of it moves
        metrics["gdn_readout_ms"] = moe_aux["gdn_readout_ms"]
    if "kda_readout_ms" in moe_aux:
        metrics["kda_readout_ms"] = moe_aux["kda_readout_ms"]
    # run_trunk (and the prediction module) summed these over the
    # routed blocks; reported as the mean over them
    blocks = cfg.n_routed_layer + cfg.n_mtp_module
    if "moe_max_load" in moe_aux:
        # rows of the fullest expert over the mean rows an expert gets
        metrics["moe_max_load"] = moe_aux["moe_max_load"] / blocks
    if "moe_held_rows" in moe_aux:
        # rows the experts held here received
        metrics["moe_held_rows"] = moe_aux["moe_held_rows"] / blocks
    if "mtp_features" in moe_aux:
        with jax.named_scope("mtp"):
            # the module at position i predicts t_{i+2}: the targets one
            # place on. The last position has none and is masked out
            mtp_mask = next_tokens(mask).at[:, -1].set(0.0)
            m_feats = _norm_block(
                moe_aux["mtp_features"], params["mtp"]["norm"], cfg
            )
            m_logits = None
            if feats is None:
                w_out, head_scale = head_weight_scale(params, cfg)
                m_logits = head_scale * jnp.einsum(
                    "bsd,dv->bsv", m_feats, w_out.astype(m_feats.dtype),
                    preferred_element_type=jnp.float32,
                )
                m_feats = None
            m_logz, m_tgt, _ = _token_nll(
                params, cfg, next_tokens(targets), m_feats, m_logits
            )
            mtp = cfg.mtp_loss_coef * (
                ((m_logz - m_tgt) * mtp_mask).sum()
                / jnp.maximum(mtp_mask.sum(), 1.0)
            )
        loss = loss + mtp
        metrics["mtp_loss"] = mtp
    acc = (amax == targets).astype(jnp.float32) * mask
    metrics["accuracy"] = acc.sum() / denom
    return loss, metrics


# ---------------------------------------------------------------------------
# KV-cache incremental decoding (inference path)
# ---------------------------------------------------------------------------


def init_kv_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype=None
) -> Dict:
    """Per-layer stacked K/V buffers for incremental decoding.

    ``dtype`` defaults to the model compute dtype; the serving tier
    passes an explicit dtype when it gathers reference bf16 buffers
    next to its int8 page pools."""
    _train_only_guard(cfg, "init_kv_cache")
    dt = jnp.dtype(cfg.dtype if dtype is None else dtype)
    shape = (cfg.n_layer, batch, max_len, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _cached_attention(q, ck, cv, pos, cfg: ModelConfig):
    """q:[B,1,H,D] over cached ck/cv:[B,Smax,Hkv,D]; attends ≤ pos.

    ``pos`` is a scalar (lockstep batch — offline sampling) or ``[B]``
    (per-slot positions — the serving engine's continuous batch, where
    every slot sits at its own depth). The scalar path is untouched so
    offline rollouts stay bitwise; the per-slot path computes the same
    elementwise math with a per-row mask."""
    b, _, h, d = q.shape
    smax, hkv = ck.shape[1], ck.shape[2]
    groups = h // hkv
    qg = q.reshape(b, hkv, groups, d)  # squeeze the length-1 axis
    scale = d**-0.5
    if cfg.mup_base_width:
        scale = 1.0  # 1/d folded into q by the caller, matching forward
    s = jnp.einsum(
        "bkgd,bskd->bkgs",
        qg.astype(jnp.float32),
        ck.astype(jnp.float32),
    ) * scale
    pos = jnp.asarray(pos)
    kpos = jnp.arange(smax)
    if pos.ndim == 0:
        mask = kpos <= pos
        if cfg.attn_window:
            # sliding window in decode: only the last attn_window slots
            mask = mask & (kpos > pos - cfg.attn_window)
        s = jnp.where(mask[None, None, None, :], s, -1e30)
    else:
        mask = kpos[None, :] <= pos[:, None]
        if cfg.attn_window:
            mask = mask & (kpos[None, :] > pos[:, None] - cfg.attn_window)
        s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, cv.astype(jnp.float32))
    return out.reshape(b, 1, h * d).astype(q.dtype)


def prefill(
    params: Params,
    tokens: jax.Array,  # [B, P] int32 — the whole prompt
    cfg: ModelConfig,
    max_len: int,
    prefix_len: Optional[jax.Array] = None,  # [B] int32 (prefix-LM)
) -> Tuple[jax.Array, Dict]:
    """Batch forward over the prompt that RETURNS the filled KV cache.

    One [B,P] forward replaces P sequential ``decode_step`` calls — the
    prompt runs at batched-matmul efficiency, and prefix-LM models
    become cacheable at all: the prompt K/V are computed WITH the
    bidirectional-prefix mask (``prefix_len``), which the per-token
    causal prefill can never produce (reference capability:
    transformers' prefill inside .generate; atorch leans on it for RL
    rollouts, rl/model_engine/model_engine.py).

    Returns (logits [B, P, V] f32, cache with positions [0, P) filled).
    """
    _train_only_guard(cfg, "prefill")
    if not cfg.causal:
        raise ValueError("prefill requires a causal model")
    if cfg.prefix_lm and prefix_len is None:
        # same footgun guard as forward(): a prefix-LM model silently
        # prefilled fully-causal would hand decode_step a wrong cache
        raise ValueError(
            "cfg.prefix_lm is set but no prefix_len was provided; pass "
            "jnp.zeros([batch], int32) for fully-causal behavior"
        )
    if getattr(cfg, "pp_interleave", 1) > 1:
        raise ValueError(
            "prefill scans layers in storage order; interleave-stacked "
            "checkpoints (pp_interleave>1) need the semantic layer "
            "permutation — use forward() paths"
        )
    dt = jnp.dtype(cfg.dtype)
    b, p = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32), (b, p))
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(dt)
    if cfg.pos == "learned":
        x = x + jnp.take(
            params["pos_embed"]["table"], positions, axis=0
        ).astype(dt)

    nh, hd = cfg.n_head, cfg.head_dim
    scale = 1.0 if cfg.mup_base_width else hd**-0.5
    # rope tables built once for the whole prompt, shared by all layers
    rope = (
        _rope_tables(positions, hd, cfg.rope_theta)
        if cfg.pos == "rope"
        else None
    )

    def layer_fn(carry, layer):
        x = carry
        ln1 = layer["ln1"]
        h = _norm(x, ln1["scale"], ln1.get("bias"), cfg.norm, cfg.norm_eps)
        q, k, v = _project_qkv(
            h, layer, cfg, positions, mup_full_scale=True, rope=rope
        )
        attn = mha_reference(
            q, k, v,
            causal=True,
            softmax_scale=scale,
            prefix_len=prefix_len,
            window=cfg.attn_window,
        ).reshape(b, p, nh * hd)
        attn_out = attn @ layer["attn"]["wo"].astype(x.dtype)
        x = _cache_layer_tail(x, attn_out, layer, cfg)
        # cache layout [B, max_len, Hkv, D], prompt slots filled
        pad = max_len - p
        ck = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x, (ck, cv)

    x, (new_k, new_v) = jax.lax.scan(layer_fn, x, params["layers"])
    fn = params["final_norm"]
    x = _norm(x, fn["scale"], fn.get("bias"), cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        w_out = params["embed"]["tokens"].T
    else:
        w_out = params["lm_head"]["w"]
    logits = jnp.einsum(
        "bsd,dv->bsv", x, w_out.astype(dt),
        preferred_element_type=jnp.float32,
    )
    if cfg.mup_base_width and cfg.tie_embeddings:
        logits = logits * (cfg.mup_base_width / cfg.d_model)
    return logits, {"k": new_k, "v": new_v}


def decode_step(
    params: Params,
    tokens: jax.Array,  # [B] int32 — token at position ``pos``
    cache: Dict,
    pos: jax.Array,     # scalar int32, or [B] int32 per-slot positions
    cfg: ModelConfig,
    prefilled: bool = False,
) -> Tuple[jax.Array, Dict]:
    """One incremental step: logits predicting position ``pos+1``.

    O(S·D) per token instead of the O(S²·D) full-prefix recompute of
    ``forward`` — the standard KV-cache inference path (the reference
    leans on transformers.generate; here it is native). Single-mesh only
    (no pp/sp); MoE layers route the single token through moe_block.

    ``pos`` may be ``[B]`` — SLOT-INDEXED decoding for the serving
    engine's continuous batch: every row advances at its own position
    (its own rope angle, cache write offset and attention mask), so
    requests at different depths share one step. The scalar path is the
    original lockstep batch, untouched.

    ``prefilled`` asserts the cache came from ``prefill``: required for
    prefix-LM models, whose prompt K/V depend on bidirectional attention
    that per-token causal decoding can never reconstruct. The causal
    cached attention here is correct for the post-prompt tail either way
    (a tail query sees all prefix keys AND earlier tail keys — both are
    ≤ pos).
    """
    _train_only_guard(cfg, "decode_step")
    if not cfg.causal:
        raise ValueError(
            "decode_step requires a causal model; encoder (bidirectional) "
            "configs have no autoregressive decode"
        )
    if cfg.prefix_lm and not prefilled:
        raise ValueError(
            "decode_step's per-token causal prefill cannot build a "
            "prefix-LM cache (prefix K/V depend on bidirectional "
            "attention below); build the cache with prefill() and pass "
            "prefilled=True, or use sample(use_cache=False)"
        )
    dt = jnp.dtype(cfg.dtype)
    b = tokens.shape[0]
    pos = jnp.asarray(pos)
    per_slot = pos.ndim == 1
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)[:, None, :]
    x = x.astype(dt)
    if per_slot:
        positions = pos[:, None].astype(jnp.int32)
    else:
        positions = jnp.broadcast_to(pos, (b, 1)).astype(jnp.int32)
    if cfg.pos == "learned":
        x = x + jnp.take(
            params["pos_embed"]["table"], positions, axis=0
        ).astype(dt)

    # single-position rope tables, built once outside the layer scan
    rope = (
        _rope_tables(positions, cfg.head_dim, cfg.rope_theta)
        if cfg.pos == "rope"
        else None
    )

    def layer_fn(carry, inp):
        x = carry
        layer, ck, cv = inp
        ln1 = layer["ln1"]
        h = _norm(x, ln1["scale"], ln1.get("bias"), cfg.norm, cfg.norm_eps)
        q, k, v = _project_qkv(
            h, layer, cfg, positions, mup_full_scale=True, rope=rope
        )
        # external caches may hold a different dtype (f32 reference
        # buffers); the write adopts it — a no-op at the default dtype
        k, v = k.astype(ck.dtype), v.astype(cv.dtype)
        if per_slot:
            # each slot writes its token row at its OWN position
            upd = lambda c, u, p: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731
                c, u, p, axis=0
            )
            ck = jax.vmap(upd)(ck, k, pos)
            cv = jax.vmap(upd)(cv, v, pos)
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k, pos, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v, pos, axis=1)
        attn = _cached_attention(q, ck, cv, pos, cfg)
        attn_out = attn @ layer["attn"]["wo"].astype(x.dtype)
        x = _cache_layer_tail(x, attn_out, layer, cfg)
        return x, (ck, cv)

    x, (new_k, new_v) = jax.lax.scan(
        layer_fn, x, (params["layers"], cache["k"], cache["v"])
    )
    fn = params["final_norm"]
    x = _norm(x, fn["scale"], fn.get("bias"), cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        w_out = params["embed"]["tokens"].T
    else:
        w_out = params["lm_head"]["w"]
    logits = jnp.einsum(
        "bsd,dv->bsv", x, w_out.astype(dt),
        preferred_element_type=jnp.float32,
    )[:, 0]
    if cfg.mup_base_width and cfg.tie_embeddings:
        logits = logits * (cfg.mup_base_width / cfg.d_model)
    return logits, {"k": new_k, "v": new_v}


def _verify_cached_attention(q, ck, cv, positions, cfg: ModelConfig):
    """q:[B,C,H,D] over PER-QUERY caches ck/cv:[B,C,Smax,Hkv,D]; query
    ci attends keys ≤ positions[b, ci] — with ``_cached_attention``'s
    EXACT op placement, batched over C query rows.

    This is the speculative-decoding verify attention. It deliberately
    does NOT reuse ``_chunk_cached_attention``: that one mirrors
    ``mha_reference`` (repeat-kv, probs cast to q.dtype before PV),
    which at bf16 differs from the decode math by ~1e-3 — enough to
    break the greedy spec-on bitwise pin. Here the grouped-head einsum
    keeps probs f32 through PV per query row, so each row's output is
    bitwise what a sequential ``decode_step`` at that position produces
    (pinned by tests/test_serving_spec.py). The cache carries a query
    axis because each query must see a DIFFERENT mix of raw vs
    as-committed chunk rows (``verify_chunk``)."""
    b, c, h, d = q.shape
    smax, hkv = ck.shape[2], ck.shape[3]
    groups = h // hkv
    qg = q.reshape(b, c, hkv, groups, d)
    scale = d**-0.5
    if cfg.mup_base_width:
        scale = 1.0  # 1/d folded into q by the caller, matching forward
    s = jnp.einsum(
        "bckgd,bcskd->bckgs",
        qg.astype(jnp.float32),
        ck.astype(jnp.float32),
    ) * scale
    kpos = jnp.arange(smax)
    mask = kpos[None, None, :] <= positions[:, :, None]  # [B, C, Smax]
    if cfg.attn_window:
        mask = mask & (kpos[None, None, :] > positions[:, :, None]
                       - cfg.attn_window)
    s = jnp.where(mask[:, :, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bckgs,bcskd->bckgd", p, cv.astype(jnp.float32))
    return out.reshape(b, c, h * d).astype(q.dtype)


def _chunk_cached_attention(q, ck, cv, positions, cfg: ModelConfig, scale):
    """q:[B,C,H,D] over cached ck/cv:[B,Smax,Hkv,D]; query ci attends
    keys ≤ positions[b, ci].

    The C-query generalization of ``_cached_attention`` used by chunked
    prefill, written with ``mha_reference``'s exact op sequence
    (repeat-kv, f32 qk einsum, -1e30 mask, softmax cast to q.dtype) so a
    chunk that covers a whole prompt reproduces ``prefill``'s logits —
    cache slots past each query's position contribute exact zeros."""
    h, hkv = q.shape[2], ck.shape[2]
    smax = ck.shape[1]
    if hkv != h:
        ck = _repeat_kv(ck, h // hkv)
        cv = _repeat_kv(cv, h // hkv)
    if device.on_cpu():
        # mirror mha_reference's CPU-vs-MXU precision split exactly
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk",
            q.astype(jnp.float32),
            ck.astype(jnp.float32),
        )
    else:
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q, ck, preferred_element_type=jnp.float32
        )
    logits = logits * scale
    kpos = jnp.arange(smax)
    mask = kpos[None, None, :] <= positions[:, :, None]  # [B, C, Smax]
    if cfg.attn_window:
        mask = mask & (kpos[None, None, :] > positions[:, :, None]
                       - cfg.attn_window)
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, cv)


def prefill_chunk(
    params: Params,
    tokens: jax.Array,  # [B, C] int32 — one prompt chunk per slot
    cache: Dict,
    start: jax.Array,   # scalar or [B] int32 — chunk start positions
    cfg: ModelConfig,
) -> Tuple[jax.Array, Dict]:
    """Prefill ``C`` prompt tokens per slot INTO an existing cache.

    The chunked-prefill primitive for the serving engine: a long prompt
    runs as ceil(P/C) of these between decode steps instead of one
    monolithic ``prefill``, so admitted long prompts never stall the
    decode batch. Each slot's chunk starts at its own ``start`` (the
    tokens already cached for that slot); chunk K/V are written at
    [start, start+C) and queries attend causally against the whole
    cache. Chunk tails past a slot's true prompt write garbage the
    position mask hides — callers route them to scratch storage (the
    serving tier's trash page) or let later writes overwrite them.

    Returns (logits [B, C, V] f32, updated cache). Causal-only:
    prefix-LM prompts need the bidirectional masking of ``prefill``.
    """
    _train_only_guard(cfg, "prefill_chunk")
    if not cfg.causal:
        raise ValueError("prefill_chunk requires a causal model")
    if cfg.prefix_lm:
        raise ValueError(
            "prefill_chunk is causal-only; prefix-LM prompts must be "
            "prefilled bidirectionally in one prefill() call"
        )
    if getattr(cfg, "pp_interleave", 1) > 1:
        raise ValueError(
            "prefill_chunk scans layers in storage order; use forward() "
            "paths for interleave-stacked checkpoints"
        )
    dt = jnp.dtype(cfg.dtype)
    b, c = tokens.shape
    start = jnp.asarray(start, jnp.int32)
    if start.ndim == 0:
        start = jnp.broadcast_to(start, (b,))
    positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(dt)
    if cfg.pos == "learned":
        x = x + jnp.take(
            params["pos_embed"]["table"], positions, axis=0
        ).astype(dt)
    nh, hd = cfg.n_head, cfg.head_dim
    scale = 1.0 if cfg.mup_base_width else hd**-0.5
    rope = (
        _rope_tables(positions, hd, cfg.rope_theta)
        if cfg.pos == "rope"
        else None
    )

    def layer_fn(carry, inp):
        x = carry
        layer, ck, cv = inp
        ln1 = layer["ln1"]
        h = _norm(x, ln1["scale"], ln1.get("bias"), cfg.norm, cfg.norm_eps)
        q, k, v = _project_qkv(
            h, layer, cfg, positions, mup_full_scale=True, rope=rope
        )
        upd = lambda cc, u, p: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731
            cc, u, p, axis=0
        )
        ck = jax.vmap(upd)(ck, k.astype(ck.dtype), start)
        cv = jax.vmap(upd)(cv, v.astype(cv.dtype), start)
        attn = _chunk_cached_attention(
            q, ck, cv, positions, cfg, scale
        ).reshape(b, c, nh * hd)
        attn_out = attn @ layer["attn"]["wo"].astype(x.dtype)
        x = _cache_layer_tail(x, attn_out, layer, cfg)
        return x, (ck, cv)

    x, (new_k, new_v) = jax.lax.scan(
        layer_fn, x, (params["layers"], cache["k"], cache["v"])
    )
    fn = params["final_norm"]
    x = _norm(x, fn["scale"], fn.get("bias"), cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        w_out = params["embed"]["tokens"].T
    else:
        w_out = params["lm_head"]["w"]
    logits = jnp.einsum(
        "bsd,dv->bsv", x, w_out.astype(dt),
        preferred_element_type=jnp.float32,
    )
    if cfg.mup_base_width and cfg.tie_embeddings:
        logits = logits * (cfg.mup_base_width / cfg.d_model)
    return logits, {"k": new_k, "v": new_v}


# ---------------------------------------------------------------------------
# Paged decode: block-table pools in, block-table pools out
# ---------------------------------------------------------------------------


def _paged_guards(cfg: ModelConfig, fn: str):
    _train_only_guard(cfg, fn)
    if not cfg.causal:
        raise ValueError(f"{fn} requires a causal model")
    if cfg.prefix_lm:
        raise ValueError(
            f"{fn} is causal-only: paged serving prefills causally in "
            "chunks, which can never build a prefix-LM cache — use the "
            "contiguous prefill() path"
        )
    if getattr(cfg, "pp_interleave", 1) > 1:
        raise ValueError(
            f"{fn} scans layers in storage order; use forward() paths "
            "for interleave-stacked checkpoints"
        )


def decode_step_paged(
    params: Params,
    tokens: jax.Array,        # [B] int32 — token at position ``pos``
    pools: Dict,              # layer-leading page pools (bf16 or int8)
    block_tables: jax.Array,  # [B, max_pages] int32, -1 = unassigned
    pos: jax.Array,           # [B] int32 per-slot positions
    valid: jax.Array,         # [B] bool — invalid lanes write the trash page
    cfg: ModelConfig,
    *,
    max_pages=None,
    interpret=None,
) -> Tuple[jax.Array, Dict]:
    """``decode_step`` over the serving tier's paged pools directly.

    The gather/scatter round trip is gone: each layer commits the new
    token's K/V row straight into its page cell (encode-on-write in
    int8 mode) and attends with ``ops.pallas_paged.paged_attention`` —
    no `[L, B, S_max, ...]` contiguous cache exists anywhere in the
    traced step, so per-token K/V traffic is O(pages held), not
    O(table width). ``max_pages`` (static) bounds the page walk to the
    host-known maximum pages any slot holds.

    bf16 pools on the reference dispatch reproduce ``decode_step`` over
    a ``kv_cache.gather`` view **bitwise** (pinned by the serving
    engine's greedy-parity tests): both paths see the same committed
    rows plus the same freshly-written row, and pages past a slot's
    position contribute exact zeros through the f32 softmax.

    Returns (logits [B, V] f32, updated pools).
    """
    _paged_guards(cfg, "decode_step_paged")
    dt = jnp.dtype(cfg.dtype)
    b = tokens.shape[0]
    pos = jnp.asarray(pos)
    if pos.ndim != 1:
        raise ValueError("decode_step_paged is per-slot: pos must be [B]")
    positions = pos[:, None].astype(jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)
    valid = jnp.asarray(valid)
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)[:, None, :]
    x = x.astype(dt)
    if cfg.pos == "learned":
        x = x + jnp.take(
            params["pos_embed"]["table"], positions, axis=0
        ).astype(dt)
    rope = (
        _rope_tables(positions, cfg.head_dim, cfg.rope_theta)
        if cfg.pos == "rope"
        else None
    )
    scale = 1.0 if cfg.mup_base_width else cfg.head_dim**-0.5

    def layer_fn(carry, inp):
        x = carry
        layer, pools_l = inp
        ln1 = layer["ln1"]
        h = _norm(x, ln1["scale"], ln1.get("bias"), cfg.norm, cfg.norm_eps)
        q, k, v = _project_qkv(
            h, layer, cfg, positions, mup_full_scale=True, rope=rope
        )
        # write-before-attend, mirroring decode_step's update order
        pools_l = pallas_paged.write_page_rows(
            pools_l, tables, positions, valid[:, None], k, v
        )
        attn = pallas_paged.paged_attention(
            q, pools_l, tables, pos, scale=scale, window=cfg.attn_window,
            kv_heads=cfg.kv_heads, max_pages=max_pages, variant="decode",
            interpret=interpret,
        ).reshape(b, 1, cfg.n_head * cfg.head_dim)
        attn_out = attn @ layer["attn"]["wo"].astype(x.dtype)
        x = _cache_layer_tail(x, attn_out, layer, cfg)
        return x, pools_l

    x, new_pools = jax.lax.scan(layer_fn, x, (params["layers"], pools))
    fn = params["final_norm"]
    x = _norm(x, fn["scale"], fn.get("bias"), cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        w_out = params["embed"]["tokens"].T
    else:
        w_out = params["lm_head"]["w"]
    logits = jnp.einsum(
        "bsd,dv->bsv", x, w_out.astype(dt),
        preferred_element_type=jnp.float32,
    )[:, 0]
    if cfg.mup_base_width and cfg.tie_embeddings:
        logits = logits * (cfg.mup_base_width / cfg.d_model)
    return logits, new_pools


def prefill_chunk_paged(
    params: Params,
    tokens: jax.Array,        # [B, C] int32 — one prompt chunk per slot
    pools: Dict,              # layer-leading page pools (bf16 or int8)
    block_tables: jax.Array,  # [B, max_pages] int32
    start: jax.Array,         # [B] int32 chunk start positions
    chunk_len: jax.Array,     # [B] int32 valid tokens in each chunk
    cfg: ModelConfig,
    *,
    max_pages=None,
    interpret=None,
) -> Tuple[jax.Array, Dict]:
    """``prefill_chunk`` over paged pools: chunk K/V rows commit
    straight to their page cells (rows past ``chunk_len`` route to the
    trash page) and queries attend through the paged kernel — the
    C-query twin of ``decode_step_paged``, same no-contiguous-cache
    contract. Returns (logits [B, C, V] f32, updated pools)."""
    _paged_guards(cfg, "prefill_chunk_paged")
    dt = jnp.dtype(cfg.dtype)
    b, c = tokens.shape
    start = jnp.asarray(start, jnp.int32)
    if start.ndim == 0:
        start = jnp.broadcast_to(start, (b,))
    positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    valid = jnp.arange(c)[None, :] < jnp.asarray(chunk_len)[:, None]
    tables = jnp.asarray(block_tables, jnp.int32)
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(dt)
    if cfg.pos == "learned":
        x = x + jnp.take(
            params["pos_embed"]["table"], positions, axis=0
        ).astype(dt)
    nh, hd = cfg.n_head, cfg.head_dim
    scale = 1.0 if cfg.mup_base_width else hd**-0.5
    rope = (
        _rope_tables(positions, hd, cfg.rope_theta)
        if cfg.pos == "rope"
        else None
    )

    def layer_fn(carry, inp):
        x = carry
        layer, pools_l = inp
        ln1 = layer["ln1"]
        h = _norm(x, ln1["scale"], ln1.get("bias"), cfg.norm, cfg.norm_eps)
        q, k, v = _project_qkv(
            h, layer, cfg, positions, mup_full_scale=True, rope=rope
        )
        pools_l = pallas_paged.write_page_rows(
            pools_l, tables, positions, valid, k, v
        )
        attn = pallas_paged.paged_attention(
            q, pools_l, tables, positions, scale=scale,
            window=cfg.attn_window, kv_heads=cfg.kv_heads,
            max_pages=max_pages, variant="chunk", interpret=interpret,
        ).reshape(b, c, nh * hd)
        attn_out = attn @ layer["attn"]["wo"].astype(x.dtype)
        x = _cache_layer_tail(x, attn_out, layer, cfg)
        return x, pools_l

    x, new_pools = jax.lax.scan(layer_fn, x, (params["layers"], pools))
    fn = params["final_norm"]
    x = _norm(x, fn["scale"], fn.get("bias"), cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        w_out = params["embed"]["tokens"].T
    else:
        w_out = params["lm_head"]["w"]
    logits = jnp.einsum(
        "bsd,dv->bsv", x, w_out.astype(dt),
        preferred_element_type=jnp.float32,
    )
    if cfg.mup_base_width and cfg.tie_embeddings:
        logits = logits * (cfg.mup_base_width / cfg.d_model)
    return logits, new_pools


# ---------------------------------------------------------------------------
# Speculative-decoding verify step
# ---------------------------------------------------------------------------


def verify_chunk(
    params: Params,
    tokens: jax.Array,  # [B, C] int32 — [last committed token, drafts...]
    cache: Dict,
    start: jax.Array,   # [B] int32 — position of the chunk's first row
    cfg: ModelConfig,
    as_committed=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Target-model logits for C candidate positions per slot, each row
    BITWISE what a sequential ``decode_step`` at that position returns.

    The speculative-decoding verify primitive over a dense cache: row 0
    is the last committed token (its K/V row was deliberately left
    unwritten by the previous step, exactly as ``decode_step`` leaves
    it), rows 1..C-1 are draft tokens. Nothing is written into
    ``cache`` — each query row gets its OWN key/value view in which
    chunk rows before it appear AS COMMITTED (``as_committed``: e.g.
    the engine's int8 pool round-trip) while its own row stays raw,
    exactly the mix a sequential gather→decode→commit loop would see
    at that position. Rows sit at their true cache indices, so the
    f32 reductions run in the sequential order and every query runs
    the decode-variant attention math (``_verify_cached_attention``) —
    NOT the chunk/prefill math, whose bf16 precision placement differs
    by ~1e-3 and would break the greedy spec-on pin. Row i's logits
    predict position start+i+1.

    Returns (logits [B, C, V] f32,
             chunk_k [L, B, C, Hkv, D], chunk_v [L, B, C, Hkv, D] —
             RAW rows; the caller commits the ACCEPTED prefix to the
             pools, which re-applies the commit encoding).
    """
    _paged_guards(cfg, "verify_chunk")
    dt = jnp.dtype(cfg.dtype)
    b, c = tokens.shape
    start = jnp.asarray(start, jnp.int32)
    if start.ndim == 0:
        start = jnp.broadcast_to(start, (b,))
    positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(dt)
    if cfg.pos == "learned":
        x = x + jnp.take(
            params["pos_embed"]["table"], positions, axis=0
        ).astype(dt)
    rope = (
        _rope_tables(positions, cfg.head_dim, cfg.rope_theta)
        if cfg.pos == "rope"
        else None
    )
    s_len = cache["k"].shape[2]
    # rel[b, s] = chunk index living at cache slot s (clipped; the
    # in_chunk/own masks gate where the gathered rows actually apply)
    rel = jnp.arange(s_len, dtype=jnp.int32)[None, :] - start[:, None]
    relc = jnp.clip(rel, 0, c - 1)
    in_chunk = ((rel >= 0) & (rel < c))[..., None, None]      # [B,S,1,1]
    own = (
        rel[:, None, :] == jnp.arange(c, dtype=jnp.int32)[None, :, None]
    )[..., None, None]                                        # [B,C,S,1,1]
    pick = jax.vmap(lambda rows, idx: rows[idx])  # [C,..],[S] -> [S,..]

    def layer_fn(carry, inp):
        x = carry
        layer, ck, cv = inp
        ln1 = layer["ln1"]
        h = _norm(x, ln1["scale"], ln1.get("bias"), cfg.norm, cfg.norm_eps)
        q, k, v = _project_qkv(
            h, layer, cfg, positions, mup_full_scale=True, rope=rope
        )
        kc = (k if as_committed is None else as_committed(k)).astype(
            ck.dtype
        )
        vc = (v if as_committed is None else as_committed(v)).astype(
            cv.dtype
        )
        # per-query views: committed prefix from the cache, earlier
        # chunk rows as-committed, the query's own row raw — all at
        # their true slot indices (sequential reduction order)
        base_k = jnp.where(in_chunk, pick(kc, relc), ck)      # [B,S,..]
        base_v = jnp.where(in_chunk, pick(vc, relc), cv)
        raw_k = pick(k.astype(ck.dtype), relc)
        raw_v = pick(v.astype(cv.dtype), relc)
        ck_q = jnp.where(own, raw_k[:, None], base_k[:, None])
        cv_q = jnp.where(own, raw_v[:, None], base_v[:, None])
        attn = _verify_cached_attention(q, ck_q, cv_q, positions, cfg)
        attn_out = attn @ layer["attn"]["wo"].astype(x.dtype)
        x = _cache_layer_tail(x, attn_out, layer, cfg)
        return x, (k, v)

    x, (chunk_k, chunk_v) = jax.lax.scan(
        layer_fn, x, (params["layers"], cache["k"], cache["v"])
    )
    fn = params["final_norm"]
    x = _norm(x, fn["scale"], fn.get("bias"), cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        w_out = params["embed"]["tokens"].T
    else:
        w_out = params["lm_head"]["w"]
    logits = jnp.einsum(
        "bsd,dv->bsv", x, w_out.astype(dt),
        preferred_element_type=jnp.float32,
    )
    if cfg.mup_base_width and cfg.tie_embeddings:
        logits = logits * (cfg.mup_base_width / cfg.d_model)
    return logits, chunk_k, chunk_v


def verify_chunk_paged(
    params: Params,
    tokens: jax.Array,        # [B, C] int32 — [last token, drafts...]
    pools: Dict,              # layer-leading page pools (READ-ONLY here)
    block_tables: jax.Array,  # [B, max_pages] int32
    start: jax.Array,         # [B] int32 — position of the chunk's row 0
    cfg: ModelConfig,
    *,
    max_pages=None,
    interpret=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``verify_chunk`` over paged pools with DEFERRED writes.

    Nothing is written: chunk K/V ride into the paged attention as
    in-flight extra keys (``variant="verify"``) and come back stacked
    per layer so the caller can commit ONLY the accepted prefix after
    the acceptance rule runs — the page-commit invariant (rejected
    draft rows never reach the pools, so encode-on-write int8 needs no
    rollback). In int8 mode the in-flight rows are round-tripped
    through the page quantizer first, so a draft row sees exactly the
    values it would have as a committed row and acceptance math is
    independent of commit timing.

    Returns (logits [B, C, V] f32,
             chunk_k [L, B, C, Hkv, D], chunk_v [L, B, C, Hkv, D]).
    """
    _paged_guards(cfg, "verify_chunk_paged")
    dt = jnp.dtype(cfg.dtype)
    b, c = tokens.shape
    start = jnp.asarray(start, jnp.int32)
    if start.ndim == 0:
        start = jnp.broadcast_to(start, (b,))
    positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    tables = jnp.asarray(block_tables, jnp.int32)
    int8_pool = "k" not in pools
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(dt)
    if cfg.pos == "learned":
        x = x + jnp.take(
            params["pos_embed"]["table"], positions, axis=0
        ).astype(dt)
    nh, hd = cfg.n_head, cfg.head_dim
    scale = 1.0 if cfg.mup_base_width else hd**-0.5
    rope = (
        _rope_tables(positions, hd, cfg.rope_theta)
        if cfg.pos == "rope"
        else None
    )

    def _as_committed(rows, pools_l):
        """What this K/V row would read back as AFTER a commit: int8
        pages round-trip through the block quantizer; bf16 pages adopt
        the pool dtype (a no-op at the default compute dtype)."""
        if int8_pool:
            blk = pools_l["k_q"].shape[-1]
            qv, sc = quant.kv_encode_rows(
                rows.reshape(b, c, cfg.kv_heads * hd), blk
            )
            return quant.kv_decode_rows(qv, sc, dt).reshape(
                b, c, cfg.kv_heads, hd
            )
        return rows.astype(pools_l["k"].dtype)

    def layer_fn(carry, inp):
        x = carry
        layer, pools_l = inp
        ln1 = layer["ln1"]
        h = _norm(x, ln1["scale"], ln1.get("bias"), cfg.norm, cfg.norm_eps)
        q, k, v = _project_qkv(
            h, layer, cfg, positions, mup_full_scale=True, rope=rope
        )
        attn = pallas_paged.paged_attention(
            q, pools_l, tables, positions, scale=scale,
            window=cfg.attn_window, kv_heads=cfg.kv_heads,
            max_pages=max_pages, variant="verify", interpret=interpret,
            extra_k=_as_committed(k, pools_l),
            extra_v=_as_committed(v, pools_l),
        ).reshape(b, c, nh * hd)
        attn_out = attn @ layer["attn"]["wo"].astype(x.dtype)
        x = _cache_layer_tail(x, attn_out, layer, cfg)
        return x, (k, v)

    x, (chunk_k, chunk_v) = jax.lax.scan(
        layer_fn, x, (params["layers"], pools)
    )
    fn = params["final_norm"]
    x = _norm(x, fn["scale"], fn.get("bias"), cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        w_out = params["embed"]["tokens"].T
    else:
        w_out = params["lm_head"]["w"]
    logits = jnp.einsum(
        "bsd,dv->bsv", x, w_out.astype(dt),
        preferred_element_type=jnp.float32,
    )
    if cfg.mup_base_width and cfg.tie_embeddings:
        logits = logits * (cfg.mup_base_width / cfg.d_model)
    return logits, chunk_k, chunk_v
