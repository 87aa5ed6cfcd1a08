"""Autoregressive sampling for the decoder.

Reference: the actor generation step of atorch's RL pipeline
(rl/model_engine + transformers .generate). Implemented as one jitted
``lax.scan`` over decode positions with a fixed-size token buffer, so the
whole rollout compiles once. Default path prefills the prompt in ONE
batch forward that returns the KV cache (decoder.prefill — matmul-bound,
like transformers' prefill), then decodes incrementally
(decoder.decode_step, O(S) per token); the full-prefix recompute path
remains for mesh/MoE setups the cache doesn't cover.
"""

from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.models import decoder


def init_kv_cache(cfg, batch: int, max_len: int, dtype=None):
    """Allocate the KV cache offline sampling and the serving engine
    share: ``{"k","v"}`` zeros of [n_layer, batch, max_len, Hkv, D].

    ONE allocation site (delegating to ``decoder.init_kv_cache``) so the
    two consumers can never drift on layout or fill value — the engine's
    gathered page views and the sampler's inline buffers are the same
    object shape, pinned bitwise by tests/test_generate_cache.py.
    ``dtype`` defaults to the model compute dtype."""
    return decoder.init_kv_cache(cfg, batch, max_len, dtype=dtype)


def warp_logits(logits, temperature, top_k=0, top_p=1.0):
    """Temperature → top-k → top-p logit warp, applied in that order.

    ``logits`` is ``[..., V]`` float32; the parameters are scalars (or
    0-d arrays — vmap over rows for per-request values). Disabled
    warpers are exact no-ops: ``top_k=0`` and ``top_p>=1`` leave the
    temperature-scaled logits bitwise untouched, so the default call is
    identical to the historical ``logits / temperature``. The caller
    guarantees ``temperature > 0`` (greedy bypasses the warp entirely).

    Masked entries become ``-inf`` — ``jax.random.categorical`` assigns
    them zero probability, so the draw distribution is the renormalized
    truncation of softmax(logits/temperature). This ONE function is
    shared by the offline sampler and the serving engine's fused
    in-step sampler, which is what makes the engine-vs-offline sampled
    pin (tests/test_serving_sampling.py) possible at all.
    """
    x = logits / temperature
    v = x.shape[-1]
    k = jnp.asarray(top_k, jnp.int32)
    srt = jnp.sort(x, axis=-1)[..., ::-1]  # descending
    kth = jnp.take_along_axis(
        srt,
        jnp.broadcast_to(jnp.clip(k, 1, v) - 1, x.shape[:-1])[..., None],
        axis=-1,
    )
    x = jnp.where((k > 0) & (x < kth), -jnp.inf, x)
    p = jnp.asarray(top_p, jnp.float32)
    # nucleus over the top-k-filtered distribution: smallest sorted
    # prefix whose probability mass reaches p (-inf entries sort last
    # and carry zero mass, so they can never be "kept")
    srt = jnp.sort(x, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(srt, axis=-1)
    exclusive = jnp.cumsum(probs, axis=-1) - probs
    n_keep = jnp.maximum((exclusive < p).sum(-1), 1)
    pth = jnp.take_along_axis(srt, (n_keep - 1)[..., None], axis=-1)
    return jnp.where((p < 1.0) & (x < pth), -jnp.inf, x)


def draw_token(logits, key, temperature, top_k=0, top_p=1.0):
    """Draw one token per row of ``logits`` ([..., V] f32).

    ``temperature == 0`` selects the argmax — the SAME op the greedy
    engine runs, so a greedy request through the sampling path stays
    bitwise identical to the pinned greedy engine. The sampled branch
    draws ``categorical(key, warp_logits(...))``; both branches are
    computed and selected elementwise so per-row temperatures can mix
    greedy and sampled requests in one fused step.
    """
    t = jnp.asarray(temperature, jnp.float32)
    greedy_tok = jnp.argmax(logits, axis=-1)
    warped = warp_logits(logits, jnp.where(t > 0, t, 1.0), top_k, top_p)
    sampled = jax.random.categorical(key, warped, axis=-1)
    return jnp.where(t > 0, sampled, greedy_tok).astype(jnp.int32)


def sample(
    params,
    cfg,
    prompts: jax.Array,       # [B, P] int32
    max_new_tokens: int,
    rng: jax.Array,
    temperature: float = 1.0,
    mesh=None,
    attn_impl: str = "auto",
    pad_id: int = 0,
    use_cache: bool = True,
    prompt_lens: Optional[jax.Array] = None,  # [B] int32 true lengths
    kv_cache: Optional[dict] = None,
    top_k: int = 0,
    top_p: float = 1.0,
) -> jax.Array:
    """Sample continuations; returns [B, P + max_new_tokens].

    ``temperature=0`` is greedy. The scan carries the growing buffer at
    fixed shape (prompt padded to full length) — XLA-friendly: no dynamic
    shapes, one compilation for the whole rollout.

    ``use_cache=True`` prefills the prompt in one batch forward
    (decoder.prefill) and decodes incrementally (O(S) per token);
    ``False`` re-runs the full prefix each step. The cache path covers
    single-mesh dense models including prefix-LM — MoE routes with
    per-step capacity in decode, a different policy than the batch
    forward's capacity drops, so MoE always takes the full-prefix path
    to keep sampling consistent with training-time logprobs.

    ``prompt_lens`` (ragged batches): per-sequence true prompt lengths.
    For prefix-LM models it bounds the bidirectional prefix per sequence
    — WITHOUT it the full padded width is used, making pad tokens
    bidirectionally-visible context for every query. (Pad tokens between
    a sequence's true length and P remain ordinarily causally visible on
    every path — left-pad ragged prompts when that matters.)

    ``kv_cache`` (cache path only): an externally allocated
    ``init_kv_cache(cfg, b, p + max_new_tokens, dtype)`` buffer the
    rollout decodes in — the serving tier and RL rollout engine allocate
    caches up front (pooled / donated) instead of per call. Prefill
    K/V land in its first ``p`` slots at the buffer's dtype; with the
    default dtype and a zero buffer the rollout is bitwise identical to
    the inline allocation.

    Sampling draws use ``fold_in(rng, position)``, so both paths consume
    the same rng stream. Greedy (temperature=0) rollouts match token for
    token across paths in float32; at temperature>0 the two paths
    compute numerically different logits (per-token decode vs
    full-prefix forward), so near-tie draws can diverge — that is
    float noise, not a cache bug.
    """
    decoder._train_only_guard(cfg, "sample")
    if not cfg.causal:
        # bidirectional (encoder) models have no autoregressive factorization:
        # the full-prefix path would silently condition on the pad filler
        raise ValueError(
            "sample() requires a causal model; encoder configs "
            "(causal=False) cannot generate autoregressively"
        )
    b, p = prompts.shape
    # GLM convention: the prompt is "part A" — bidirectionally visible.
    # Per-sequence true lengths keep ragged pads out of the prefix.
    prefix = None
    if cfg.prefix_lm:
        prefix = (
            prompt_lens.astype(jnp.int32)
            if prompt_lens is not None
            else jnp.full((b,), p, jnp.int32)
        )
    # the cache path needs no model-parallel axes (prefill/decode_step
    # carry no sharding constraints); a dp/fsdp-only mesh is fine — the
    # batch axis shards through GSPMD propagation. Interleave-stacked
    # checkpoints (pp_interleave>1) are excluded: prefill/decode_step
    # scan layers in storage order, not the semantic_layer_perm order
    # the pipeline layout requires.
    cacheable_mesh = mesh is None or all(
        mesh.shape.get(a, 1) == 1 for a in ("tp", "sp", "pp", "ep")
    )
    if (
        use_cache
        and cacheable_mesh
        and cfg.n_experts == 0
        and getattr(cfg, "pp_interleave", 1) <= 1
    ):
        return _sample_cached(
            params, cfg, prompts, max_new_tokens, rng, temperature,
            pad_id, prefix, kv_cache, top_k, top_p,
        )
    if kv_cache is not None:
        raise ValueError(
            "kv_cache was provided but this config/mesh takes the "
            "full-prefix (cacheless) path; drop the buffer or use a "
            "cacheable setup"
        )
    total = p + max_new_tokens
    buf = jnp.full((b, total), pad_id, dtype=jnp.int32)
    buf = buf.at[:, :p].set(prompts)
    positions = jnp.broadcast_to(jnp.arange(total, dtype=jnp.int32), (b, total))

    def step(buf, i):
        logits = decoder.forward(
            params, buf, cfg, mesh=mesh, positions=positions,
            attn_impl=attn_impl, prefix_len=prefix,
        )
        # logits at position i-1 predict token i
        step_logits = jax.lax.dynamic_slice_in_dim(
            logits, i - 1, 1, axis=1
        )[:, 0, :]
        if temperature > 0.0:
            tok = jax.random.categorical(
                jax.random.fold_in(rng, i),
                warp_logits(step_logits, temperature, top_k, top_p),
            )
        else:
            tok = jnp.argmax(step_logits, axis=-1)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, tok[:, None].astype(jnp.int32), i, axis=1
        )
        return buf, None

    buf, _ = jax.lax.scan(step, buf, jnp.arange(p, total))
    return buf


def _sample_cached(
    params, cfg, prompts, max_new_tokens, rng, temperature, pad_id,
    prefix, kv_cache=None, top_k=0, top_p=1.0,
):
    """Prefill + incremental decode: one batch forward fills the KV
    cache for the whole prompt (prefix-LM masking included), then the
    scan decodes only the new positions."""
    b, p = prompts.shape
    total = p + max_new_tokens
    buf = jnp.full((b, total), pad_id, dtype=jnp.int32)
    buf = buf.at[:, :p].set(prompts)
    if max_new_tokens <= 0:
        return buf

    logits_p, cache = decoder.prefill(
        params, prompts, cfg, total, prefix_len=prefix
    )
    # grow the cache buffers to total via prefill's max_len — done there
    if kv_cache is not None:
        # decode in the caller's buffer: prefill K/V land in its first
        # p slots at the BUFFER's dtype (prefill pads with zeros, so a
        # zero buffer at the default dtype stays bitwise identical)
        for key in ("k", "v"):
            if kv_cache[key].shape != cache[key].shape:
                raise ValueError(
                    f"kv_cache[{key!r}] shape {kv_cache[key].shape} != "
                    f"required {cache[key].shape} "
                    f"(init_kv_cache(cfg, {b}, {total}))"
                )
        cache = {
            key: kv_cache[key]
            .at[:, :, :p]
            .set(cache[key][:, :, :p].astype(kv_cache[key].dtype))
            for key in ("k", "v")
        }

    def draw(step_logits, i):
        if temperature > 0.0:
            return jax.random.categorical(
                jax.random.fold_in(rng, i),
                warp_logits(step_logits, temperature, top_k, top_p),
            )
        return jnp.argmax(step_logits, axis=-1)

    # first new token comes from the prefill logits at position p-1
    tok0 = draw(logits_p[:, p - 1, :], jnp.int32(p)).astype(jnp.int32)
    buf = buf.at[:, p].set(tok0)

    def step(carry, i):
        buf, cache = carry
        tok_in = jax.lax.dynamic_slice_in_dim(buf, i - 1, 1, axis=1)[:, 0]
        logits, cache = decoder.decode_step(
            params, tok_in, cache, i - 1, cfg, prefilled=True
        )
        tok = draw(logits, i).astype(jnp.int32)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, tok[:, None], i, axis=1
        )
        return (buf, cache), None

    (buf, _), _ = jax.lax.scan(
        step, (buf, cache), jnp.arange(p + 1, total)
    )
    return buf


def greedy(params, cfg, prompts, max_new_tokens, mesh=None, **kw):
    return sample(
        params,
        cfg,
        prompts,
        max_new_tokens,
        rng=jax.random.key(0),
        temperature=0.0,
        mesh=mesh,
        **kw,
    )
