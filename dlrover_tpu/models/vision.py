"""ViT image encoder + CLIP dual-tower model, TPU-first.

Reference parity: atorch ships Megatron-TP CLIP transformer blocks
(atorch/atorch/modules/distributed_modules/transformer.py:220 — TP
variants of CLIPAttention/MLP) and registers CLIP modules for tensor
parallelism (modules_registry.py). Here the vision family is built the
TPU way instead of swapping modules:

- **patchify is a reshape + matmul**, not a conv: ``[B,H,W,C]`` is
  rearranged into ``[B, N, P·P·C]`` and projected with one dense layer —
  a single large MXU matmul, no im2col machinery.
- **the transformer trunk is the decoder's**: the ViT encoder reuses
  ``decoder._layer_body`` (scan over stacked layers, remat policies,
  PartitionSpec parallelism) with ``causal=False`` — one trunk
  implementation serves GPT/LLaMA/BERT/ViT/CLIP.
- **CLIP's global contrastive loss needs no explicit all-gather**: under
  pjit the batch axis is logically global, so ``img @ txt.T`` over the
  full batch is plain jnp and the partitioner inserts the collectives
  (the reference must hand-write torch.distributed all_gathers to get
  global negatives).
"""

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.models import decoder
from dlrover_tpu.models.config import ModelConfig
from dlrover_tpu.ops.attention import mha_reference
from dlrover_tpu.parallel import sharding as shd

Params = Dict[str, Any]


@dataclass(frozen=True)
class ViTConfig:
    """Vision transformer: patch frontend + a ModelConfig trunk.

    The trunk must be an encoder (``causal=False``); position embeddings
    are owned by the frontend (one learned table over patches + CLS), so
    ``trunk.pos`` is forced to ``"none"``-like behavior by construction
    (we never call the decoder's embedding path).
    """

    image_size: int = 224
    patch_size: int = 16
    channels: int = 3
    pool: str = "cls"  # cls | mean
    trunk: ModelConfig = field(
        default_factory=lambda: ModelConfig(
            name="vit-trunk",
            vocab_size=128,  # trunk embed tables are discarded; keep tiny
            causal=False,
            norm="layernorm",
            act="gelu",
            pos="learned",
        )
    )

    def __post_init__(self):
        if self.image_size % self.patch_size:
            raise ValueError(
                f"image_size {self.image_size} not divisible by "
                f"patch_size {self.patch_size}"
            )
        if self.pool not in ("cls", "mean"):
            raise ValueError(f"pool must be 'cls' or 'mean', got {self.pool}")
        if self.trunk.causal:
            raise ValueError("ViT trunk must have causal=False")
        if self.trunk.n_experts > 0:
            # forward_vit has no loss to carry router aux losses into —
            # an MoE trunk would train with load-balancing silently off
            raise ValueError("MoE trunks are not supported for ViT")

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def seq_len(self) -> int:
        return self.n_patches + (1 if self.pool == "cls" else 0)


def _vit(name, image_size, patch_size, n_layer, n_head, d_model):
    return ViTConfig(
        image_size=image_size,
        patch_size=patch_size,
        trunk=ModelConfig(
            name=name,
            # the trunk's token/pos embeddings are unused (the patch
            # frontend owns them) — keep the throwaway tables tiny
            vocab_size=128,
            n_layer=n_layer,
            n_head=n_head,
            d_model=d_model,
            d_ff=4 * d_model,
            causal=False,
            norm="layernorm",
            act="gelu",
            pos="learned",
            max_seq=(image_size // patch_size) ** 2 + 1,
        ),
    )


VIT_CONFIGS = {
    "vit-tiny-test": _vit("vit-tiny-test", 32, 8, 2, 4, 128),
    "vit-b-16": _vit("vit-b-16", 224, 16, 12, 12, 768),
    "vit-l-14": _vit("vit-l-14", 224, 14, 24, 16, 1024),
}


def init_vit(rng: jax.Array, cfg: ViTConfig) -> Params:
    """ViT params; the trunk reuses the decoder's stacked-layer layout."""
    t = cfg.trunk
    pdt = jnp.dtype(t.param_dtype)
    d = t.d_model
    patch_dim = cfg.patch_size * cfg.patch_size * cfg.channels
    k_full = jax.random.split(rng, 4)
    trunk = decoder.init(k_full[0], t)
    params: Params = {
        "patch_embed": {
            "w": (
                jax.random.normal(k_full[1], (patch_dim, d))
                / np.sqrt(patch_dim)
            ).astype(pdt),
            "b": jnp.zeros((d,), pdt),
        },
        "pos_embed": {
            "table": (
                jax.random.normal(k_full[2], (cfg.seq_len, d)) * 0.01
            ).astype(pdt)
        },
        "layers": trunk["layers"],
        "final_norm": trunk["final_norm"],
    }
    if cfg.pool == "cls":
        params["cls_token"] = (
            jax.random.normal(k_full[3], (1, 1, d)) * 0.02
        ).astype(pdt)
    return params


def vit_logical_axes(cfg: ViTConfig) -> Params:
    trunk = decoder.logical_axes(cfg.trunk)
    ax: Params = {
        "patch_embed": {"w": ("patch", "embed"), "b": ("norm",)},
        "pos_embed": {"table": ("seq", "embed")},
        "layers": trunk["layers"],
        "final_norm": trunk["final_norm"],
    }
    if cfg.pool == "cls":
        ax["cls_token"] = (None, None, "embed")
    return ax


def patchify(images: jax.Array, patch: int) -> jax.Array:
    """[B, H, W, C] → [B, N, P·P·C] by reshape/transpose only."""
    b, h, w, c = images.shape
    gh, gw = h // patch, w // patch
    x = images.reshape(b, gh, patch, gw, patch, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # [B, gh, gw, P, P, C]
    return x.reshape(b, gh * gw, patch * patch * c)


def forward_vit(
    params: Params,
    images: jax.Array,  # [B, H, W, C]
    cfg: ViTConfig,
    mesh=None,
    attn_impl: str = "auto",
    features_only: bool = False,
) -> jax.Array:
    """→ pooled features [B, D] (or token features [B, S, D])."""
    t = cfg.trunk
    dt = jnp.dtype(t.dtype)
    pe = params["patch_embed"]
    x = patchify(images.astype(dt), cfg.patch_size)
    x = x @ pe["w"].astype(dt) + pe["b"].astype(dt)
    if cfg.pool == "cls":
        cls = jnp.broadcast_to(
            params["cls_token"].astype(dt), (x.shape[0], 1, t.d_model)
        )
        x = jnp.concatenate([cls, x], axis=1)
    x = x + params["pos_embed"]["table"].astype(dt)[None]
    if mesh is not None:
        x = shd.constrain(x, mesh, "batch", "seq", None)

    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if attn_impl == "auto":
        # patch sequences are short and rarely 128-aligned: the plain
        # fused-softmax path beats odd-tiled flash kernels here
        attn_impl = "reference"
    if attn_impl not in ("reference", "flash"):
        # 'ring'/'ulysses' are valid for the decoder but meaningless on
        # short unsharded patch sequences — fail loudly rather than
        # silently dropping the requested parallelism
        raise ValueError(f"unsupported ViT attn_impl: {attn_impl!r}")

    def attn_fn(q, k, v):
        if attn_impl == "reference":
            return mha_reference(q, k, v, causal=False)
        from dlrover_tpu.ops.pallas_attention import flash_attention

        return flash_attention(
            q, k, v, causal=False,
            block_q=t.attn_block_q, block_k=t.attn_block_k,
        )

    x, _ = decoder.run_trunk(
        x,
        params["layers"],
        positions,
        t,
        mesh=mesh,
        attn_fn=attn_fn,
    )
    fn = params["final_norm"]
    x = decoder._norm(x, fn["scale"], fn.get("bias"), t.norm)
    if features_only:
        return x
    if cfg.pool == "cls":
        return x[:, 0]
    return x.mean(axis=1)


# ---------------------------------------------------------------------------
# CLIP
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CLIPConfig:
    """Dual-tower contrastive model (image ViT + causal text encoder).

    The text tower follows the CLIP convention: causal transformer, the
    sequence feature is read at each sequence's EOT position (supplied by
    the batch as ``eot_pos``, or defaulting to the last token).
    """

    embed_dim: int = 128
    vision: ViTConfig = field(
        default_factory=lambda: VIT_CONFIGS["vit-tiny-test"]
    )
    text: ModelConfig = field(
        default_factory=lambda: ModelConfig(
            name="clip-text",
            vocab_size=49408,
            causal=True,
            norm="layernorm",
            act="gelu",
            pos="learned",
        )
    )
    logit_scale_init: float = float(np.log(1.0 / 0.07))
    logit_scale_max: float = float(np.log(100.0))


def clip_tiny_test() -> CLIPConfig:
    return CLIPConfig(
        embed_dim=64,
        vision=VIT_CONFIGS["vit-tiny-test"],
        text=ModelConfig(
            name="clip-text-tiny",
            vocab_size=512,
            n_layer=2,
            n_head=4,
            d_model=128,
            d_ff=512,
            max_seq=32,
            causal=True,
            norm="layernorm",
            act="gelu",
            pos="learned",
        ),
    )


def init_clip(rng: jax.Array, cfg: CLIPConfig) -> Params:
    kv, kt, kp1, kp2 = jax.random.split(rng, 4)
    dv = cfg.vision.trunk.d_model
    dt_ = cfg.text.d_model
    pdt = jnp.dtype(cfg.text.param_dtype)
    return {
        "vision": init_vit(kv, cfg.vision),
        "text": decoder.init(kt, cfg.text),
        "image_proj": {
            "w": (jax.random.normal(kp1, (dv, cfg.embed_dim)) / np.sqrt(dv))
            .astype(pdt)
        },
        "text_proj": {
            "w": (jax.random.normal(kp2, (dt_, cfg.embed_dim)) / np.sqrt(dt_))
            .astype(pdt)
        },
        "logit_scale": jnp.asarray(cfg.logit_scale_init, jnp.float32),
    }


def clip_logical_axes(cfg: CLIPConfig) -> Params:
    return {
        "vision": vit_logical_axes(cfg.vision),
        "text": decoder.logical_axes(cfg.text),
        "image_proj": {"w": ("embed", "clip_embed")},
        "text_proj": {"w": ("embed", "clip_embed")},
        "logit_scale": None,
    }


def encode_image(params, images, cfg: CLIPConfig, mesh=None,
                 attn_impl="auto"):
    f = forward_vit(
        params["vision"], images, cfg.vision, mesh=mesh, attn_impl=attn_impl
    )
    f = f.astype(jnp.float32) @ params["image_proj"]["w"].astype(jnp.float32)
    return f / jnp.linalg.norm(f, axis=-1, keepdims=True).clip(1e-6)


def encode_text(params, tokens, cfg: CLIPConfig, mesh=None,
                eot_pos: Optional[jax.Array] = None, attn_impl="auto"):
    feats = decoder.forward(
        params["text"], tokens, cfg.text, mesh=mesh,
        attn_impl=attn_impl, features_only=True,
    )
    if eot_pos is None:
        eot_pos = jnp.full((tokens.shape[0],), tokens.shape[1] - 1,
                           jnp.int32)
    f = jnp.take_along_axis(
        feats, eot_pos[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    f = f.astype(jnp.float32) @ params["text_proj"]["w"].astype(jnp.float32)
    return f / jnp.linalg.norm(f, axis=-1, keepdims=True).clip(1e-6)


def clip_loss(
    params: Params,
    batch: Dict[str, jax.Array],  # images [B,H,W,C], tokens [B,S], eot_pos?
    cfg: CLIPConfig,
    mesh=None,
    attn_impl: str = "auto",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Symmetric InfoNCE over the GLOBAL batch.

    Under pjit the [B,B] similarity matrix spans every device's samples —
    SPMD gives global negatives without the explicit feature all-gather
    the reference's torch towers need.
    """
    img = encode_image(params, batch["images"], cfg, mesh, attn_impl)
    txt = encode_text(
        params, batch["tokens"], cfg, mesh, batch.get("eot_pos"), attn_impl
    )
    scale = jnp.exp(
        jnp.clip(params["logit_scale"], max=cfg.logit_scale_max)
    )
    logits = scale * (img @ txt.T)  # [B, B] f32
    b = logits.shape[0]
    labels = jnp.arange(b)
    logz_i = jax.nn.logsumexp(logits, axis=1)
    logz_t = jax.nn.logsumexp(logits, axis=0)
    diag = jnp.diagonal(logits)
    loss_i = (logz_i - diag).mean()
    loss_t = (logz_t - diag).mean()
    loss = 0.5 * (loss_i + loss_t)
    acc = (jnp.argmax(logits, axis=1) == labels).astype(jnp.float32).mean()
    return loss, {
        "loss": loss,
        "img_loss": loss_i,
        "txt_loss": loss_t,
        "accuracy": acc,
        "logit_scale": scale,
    }
