"""CLIP contrastive pretraining on a sharded mesh (synthetic data).

Run (8-device virtual CPU mesh):

    JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train_clip.py --steps 10

Demonstrates the vision family (models/vision.py): ViT image tower +
causal text tower, symmetric InfoNCE over the GLOBAL batch — under pjit
the [B,B] similarity matrix spans every device's samples, so SPMD
provides the global negatives the reference's torch towers need explicit
all_gathers for (SURVEY §2.3, atorch TP CLIP blocks).
"""

import argparse
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_tpu.models.vision import (
    clip_tiny_test,
    clip_logical_axes,
    clip_loss,
    init_clip,
)
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.parallel import sharding as shd


def synthetic_batch(rng, b=32):
    """Correlated (image, caption) pairs from 16 latent classes."""
    cls = rng.integers(0, 16, size=b)
    shades = np.random.default_rng(7).normal(size=(16, 3))
    imgs = np.broadcast_to(
        shades[cls][:, None, None, :], (b, 32, 32, 3)
    ).astype(np.float32)
    imgs = imgs + rng.normal(scale=0.05, size=imgs.shape)
    tokens = np.broadcast_to((cls + 1)[:, None], (b, 8)).astype(np.int32)
    return {
        "images": jnp.asarray(imgs, jnp.float32),
        "tokens": jnp.asarray(tokens),
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--lr", type=float, default=3e-3)
    args = p.parse_args()

    n_dev = jax.device_count()
    mesh = build_mesh(MeshConfig(dp=n_dev))
    cfg = clip_tiny_test()
    params = jax.device_put(
        init_clip(jax.random.key(0), cfg),
        shd.shardings_for_tree(mesh, clip_logical_axes(cfg)),
    )
    opt = optax.adam(args.lr)
    opt_state = opt.init(params)
    bsh = shd.shardings_for_tree(
        mesh,
        {"images": ("batch", None, None, None), "tokens": ("batch", None)},
    )

    @jax.jit
    def step(params, opt_state, batch):
        (_, metrics), grads = jax.value_and_grad(
            clip_loss, has_aux=True
        )(params, batch, cfg, mesh=mesh)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, metrics

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(1, args.steps + 1):
        batch = jax.device_put(synthetic_batch(rng, args.batch), bsh)
        params, opt_state, m = step(params, opt_state, batch)
        print(
            f"[clip] step={i} loss={float(m['loss']):.4f} "
            f"acc={float(m['accuracy']):.3f} "
            f"scale={float(m['logit_scale']):.2f}"
        )
    dt = time.perf_counter() - t0
    print(f"[clip] done at step {args.steps} ({dt:.1f}s, dp={n_dev})")


if __name__ == "__main__":
    main()
