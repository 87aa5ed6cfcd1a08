"""GLM-style prefix-LM training (blank infilling) on a sharded mesh.

Run (8-device virtual CPU mesh):

    JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train_glm.py --steps 10

Demonstrates the prefix-LM family (models/config.py tiny-glm / glm-10b):
each sequence's prefix (the "part A" context) is bidirectionally visible
while the tail is generated causally — the mask rule runs inside the
flash kernel (per-batch prefix scalar in SMEM) and through ring/ulysses
sequence parallelism. The loss is masked to the causal tail, the GLM
objective.
"""

import argparse
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.models import get_config
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.parallel import sharding as shd
from dlrover_tpu.train import (
    TrainStepBuilder,
    batch_sharding,
    init_train_state,
    make_optimizer,
)


def infilling_batch(rng, b, s, vocab):
    """Synthetic GLM-shaped batch: random tokens with a per-sequence
    prefix/tail split — the prefix is bidirectionally visible context
    and the loss scores only the causal tail (the GLM objective shape;
    the data itself is random, this demonstrates plumbing not MLM)."""
    toks = rng.integers(4, vocab, size=(b, s)).astype(np.int32)
    prefix = rng.integers(s // 4, 3 * s // 4, size=(b,)).astype(np.int32)
    pos = np.arange(s)[None, :]
    mask = (pos >= prefix[:, None]).astype(np.float32)
    targets = np.roll(toks, -1, axis=1)
    return {
        "tokens": jnp.asarray(toks),
        "targets": jnp.asarray(targets),
        "mask": jnp.asarray(mask),           # score only the causal tail
        "prefix_len": jnp.asarray(prefix),
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    args = p.parse_args()

    n_dev = jax.device_count()
    mesh = build_mesh(MeshConfig(dp=n_dev))
    cfg = get_config("tiny-glm", max_seq=args.seq, n_layer=2)
    opt = make_optimizer(
        learning_rate=1e-3, warmup_steps=5, decay_steps=500
    )
    state = init_train_state(jax.random.key(0), cfg, mesh, opt)
    step = TrainStepBuilder(cfg, mesh, opt).build()
    bsh = batch_sharding(mesh)
    psh = shd.shardings_for_tree(mesh, {"p": ("batch",)})["p"]

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(1, args.steps + 1):
        batch = infilling_batch(rng, args.batch, args.seq, cfg.vocab_size)
        batch = {
            k: jax.device_put(v, psh if v.ndim == 1 else bsh)
            for k, v in batch.items()
        }
        state, m = step(state, batch)
        print(
            f"[glm] step={i} loss={float(m['loss']):.4f} "
            f"acc={float(m['accuracy']):.3f}"
        )
    print(
        f"[glm] done at step {args.steps} "
        f"({time.perf_counter() - t0:.1f}s, prefix-LM over dp={n_dev})"
    )


if __name__ == "__main__":
    main()
