"""BERT-family masked-LM pretraining on a sharded mesh (synthetic data).

Run (8-device virtual CPU mesh):

    JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train_bert_mlm.py --steps 10

The encoder family needs no separate model: ``causal=False`` turns the
shared trunk bidirectional, and ``decoder.loss_fn`` already scores
arbitrary (tokens, targets, mask) triples — MLM is corrupted tokens in,
original tokens as targets, loss masked to the corrupted positions
(reference: atorch's TP BERT blocks, distributed_modules/transformer.py:45;
here the same weights/sharding machinery as GPT, different mask).
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
from dlrover_tpu.train import (
    TrainStepBuilder,
    batch_sharding,
    init_train_state,
    make_optimizer,
)

MASK_ID = 3  # [MASK] sentinel in the synthetic vocab


def mlm_batch(rng, b, s, vocab, mask_rate=0.15):
    """BERT recipe: of the selected positions, 80% → [MASK], 10% →
    random token, 10% unchanged; loss only on selected positions."""
    original = rng.integers(4, vocab, size=(b, s)).astype(np.int32)
    selected = rng.random((b, s)) < mask_rate
    roll = rng.random((b, s))
    corrupted = original.copy()
    corrupted[selected & (roll < 0.8)] = MASK_ID
    rand_pos = selected & (roll >= 0.8) & (roll < 0.9)
    corrupted[rand_pos] = rng.integers(
        4, vocab, size=int(rand_pos.sum())
    ).astype(np.int32)
    return {
        "tokens": jnp.asarray(corrupted),
        "targets": jnp.asarray(original),
        "mask": jnp.asarray(selected.astype(np.float32)),
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--seq", type=int, default=64)
    args = p.parse_args()

    n_dev = jax.device_count()
    mesh = build_mesh(MeshConfig(dp=n_dev))
    cfg = get_config("tiny-bert", max_seq=args.seq)
    opt = make_optimizer(
        learning_rate=1e-3, warmup_steps=5, decay_steps=500
    )
    state = init_train_state(jax.random.key(0), cfg, mesh, opt)
    step = TrainStepBuilder(cfg, mesh, opt).build()
    bsh = batch_sharding(mesh)

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(1, args.steps + 1):
        batch = jax.device_put(
            mlm_batch(rng, args.batch, args.seq, cfg.vocab_size), bsh
        )
        state, m = step(state, batch)
        print(
            f"[bert-mlm] step={i} loss={float(m['loss']):.4f} "
            f"masked_acc={float(m['accuracy']):.3f}"
        )
    print(
        f"[bert-mlm] done at step {args.steps} "
        f"({time.perf_counter() - t0:.1f}s, dp={n_dev})"
    )


if __name__ == "__main__":
    main()
