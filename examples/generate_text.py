"""Sample continuations from a flash-checkpoint-trained model.

Completes the user loop the other examples start: train (any of the
training examples with --ckpt-dir) -> restore the latest committed
checkpoint -> KV-cache sampling (prefill + incremental decode). With no
checkpoint it samples from a fresh init, exercising the same path.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/generate_text.py --prompt-len 8 --new-tokens 24
"""

import argparse
import sys

sys.path.insert(0, ".")  # repo-root run: `python examples/...`

import jax
import jax.numpy as jnp


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="tiny")
    p.add_argument("--ckpt-dir", default="/tmp/dlrover_tpu_example_ckpt")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=8)
    p.add_argument("--new-tokens", type=int, default=24)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--no-cache", action="store_true",
                   help="full-prefix sampling instead of KV cache")
    args = p.parse_args()

    from dlrover_tpu.checkpoint import Checkpointer
    from dlrover_tpu.models import generate, get_config
    from dlrover_tpu.parallel import MeshConfig, build_mesh
    from dlrover_tpu.train import make_optimizer, restore_or_init_train_state

    cfg = get_config(args.model)
    mesh = build_mesh(MeshConfig(dp=-1))
    opt = make_optimizer(learning_rate=1e-3)
    ckpt = Checkpointer(args.ckpt_dir, use_agent=False)
    state, restored = restore_or_init_train_state(
        ckpt, jax.random.key(0), cfg, mesh, opt
    )
    if restored:
        print(f"[generate] restored step {int(state['step'])}")
    else:
        print("[generate] no checkpoint found; sampling from init")

    prompts = jax.random.randint(
        jax.random.key(1),
        (args.batch, args.prompt_len),
        0,
        cfg.vocab_size,
    )
    out = generate.sample(
        state["params"],
        cfg,
        prompts,
        max_new_tokens=args.new_tokens,
        rng=jax.random.key(2),
        temperature=args.temperature,
        mesh=mesh,
        use_cache=not args.no_cache,
    )
    assert out.shape == (
        args.batch, args.prompt_len + args.new_tokens
    )
    for i in range(args.batch):
        toks = [int(t) for t in out[i]]
        print(f"[generate] seq{i}: {toks[:args.prompt_len]} -> "
              f"{toks[args.prompt_len:]}")
    print("[generate] done", flush=True)


if __name__ == "__main__":
    main()
