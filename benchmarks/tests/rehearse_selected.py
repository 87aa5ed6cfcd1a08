#!/usr/bin/env python3
"""The chip rehearsal of the ``selected`` comparison, as it was run for
PR 36 (PERF.md section 4) and, by BLOCKS of keys, for PR 56: a stand-in
judged against a plain reference by ``lib/selected.py``, seed after
seed, and then with each defect planted. Two shapes, as data:

- ``--shape keye`` (PR 36): ``sparse_standin.py`` against
  ``sparse_plain.py`` at Keye-VL-2.0's language widths, a selection of
  2,048 KEYS by an indexer, one a layer, B 1, S 8192; the defects of
  ``defects.SELECTED_INJECT``;
- ``--shape sala`` (PR 56): ``block_standin.py`` against
  ``block_plain.py`` at MiniCPM-SALA's sparse layer, a selection of 64
  BLOCKS of 64 keys by pooled keys, one a KV head, 33 of them forced
  by the model's rule, B 1, S 16384; the defects of
  ``defects.BLOCK_INJECT``.

No test (pytest does not collect it) and no part of a benchmark run:

    python3 benchmarks/tests/rehearse_selected.py --shape sala --layers 1 \\
        --seeds 12 --defect-seeds 1 --out chiprun_out/rehearse56.jsonl

One JSON line a reading: the checks (name, ok, value, limit) and the
``BENCH reference`` record. ``--tiny`` runs the same at a size the CPU
holds. The limits in ``lib/selected.py`` were set from these lines.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

TINY = {
    "d_model": 128, "n_head": 4, "n_kv_head": 2, "head_dim": 32,
    "d_ff": 256, "vocab_size": 512, "index_n_heads": 8,
    "index_head_dim": 16, "index_topk": 16,
}
# the block selection's: 6 of a query's blocks of 16 keys, 3 of them
# forced (the first and a local window of 2), pooled 8 keys every 4
TINY_BLOCKS = {
    "d_model": 128, "n_head": 4, "n_kv_head": 2, "head_dim": 32,
    "d_ff": 256, "vocab_size": 512, "index_topk": 6, "select_block": 16,
    "pool_window": 8, "pool_stride": 4, "select_local": 32,
}


def shape(name):
    """A rehearsal shape: its stand-in and reference modules, widths and
    sequence at the chip's size and at ``--tiny``'s, and its defects."""
    import types

    from benchmarks.tests import (
        block_plain, block_standin, defects, sparse_plain, sparse_standin,
    )

    if name == "keye":
        return types.SimpleNamespace(
            standin=sparse_standin, plain=sparse_plain,
            widths=sparse_standin.KEYE_WIDTHS, tiny=TINY, seq=8192,
            tiny_seq=128, inject=defects.SELECTED_INJECT,
        )
    return types.SimpleNamespace(
        standin=block_standin, plain=block_plain,
        widths=block_standin.SALA_WIDTHS, tiny=TINY_BLOCKS, seq=16384,
        tiny_seq=256, inject=defects.BLOCK_INJECT,
    )


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--shape", choices=("keye", "sala"), default="keye")
    p.add_argument("--layers", type=int, default=1)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first-seed", type=int, default=3600001000)
    p.add_argument("--defect-seeds", type=int, default=0)
    p.add_argument("--defects", default="", help="names, comma-separated; "
                   "absent: every defect of the shape")
    p.add_argument("--seq", type=int, default=0, help="absent: the shape's")
    p.add_argument("--q-block", type=int, default=512)
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--multipliers", action="store_true", help="shape sala: "
                   "with the model's embedding, residual and head scales")
    p.add_argument("--out", default="")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks.lib.watch import hbm, synthetic_batch
    from benchmarks.runners.train import (
        LOGIT_RMS_TOL, LOGIT_TOL, LOSS_TOL, _seed_key,
    )

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_compile_cache"
    )
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    case = shape(args.shape)
    standin, plain, inject = case.standin, case.plain, case.inject
    sizes = dict(case.widths, n_layer=args.layers)
    if args.multipliers:
        sizes.update(standin.SALA_MULTIPLIERS)
    seq = args.seq or case.seq
    if args.tiny:
        sizes.update(case.tiny)
        seq = min(seq, case.tiny_seq)
    out = open(args.out, "a") if args.out else None

    def reading(seed, defect):
        undo = []

        def patch(module, name, value):
            undo.append((module, name, getattr(module, name)))
            setattr(module, name, value)

        if defect:
            inject[defect](patch)
        try:
            params = standin.init(_seed_key(seed), sizes)
            batch = {
                k: jnp.asarray(v) for k, v in synthetic_batch(
                    seed, 0, 1, seq, sizes["vocab_size"]
                ).items()
            }
            t0 = time.perf_counter()
            checks, record = standin.judge(
                plain, params, batch, sizes, args.q_block,
                (LOGIT_TOL, LOGIT_RMS_TOL, LOSS_TOL),
            )
            wall = time.perf_counter() - t0
        finally:
            for module, name, value in reversed(undo):
                setattr(module, name, value)
        line = {
            "shape": args.shape, "multipliers": args.multipliers,
            "layers": args.layers, "seq": seq,
            "seed": seed,
            "defect": defect, "wall_s": wall,
            "device": jax.devices()[0].device_kind,
            "checks": checks, "record": record,
            "hbm_peak": max(hbm(jax.devices()[:1])["peak_bytes_in_use"]),
        }
        text = json.dumps(line, default=repr)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
        failed = [name for name, ok, _v, _l in checks if not ok]
        print(
            f"# layers {args.layers} seed {seed} defect {defect}: "
            f"failed {failed} in {wall:.1f}s", file=sys.stderr, flush=True,
        )

    seeds = [args.first_seed + i for i in range(args.seeds)]
    for seed in seeds:
        reading(seed, None)
    for defect in args.defects.split(",") if args.defects else sorted(inject):
        for seed in seeds[: args.defect_seeds]:
            reading(seed, defect)


if __name__ == "__main__":
    main()
