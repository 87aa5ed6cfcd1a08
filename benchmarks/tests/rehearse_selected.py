#!/usr/bin/env python3
"""The chip rehearsal of the ``selected`` comparison, as it was run for
PR 36 (PERF.md section 4): the stand-in (``sparse_standin.py``) judged
against the plain reference (``sparse_plain.py``) by ``lib/selected.py``
at Keye-VL-2.0's language widths, B 1, S 8192, seed after seed, and then
with each defect of ``defects.SELECTED_INJECT`` planted. No test (pytest
does not collect it) and no part of a benchmark run:

    python3 benchmarks/tests/rehearse_selected.py --layers 1 --seeds 12 \\
        --defect-seeds 3 --out chiprun_out/rehearse36.jsonl

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


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--layers", type=int, default=1)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first-seed", type=int, default=3600001000)
    p.add_argument("--defect-seeds", type=int, default=0)
    p.add_argument("--seq", type=int, default=8192)
    p.add_argument("--q-block", type=int, default=512)
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--out", default="")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks.lib.watch import hbm, synthetic_batch
    from benchmarks.runners.train import (
        LOGIT_RMS_TOL, LOGIT_TOL, LOSS_TOL, _seed_key,
    )
    from benchmarks.tests import defects, sparse_plain, sparse_standin

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_compile_cache"
    )
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    sizes = dict(sparse_standin.KEYE_WIDTHS, n_layer=args.layers)
    seq = args.seq
    if args.tiny:
        sizes.update(TINY)
        seq = min(seq, 128)
    out = open(args.out, "a") if args.out else None

    def reading(seed, defect):
        undo = []

        def patch(module, name, value):
            undo.append((module, name, getattr(module, name)))
            setattr(module, name, value)

        if defect:
            defects.SELECTED_INJECT[defect](patch)
        try:
            params = sparse_standin.init(_seed_key(seed), sizes)
            batch = {
                k: jnp.asarray(v) for k, v in synthetic_batch(
                    seed, 0, 1, seq, sizes["vocab_size"]
                ).items()
            }
            t0 = time.perf_counter()
            checks, record = sparse_standin.judge(
                sparse_plain, params, batch, sizes, args.q_block,
                (LOGIT_TOL, LOGIT_RMS_TOL, LOSS_TOL),
            )
            wall = time.perf_counter() - t0
        finally:
            for module, name, value in reversed(undo):
                setattr(module, name, value)
        line = {
            "layers": args.layers, "seq": seq, "seed": seed,
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
    for defect in sorted(defects.SELECTED_INJECT):
        for seed in seeds[: args.defect_seeds]:
            reading(seed, defect)


if __name__ == "__main__":
    main()
