"""Defects a program of MiniCPM-SALA's architecture can have, each
planted IN THE PROGRAM by patching it from outside
(``models/decoder.py``, ``ops/ssd.py``), in ``jamba_defects.py``'s
manner, with the check of the ``selected`` comparison that has to catch
it. The selection's are ``defects.BLOCK_CAUGHT_BY``'s that a program can
have (PR 56 planted them in a stand-in; ``short_row`` and
``future_block`` fail ``selection_valid`` on the host whoever makes the
rows, and are the stand-in's); the rest of the model is held by the
teacher-forced logits and loss, the sparse attention's own output by
``sparse_attn_out_ms_vs_reference`` (one sparse layer is a hundredth of
the stream, and an attention that ignores its selection hardly moves the
logits) and the lightning parts' fastest heads' read-out by
``lightning_fast_out_ms_vs_reference`` (they are a hundredth of a
read-out that is normed whole: PERF.md section 6).

``test_sala_cell.py`` runs them at a tiny size on the CPU, float32 on
both sides. On the chip:

    python3 benchmarks/tests/sala_defects.py <defect> --workload
        minicpm-sala-l4-train-b1s16384 --seed N --seconds 5 --trace 0

runs the cell with the defect planted (it must print ``correct:
false``), and

    python3 benchmarks/tests/sala_defects.py --checks <seed>
        [--workload <cell>] [defect ...]

runs, in ONE process on one seeded state, the comparison that decides
``correct`` (``runners/train._check_outputs``: no step is compiled and
none runs) sound and then with each defect planted, one JSON line each
on standard output and in ``chiprun_out/sala_defects.jsonl``.

Each ``inject(setattr)`` takes a ``setattr``-like callable
(``monkeypatch.setattr`` in a test).
"""


def _decoder():
    from dlrover_tpu.models import decoder

    return decoder


# ---- the selection ----------------------------------------------------------

def recent_blocks(patch):
    """The free places go to the most recent blocks, whatever the pooled
    keys say."""
    import jax.numpy as jnp

    decoder = _decoder()
    scores = decoder._block_scores

    def by_recency(q, pooled, qpos, cfg, n_units):
        real = scores(q, pooled, qpos, cfg, n_units)
        return jnp.broadcast_to(
            jnp.arange(n_units, dtype=real.dtype), real.shape
        )

    patch(decoder, "_block_scores", by_recency)


def one_head_scores(patch):
    """A KV head's blocks scored by its FIRST query head alone, not by
    the sum over its sixteen."""
    patch(_decoder(), "_head_sum", lambda p: p[:, :, 0])


def block_mean(patch):
    """A block scored by the mean of its pooled keys, not their max."""
    import jax.numpy as jnp

    patch(_decoder(), "_block_reduce", lambda p: jnp.mean(p, axis=-1))


def pool_no_overlap(patch):
    """Pooled keys every ``pool_window`` keys (stride 32 for 16): every
    other pooled key is not there, and the windows do not overlap."""
    import dataclasses

    decoder = _decoder()
    pooled_keys, scores = decoder._pooled_keys, decoder._block_scores

    def wide(cfg):
        return dataclasses.replace(cfg, pool_stride=cfg.pool_window)

    patch(
        decoder, "_pooled_keys",
        lambda k, window, stride: pooled_keys(k, window, window),
    )
    patch(
        decoder, "_block_scores",
        lambda q, pooled, qpos, cfg, n_units: scores(
            q, pooled, qpos, wide(cfg), n_units
        ),
    )


def group0_for_both(patch):
    """Every KV head's queries attend under KV head 0's selection, and
    that is what is handed over for each."""
    import jax.numpy as jnp

    decoder = _decoder()
    select = decoder._select_blocks

    def first_for_all(q, k, cfg):
        chosen = select(q, k, cfg)
        return jnp.broadcast_to(chosen[:, :1], chosen.shape)

    patch(decoder, "_select_blocks", first_for_all)


def _forced_with(patch, changed):
    """``_forced_blocks`` under ``cfg`` with ``changed(cfg)``'s fields."""
    import dataclasses

    decoder = _decoder()
    forced = decoder._forced_blocks

    def other(qpos, n_units, cfg):
        return forced(
            qpos, n_units, dataclasses.replace(cfg, **changed(cfg))
        )

    patch(decoder, "_forced_blocks", other)


def initial_dropped(patch):
    """The initial block left to its score like any other."""
    _forced_with(patch, lambda cfg: {"select_init_blocks": 0})


def local_dropped(patch):
    """The local window's blocks left to their scores, but for the
    query's own."""
    _forced_with(patch, lambda cfg: {"select_local": cfg.sparse_block})


def blocks_ignored(patch):
    """A valid selection handed over while the attention runs over
    every visible key: the sparse attention not applied at all (the
    kernels on the chip, the jnp attention off it)."""
    import jax.numpy as jnp

    from dlrover_tpu.ops import pallas_attention

    decoder = _decoder()

    def every_key(attention):
        def ignoring(q, k, v, *args, selected=None, **kw):
            if selected is not None:
                selected = jnp.ones_like(selected)
            return attention(q, k, v, *args, selected=selected, **kw)

        return ignoring

    patch(decoder, "mha_reference", every_key(decoder.mha_reference))
    patch(
        pallas_attention, "flash_attention",
        every_key(pallas_attention.flash_attention),
    )


# ---- the rest of the model --------------------------------------------------

def decay_one(patch):
    """lambda = 1 in every head: a state that forgets nothing."""
    import jax.numpy as jnp

    decoder = _decoder()
    patch(
        decoder, "_lightning_decay",
        lambda cfg: jnp.zeros((cfg.n_head,), jnp.float32),
    )


def decay_bf16(patch):
    """The running sum of the log-decays kept in bf16: what a scan
    written in the compute dtype does. Eight bits of a sum that passes
    200 within a chunk in the fastest heads. The forced logits do NOT
    see it on the chip at the cell's size (my chip run, PR 57, seed
    5700000901: max 3.82e-2 of 4e-2, rms 1.63e-2 of 2.5e-2): the heads
    it garbles hold about 1% of the energy of a read-out that is normed
    whole. ``lightning_fast_out_ms``, those heads' own mean square, is
    what holds it (PERF.md section 6)."""
    import jax.numpy as jnp

    from dlrover_tpu.ops import ssd

    class Rounded:
        """``jnp`` as ``ops/ssd.py`` sees it, but for ``cumsum``."""

        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def cumsum(x, axis):
            return jnp.cumsum(x, axis=axis).astype(jnp.bfloat16).astype(
                x.dtype
            )

    patch(ssd, "jnp", Rounded())


def lightning_rope_missing(patch):
    """The lightning parts' q and k not turned."""
    decoder = _decoder()
    block = decoder._lightning_block
    patch(
        decoder, "_lightning_block",
        lambda h, lin, cfg, mesh, rope: block(h, lin, cfg, mesh, None),
    )


def sparse_rope_present(patch):
    """The sparse part's q and k turned by rope, which the model's
    sparse layers do not have (``attn_use_rope`` false)."""
    decoder = _decoder()
    project = decoder._project_qkv

    def with_rope(x, layer, cfg, positions, **kw):
        if kw.get("rope") is False:
            kw["rope"] = None  # made from the positions
        return project(x, layer, cfg, positions, **kw)

    patch(decoder, "_project_qkv", with_rope)


def _lightning_without(patch, *norms):
    """The lightning part with the named per-head norms left out."""
    decoder = _decoder()
    block, norm = decoder._lightning_block, decoder._head_norm

    def norm_or_not(t, scale, cfg):
        return t if scale is None else norm(t, scale, cfg)

    def without(h, lin, cfg, mesh, rope):
        gone = {name: {"scale": None} for name in norms}
        return block(h, {**lin, **gone}, cfg, mesh, rope)

    patch(decoder, "_head_norm", norm_or_not)
    patch(decoder, "_lightning_block", without)


def output_norm_dropped(patch):
    """The recurrence's read-out reaches its gate as it is."""
    _lightning_without(patch, "o_norm")


def lightning_gate_dropped(patch):
    """The lightning parts' output not gated."""
    patch(_decoder(), "_lightning_gate", lambda o, h, w_gate: o)


def sparse_gate_dropped(patch):
    """The sparse part's output not gated."""
    patch(_decoder(), "_gate_output", lambda out, x, w_gate: out)


def scale_emb_dropped(patch):
    """The token embeddings reach the first layer unscaled: the lookup
    hands back rows divided by ``scale_emb`` (12, the model's), which
    the forward's own factor then cancels."""
    import jax.numpy as jnp

    decoder = _decoder()
    embed = decoder._embed_tokens

    def unscaled(params, tokens, mesh, dt):
        return (embed(params, tokens, mesh, jnp.float32) / 12.0).astype(dt)

    patch(decoder, "_embed_tokens", unscaled)


def depth_of_the_cut(patch):
    """Every part's output times 1.4 / sqrt(the layers that are RUN),
    not the published 32."""
    import jax.numpy as jnp

    def scaled(out, cfg):
        return out.astype(jnp.float32) * (1.4 / cfg.n_layer ** 0.5)

    patch(_decoder(), "_residual_scaled", scaled)


def head_divisor_dropped(patch):
    """The last hidden state reaches the head undivided."""
    decoder = _decoder()
    head = decoder.head_weight_scale
    patch(
        decoder, "head_weight_scale",
        lambda params, cfg: (head(params, cfg)[0], 1.0),
    )


def qk_norm_dropped(patch):
    """q and k of both kinds of layer reach their products unnormed."""
    import dataclasses

    decoder = _decoder()
    project = decoder._project_qkv

    def unnormed(x, layer, cfg, positions, **kw):
        return project(
            x, layer, dataclasses.replace(cfg, qk_head_norm=False),
            positions, **kw,
        )

    patch(decoder, "_project_qkv", unnormed)
    _lightning_without(patch, "q_norm", "k_norm")


SELECTION = ("selection_regret", "selection_moved")
LOGITS = (
    "logits_vs_reference", "logits_rms_vs_reference", "loss_vs_reference",
)
OUTPUT = ("sparse_attn_out_ms_vs_reference",)
READ_OUT = ("lightning_fast_out_ms_vs_reference",)
# defect -> the checks of which at least one has to read not ok
CAUGHT_BY = {
    "recent_blocks": ("selection_regret",),
    "one_head_scores": SELECTION,
    "block_mean": SELECTION,
    "pool_no_overlap": SELECTION,
    "group0_for_both": ("selection_regret",),
    "initial_dropped": ("selection_forced",),
    "local_dropped": ("selection_forced",),
    "blocks_ignored": LOGITS[:2] + OUTPUT,
    "decay_one": LOGITS + READ_OUT,
    "decay_bf16": READ_OUT,
    "lightning_rope_missing": LOGITS,
    "sparse_rope_present": LOGITS + SELECTION + OUTPUT,
    "output_norm_dropped": LOGITS,
    "lightning_gate_dropped": LOGITS,
    "sparse_gate_dropped": LOGITS,
    "scale_emb_dropped": LOGITS,
    "depth_of_the_cut": LOGITS,
    "head_divisor_dropped": LOGITS,
    "qk_norm_dropped": LOGITS + SELECTION + OUTPUT,
}
INJECT = {name: globals()[name] for name in CAUGHT_BY}


class _Patches:
    """A ``setattr`` that can be undone."""

    def __init__(self):
        self.undo = []

    def __call__(self, target, name, value):
        self.undo.append((target, name, getattr(target, name)))
        setattr(target, name, value)

    def restore(self):
        for target, name, value in reversed(self.undo):
            setattr(target, name, value)
        self.undo = []


def _checks(seed, cell_name, names):
    """The comparison alone, sound and under each defect, on one seeded
    state: one record a case."""
    import json
    import os
    import time

    import jax

    from benchmarks import run
    from benchmarks.lib import device as devlib
    from benchmarks.lib.watch import synthetic_batch
    from benchmarks.runners import train
    from dlrover_tpu.parallel import MeshConfig, build_mesh
    from dlrover_tpu.train import (
        TrainStepBuilder, batch_sharding, init_train_state, make_optimizer,
    )
    from dlrover_tpu.train.data_utils import form_global_batch

    manifest = run.load_json("BENCHMARK.json")
    cell = run.by_name(manifest["workloads"], cell_name, "workload")
    config = run.load_json(
        run.by_name(manifest["configs"], cell["config"], "config")["file"]
    )
    traffic = run.load_json("benchmarks", "traffic", cell["traffic"] + ".json")
    devices, _, _ = devlib.require_chips(cell["chips"])
    devlib.enable_compile_cache(run.ROOT)
    prog = config["program"]
    cfg = train._program_config(config)
    mesh = build_mesh(MeshConfig(**prog["mesh"]), devices=devices)
    opt = make_optimizer(**prog["optimizer"])
    builder = TrainStepBuilder(cfg, mesh, opt, comm=None)
    state = init_train_state(
        train._seed_key(seed), cfg, mesh, opt, comm=builder.comm_resolved
    )
    # the comparison reads the parameters alone
    state = {"params": jax.block_until_ready(state["params"])}
    batch0 = form_global_batch(
        synthetic_batch(
            seed, 0, traffic["global_batch"], traffic["seq"], cfg.vocab_size
        ),
        batch_sharding(mesh),
    )
    out = os.path.join(run.ROOT, "chiprun_out", "sala_defects.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    ctx = {
        "config": config, "traffic": traffic, "say": lambda **record: None,
    }
    patches = _Patches()
    for name in ("sound", *names):
        if name != "sound":
            INJECT[name](patches)
        t0 = time.perf_counter()
        try:
            results = train._check_outputs(
                ctx, cfg, mesh, state, batch0, devices[0]
            )["results"]
        finally:
            patches.restore()
        checks = {
            check: {"value": run_value(value), "limit": limit, "ok": bool(ok)}
            for check, ok, value, limit in results
        }
        failed = sorted(c for c, r in checks.items() if not r["ok"])
        want = CAUGHT_BY.get(name, ())
        record = {
            "case": name, "seed": seed, "failed": failed,
            "caught_by_a_named_check": bool(set(failed) & set(want)),
            "named": list(want), "checks": checks,
            "seconds": time.perf_counter() - t0,
        }
        line = json.dumps(record)
        print(line, flush=True)
        with open(out, "a") as f:
            f.write(line + "\n")


def run_value(value):
    return value if isinstance(value, (int, float, str)) else float(value)


if __name__ == "__main__":
    import os
    import sys

    sys.path[0] = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    if sys.argv[1] == "--checks":
        args = sys.argv[3:]
        cell = "minicpm-sala-l4-train-b1s16384"
        if args[:1] == ["--workload"]:
            cell, args = args[1], args[2:]
        _checks(int(sys.argv[2]), cell, args or sorted(INJECT))
        sys.exit(0)
    from benchmarks import run

    INJECT[sys.argv[1]](setattr)
    sys.exit(run.main(sys.argv[2:]))
