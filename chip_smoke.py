#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the two main paths start on a TPU.

    python chip_smoke.py            # one chip: train, then serve
    python chip_smoke.py --chips 4  # four chips: dp=4 ZeRO-1 vs one chip

Drives the system through the entry points a user would call, at the full
published width and depth of GPT-2 XL (``gpt2-1.5b``: 48 layers, d 1600,
25 heads x 64, d_ff 6400, vocab 50257), weights random from ``--seed``:

- ``train``: ``python -m dlrover_tpu.agent.launcher`` spawns a worker that
  trains at b8 x s1024 (bf16 params, bf16 AdamW state, full remat — the
  recipe of the repo's only chip rows), flash-checkpoints to memory and
  to disk, crashes on purpose, is restarted by the agent, resumes from
  the saved step out of a warm compile cache, and finishes.
- ``serve``: a ``GenerationServer`` (scheduler -> ``ServingEngine`` ->
  paged kernel) answers a few 512..992-token prompts with 32 new tokens
  each, once with int8 KV pools (the default) and once with bf16; the
  first decode step's logits are compared with ``decoder.forward``, and
  the paged kernel alone with float64 attention over pools of known
  rows (bf16 pools hold them bit for bit, int8 within the quantiser's
  bound).
- ``--chips 4`` (no other phase runs): the dp=4 ZeRO-1 train step through
  the same launcher, and the one-chip run of the same seed and global
  batch through ``ElasticTrainer``'s grad accumulation — the framework's
  invariant that the world size does not change the loss.

The parent never imports jax: a chip belongs to one process, so every
phase is a child, run one after another, each printing ``SMOKE {json}``
records. Any failed phase, a platform other than ``tpu``, a compiled
program without a ``tpu_custom_call``, a non-finite loss or a mismatch
with the reference exits non-zero and prints no result line. On success
the last stdout line is ``{"ok": true, "device": {...}}``.
"""

import argparse
import glob
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
# children re-enter through the script that was started (a rehearsal
# wrapper under /root/scratch patches sizes, then calls main())
SELF = os.path.abspath(sys.argv[0])
WORK = os.path.join(HERE, ".chip_smoke_work")
RECORD = "SMOKE "

MODEL = "gpt2-1.5b"

TRAIN = dict(
    batch=8, seq=1024, steps=8, ckpt_every=2, crash_at=5, remat="full",
    param_dtype="bfloat16", state_dtype="bfloat16", model_kw={},
)
SERVE = dict(
    n_slots=12, max_len=1024, page_size=16, prefill_chunk=256,
    prompt_lens=(512, 640, 768, 896, 992, 512), max_new=32, model_kw={},
)
# ZeRO's flat optimizer packs an f32 master-param stream, and the
# one-chip side holds params + both moments + an accumulation buffer in
# one chip's HBM: full width, depth cut to 12 layers
DP = dict(
    batch=32, micro=8, seq=1024, steps=3, remat="full",
    param_dtype="float32", model_kw={"n_layer": 12},
)
# first-decode-step logits vs decoder.forward, max |diff| over max |ref|:
# the whole serving path against the plain model. What sets this error
# is 48 layers of a bf16 residual stream rounded in two different orders
# (online softmax with f32 probabilities vs a bf16 probability matrix),
# not the pools: 1.3e-2 (prefill row) and 1.5e-2 (decode row) with bf16
# pools on a v5e at seed 0, 1.5e-2 and 1.5e-2 with int8. Held to twice
# that; it catches a wrong token, table or position, and cannot tell the
# two pool modes apart — KERNEL_TOL below is the check that can.
LOGIT_TOL = {"bf16": 3e-2, "int8": 4e-2}
# the paged kernel alone, on one layer's pools of the engine's geometry
# filled with known rows through the engine's own writer:
# - "pool": what the pools hold against what was written, as a multiple
#   of the mode's bound. bf16 pools hold the rows bit for bit (bound 0,
#   so any difference fails — an int8 path fails here). int8 pools may
#   be off by the quantiser's bound and no more: half a step of a
#   (token, head) row's scale, max|row|/254, plus the dequantised
#   value's rounding to bf16, |x|/256 (0.97 of it on a v5e at seed 0).
# - "max" / "rms": the kernel (prefill chunk and decode) against plain
#   float64 attention over what the pools hold, so the pool mode cancels
#   and both modes are held alike. The kernel works in f32 and rounds
#   its output to bf16 once: no element may be off by more than that
#   rounding at the output's scale (2**-8 = 3.9e-3 of max |ref|;
#   2.6e-3..3.4e-3 on the chip), and the rms of the difference over the
#   rms of the reference is that of uniform rounding, 1.7e-3 (measured:
#   1.71e-3..1.73e-3 in both modes): there is room for the output's
#   rounding and not for a second one of its size.
KERNEL_TOL = {"pool": 1.0, "max": 2.0 ** -8, "rms": 2e-3}
# dp=4 vs one chip, relative loss difference per step. Both sides run the
# same 8-row microbatches on f32 params with bf16 activations, but
# through two XLA programs (a dp-manual region vs a microbatch scan), and
# they sum four gradients in a different order. On a v5e 2x2 the first
# two losses came out bitwise equal and the third 2.6e-6 apart.
DP_LOSS_RTOL = 1e-4


def emit(**rec):
    print(RECORD + json.dumps(rec), flush=True)


# ---------------------------------------------------------------------------
# Parent: no jax in this process
# ---------------------------------------------------------------------------


class PhaseFailed(Exception):
    pass


def _child_env(run_id):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (HERE, env.get("PYTHONPATH", "")) if p
    )
    env["DLROVER_TPU_RUN_ID"] = run_id
    env["DLROVER_TPU_SOCK_DIR"] = os.path.join(WORK, "sock")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def _maps_libtpu(pid):
    try:
        with open(f"/proc/{pid}/maps") as f:
            return "libtpu" in f.read()
    except OSError:
        return False


def run_child(name, cmd, env, timeout_s, watch_off_device=False):
    """Run one phase, echo its output, return its SMOKE records. The
    child leads its own process group so everything it started dies
    with it."""
    print(f"[chip_smoke] phase {name}: {' '.join(cmd)}", flush=True)
    t0 = time.monotonic()
    proc = subprocess.Popen(
        cmd, env=env, cwd=HERE, stdout=subprocess.PIPE, text=True,
        start_new_session=True,
    )
    records = []
    touched_device = False

    def kill_group():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    # a child that hangs in silence never ends the read loop by itself
    watchdog = threading.Timer(timeout_s, kill_group)
    watchdog.start()
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if line.startswith(RECORD):
                records.append(json.loads(line[len(RECORD):]))
            if watch_off_device and not touched_device:
                touched_device = _maps_libtpu(proc.pid)
        rc = proc.wait()
    finally:
        timed_out = not watchdog.is_alive()
        watchdog.cancel()
        kill_group()
        proc.wait()
    if timed_out:
        raise PhaseFailed(f"{name}: no end after {timeout_s}s")
    if rc != 0:
        raise PhaseFailed(f"{name}: exit code {rc}")
    if touched_device:
        raise PhaseFailed(
            f"{name}: the launcher's own process mapped libtpu — the "
            "agent must leave the chip to its worker"
        )
    print(
        f"[chip_smoke] phase {name} done in {time.monotonic() - t0:.1f}s",
        flush=True,
    )
    return records


def phase_cmd(phase, *extra):
    return [sys.executable, SELF, "--phase", phase, *extra]


def launcher_cmd(nproc, phase, *extra):
    return [
        sys.executable, "-m", "dlrover_tpu.agent.launcher",
        "--nnodes", "1", "--nproc", str(nproc), "--max-restarts", "2",
        "--monitor-interval", "1", "--", *phase_cmd(phase, *extra),
    ]


def check(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


def _one(records, **match):
    hits = [
        r for r in records
        if all(r.get(k) == v for k, v in match.items())
    ]
    check(hits, f"no record matching {match}")
    return hits[-1]


def check_device(rec, chips):
    dev = rec["device"]
    check(dev["platform"] == "tpu", f"platform is {dev['platform']!r}")
    check(dev["count"] == chips, f"{dev['count']} devices, want {chips}")
    return dev


def check_capacity(state_gb):
    """The train state is staged to /dev/shm and persisted under WORK:
    say so in a sentence if either cannot hold it, instead of a SIGBUS
    or an OSError in the saver's thread."""
    need = int(state_gb * 1.1e9)
    for what, path in (("/dev/shm", "/dev/shm"), ("the work dir", WORK)):
        free = shutil.disk_usage(path).free
        check(
            free >= need,
            f"{what} has {free / 1e9:.1f} GB free; the checkpoint of the "
            f"train state needs {need / 1e9:.1f} GB there",
        )


def parent_train(env, args):
    check_capacity(state_gb=9.5)
    ckpt = os.path.join(WORK, "ckpt")
    recs = run_child(
        "train",
        launcher_cmd(
            1, "train-worker", "--seed", str(args.seed), "--ckpt-dir", ckpt
        ),
        env, timeout_s=900, watch_off_device=True,
    )
    first = _one(recs, event="compiled", attempt=0)
    again = _one(recs, event="compiled", attempt=1)
    for rec in (first, again):
        check_device(rec, 1)
    steps = [r for r in recs if r.get("event") == "step"]
    check(steps, "no train step ran")
    check(
        all(math.isfinite(r["loss"]) for r in steps),
        f"non-finite loss: {[r['loss'] for r in steps]}",
    )
    kinds = {r["kind"] for r in recs if r.get("event") == "saved"}
    check({"memory", "disk"} <= kinds, f"checkpoint kinds saved: {kinds}")
    check(
        _one(recs, event="crash")["attempt"] == 0, "crash outside attempt 0"
    )
    resumed = _one(recs, event="resumed", attempt=1)
    saved = _one(recs, event="saved", step=resumed["step"], attempt=0)
    # the agent persisted the staged step before it restarted the worker
    check(
        resumed["committed_step"] == resumed["step"],
        f"step {resumed['step']} was restored but storage holds step "
        f"{resumed['committed_step']}",
    )
    check(
        resumed["checksum"] == saved["checksum"],
        f"restored parameters differ from those saved at step "
        f"{resumed['step']}: {resumed['checksum']} != {saved['checksum']}",
    )
    check(
        again["step_cache_hits"] == 1 and again["step_cache_misses"] == 0,
        f"restarted worker missed the cache: {again['step_cache_hits']} "
        f"hits, {again['step_cache_misses']} misses in its first step",
    )
    # where the machine keeps a cache between calls the first attempt
    # hits it too, and there is no cold compile to compare with
    check(
        first["step_cache_hits"]
        or again["compile_s"] < 0.5 * first["compile_s"],
        f"restart compiled in {again['compile_s']:.1f}s, first "
        f"{first['compile_s']:.1f}s: not a cache hit",
    )
    done = _one(recs, event="done", attempt=1)
    check(done["step"] > resumed["step"], "no step after the resume")
    check(first["pid"] != again["pid"], "worker was not restarted")
    return first["device"]


def parent_serve(env, args):
    dev = None
    for mode in ("int8", "bf16"):
        recs = run_child(
            f"serve:{mode}",
            phase_cmd("serve", "--mode", mode, "--seed", str(args.seed)),
            env, timeout_s=900,
        )
        rec = _one(recs, event="served", mode=mode)
        dev = check_device(rec, 1)
        check(
            rec["requests_ok"] == len(SERVE["prompt_lens"]),
            f"{mode}: {rec['requests_ok']} requests answered",
        )
        for which in ("prefill_logit_err", "decode_logit_err"):
            check(
                rec[which] <= LOGIT_TOL[mode],
                f"{mode} {which} {rec[which]:.4g} > {LOGIT_TOL[mode]}",
            )
        check(rec["engine_tokens_ok"], f"{mode}: engine tokens off argmax")
        for what, errs in rec["kernel_check"].items():
            for which, err in errs.items():
                check(
                    err <= KERNEL_TOL[which],
                    f"{mode} paged kernel, {what}: {which} error "
                    f"{err:.4g} > {KERNEL_TOL[which]:.4g}",
                )
    return dev


def parent_dp(env, args):
    runs = {}
    for n in (4, 1):
        recs = run_child(
            f"dp{n}",
            launcher_cmd(
                n, "dp-worker", "--devices", str(n), "--seed", str(args.seed)
            ),
            env, timeout_s=900, watch_off_device=True,
        )
        rec = _one(recs, event="dp_done")
        check_device(rec, 4)
        check(
            all(math.isfinite(x) for x in rec["losses"]),
            f"dp{n} non-finite loss: {rec['losses']}",
        )
        runs[n] = rec
    check(runs[4]["update_sharding"], "dp=4 fell back to a replicated update")
    used = runs[4]["hbm"]["bytes_in_use"]
    check(
        len(used) == 4 and min(used) > 0.5 * max(used),
        f"state is not spread over the four devices: bytes_in_use {used}",
    )
    for a, b in zip(runs[4]["losses"], runs[1]["losses"]):
        check(
            abs(a - b) <= DP_LOSS_RTOL * abs(b),
            f"dp=4 loss {a} vs one-chip {b}: beyond rtol {DP_LOSS_RTOL}",
        )
    emit(
        event="dp_compare", losses_dp4=runs[4]["losses"],
        losses_dp1=runs[1]["losses"], rtol=DP_LOSS_RTOL,
    )
    return runs[4]["device"]


def remove_shm(run_id):
    for path in glob.glob(f"/dev/shm/dlrover_tpu_ckpt_{run_id}_*"):
        os.unlink(path)


def parent_main(args):
    run_id = f"smoke{os.getpid()}"
    env = _child_env(run_id)
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(os.path.join(WORK, "sock"))
    try:
        # seconds, not minutes: without a chip nothing heavy starts
        probe = _one(
            run_child("probe", phase_cmd("probe"), env, timeout_s=120),
            event="probe",
        )
        check_device(probe, args.chips)
        if args.chips == 4:
            dev = parent_dp(env, args)
        else:
            dev = parent_train(env, args)
            check(dev == parent_serve(env, args), "phases disagree on device")
    except PhaseFailed as exc:
        print(f"[chip_smoke] FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    finally:
        remove_shm(run_id)
        shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# Children: each one process, each the only holder of the chip
# ---------------------------------------------------------------------------


def _device_record():
    import jax

    from dlrover_tpu.common import device

    info = device.require_tpu()
    return {
        "platform": info.platform, "kind": info.device_kind,
        "count": info.count,
    }, jax.__version__


class CompileWatch:
    """What jax's own monitoring says of this process's compiles: how
    many executables came out of the persistent cache (hits) or out of
    the compiler (misses), and the seconds spent tracing, lowering and
    compiling — or fetching, on a hit."""

    _SECONDS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration
        )

    def _on_event(self, event, **_kw):
        self.hits += event == "/jax/compilation_cache/cache_hits"
        self.misses += event == "/jax/compilation_cache/cache_misses"

    def _on_duration(self, event, seconds, **_kw):
        if event in self._SECONDS:
            self.seconds += seconds

    def since(self, mark=(0, 0, 0.0)):
        """(hits, misses, seconds) since ``mark``, itself a ``since()``."""
        now = (self.hits, self.misses, self.seconds)
        return tuple(a - b for a, b in zip(now, mark))


def _hbm():
    import jax

    # arrays are "in use"; a running program's temporaries are
    # "reserved" — the peak a chip must hold is the sum of the two
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return {
        key: [s.get(key, 0) for s in stats]
        for key in (
            "bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved",
            "bytes_limit",
        )
    }


def _checksum(tree):
    """Exact, order-independent fingerprint of a pytree's bits."""
    import jax
    import jax.numpy as jnp

    def leaf(x):
        bits = jax.lax.bitcast_convert_type(
            x, {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        )
        return jnp.sum(bits.astype(jnp.uint32), dtype=jnp.uint32)

    total = jax.jit(
        lambda t: sum(leaf(x) for x in jax.tree.leaves(t))
    )(tree)
    return int(total)


def _synthetic_batch(start, batch, seq, vocab):
    import numpy as np

    data = np.random.RandomState(start).randint(
        0, vocab, size=(batch, seq + 1)
    )
    return {"tokens": data[:, :-1], "targets": data[:, 1:]}


def child_probe(args):
    import psutil

    dev, version = _device_record()
    emit(
        event="probe", device=dev, jax=version,
        host={
            "cpus": os.cpu_count(),
            "ram_gb": psutil.virtual_memory().total / 1e9,
            "shm_free_gb": shutil.disk_usage("/dev/shm").free / 1e9,
            "disk_free_gb": shutil.disk_usage(HERE).free / 1e9,
        },
    )
    return 0


def child_train_worker(args):
    """The calls of examples/train_gpt_elastic.py, at the smoke's recipe."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.agent.master_client import build_master_client
    from dlrover_tpu.agent.sharding_client import ShardingClient
    from dlrover_tpu.checkpoint import Checkpointer, StorageType
    from dlrover_tpu.common import compile_cache, device
    from dlrover_tpu.models import get_config
    from dlrover_tpu.parallel import MeshConfig, build_mesh
    from dlrover_tpu.train import (
        TrainStepBuilder, batch_sharding, make_optimizer,
        restore_or_init_train_state,
    )
    from dlrover_tpu.train.data_utils import (
        form_global_batch, iter_shards_spmd,
    )
    from dlrover_tpu.train.distributed import init_distributed
    from dlrover_tpu.train.train_step import abstract_train_state

    t = TRAIN
    attempt = int(os.environ.get("DLROVER_TPU_RESTART_COUNT", "0"))
    dev, version = _device_record()
    cache_dir = compile_cache.enable_compile_cache()
    watch = CompileWatch()
    init_distributed()
    client = build_master_client()
    mesh = build_mesh(MeshConfig(dp=-1))
    cfg = get_config(
        MODEL, max_seq=t["seq"], remat=t["remat"],
        param_dtype=t["param_dtype"], **t["model_kw"],
    )
    opt = make_optimizer(
        learning_rate=1e-4, warmup_steps=10, decay_steps=1000,
        state_dtype=t["state_dtype"],
    )
    builder = TrainStepBuilder(cfg, mesh, opt)
    step = builder.build()
    ckpt = Checkpointer(args.ckpt_dir, master_client=client)
    t0 = time.perf_counter()
    state, resumed = restore_or_init_train_state(
        ckpt, jax.random.key(args.seed), cfg, mesh, opt
    )
    if resumed:
        jax.block_until_ready(state)
        emit(
            event="resumed", attempt=attempt, step=int(state["step"]),
            restore_s=time.perf_counter() - t0,
            committed_step=ckpt.latest_committed_step(),
            checksum=_checksum(state["params"]),
        )
    bsh = batch_sharding(mesh)
    sharding = ShardingClient(
        client, "train", dataset_size=t["steps"] * t["batch"],
        shard_size=t["batch"],
    )
    compiled = False
    for start, _end in iter_shards_spmd(sharding):
        n = int(state["step"])
        if attempt == 0 and n >= t["crash_at"]:
            emit(event="crash", attempt=attempt, step=n)
            os._exit(17)
        batch = form_global_batch(
            _synthetic_batch(start, t["batch"], t["seq"], cfg.vocab_size),
            bsh,
        )
        mark = watch.since()
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        loss = float(metrics["loss"])  # host readback: the step is over
        step_s = time.perf_counter() - t0
        n = int(state["step"])
        if not compiled:
            # the first call of the jitted step, as any worker makes it:
            # whatever it compiled or fetched happened inside it
            compiled = True
            hits, misses, compile_s = watch.since(mark)
            # its kernels, read from the same program lowered once more
            # (the template spells shardings as the state did)
            tok = jax.ShapeDtypeStruct(
                (t["batch"], t["seq"]), jnp.int32, sharding=bsh
            )
            kernels = device.require_kernels(
                step.lower(
                    abstract_train_state(cfg, mesh, opt),
                    {"tokens": tok, "targets": tok},
                ).compile(),
                "train step",
            )
            emit(
                event="compiled", attempt=attempt, pid=os.getpid(),
                device=dev, jax=version, cache_dir=cache_dir,
                first_step_s=step_s, compile_s=compile_s,
                step_cache_hits=hits, step_cache_misses=misses,
                tpu_custom_calls=kernels, hbm=_hbm(),
            )
        emit(
            event="step", attempt=attempt, step=n, loss=loss, step_s=step_s
        )
        client.report_global_step(n)
        if n % t["ckpt_every"] == 0:
            disk = n % (2 * t["ckpt_every"]) == 0
            t0 = time.perf_counter()
            ok = ckpt.save_checkpoint(
                n, state, StorageType.DISK if disk else StorageType.MEMORY
            )
            if ok:
                emit(
                    event="saved", attempt=attempt, step=n,
                    kind="disk" if disk else "memory",
                    stall_s=time.perf_counter() - t0,
                    checksum=_checksum(state["params"]),
                )
    emit(event="done", attempt=attempt, step=int(state["step"]), hbm=_hbm())
    return 0


def child_serve(args):
    import concurrent.futures

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.common import compile_cache, device
    from dlrover_tpu.models import decoder, get_config
    from dlrover_tpu.serving import kv_cache as kvc
    from dlrover_tpu.serving.server import GenerationServer

    s = SERVE
    mode = args.mode
    dev, version = _device_record()
    compile_cache.enable_compile_cache()
    watch = CompileWatch()
    cfg = get_config(MODEL, param_dtype="bfloat16", **s["model_kw"])
    params = jax.jit(lambda k: decoder.init(k, cfg))(
        jax.random.key(args.seed)
    )
    rng = np.random.RandomState(args.seed)
    prompts = [
        rng.randint(0, cfg.vocab_size, size=n).tolist()
        for n in s["prompt_lens"]
    ]
    server = GenerationServer(
        params, cfg, n_slots=s["n_slots"], max_len=s["max_len"],
        page_size=s["page_size"], mode=mode,
        prefill_chunk=s["prefill_chunk"],
    ).start()
    engine = server.engine

    def answer(batch, new_tokens):
        t0 = time.perf_counter()
        reqs = [server.submit(p, new_tokens) for p in batch]
        pending = {r.future for r in reqs}
        while pending:
            _, pending = concurrent.futures.wait(pending, timeout=1.0)
            if pending and not server.alive:
                raise RuntimeError("the serving loop died; see its traceback")
            if time.perf_counter() - t0 > 600:
                raise TimeoutError(f"{len(pending)} requests unanswered")
        return reqs, time.perf_counter() - t0

    # one request first, as a deployment warms a replica: it pays the
    # compile of the prefill and the decode program, the rest do not
    _, warmup_s = answer(prompts[:1], 2)
    reqs, serve_s = answer(prompts, s["max_new"])
    outs = [r.future.result() for r in reqs]
    stats = engine.stats()
    hbm = _hbm()
    # the programs the engine ran, re-lowered at their shapes to read
    # their kernels (a persistent-cache hit, not a second compile)
    one = (
        jnp.zeros((1, 2), jnp.uint32), jnp.zeros(1, jnp.float32),
        jnp.zeros(1, jnp.int32), jnp.ones(1, jnp.float32),
    )
    every = tuple(jnp.repeat(a, s["n_slots"], axis=0) for a in one)
    tables = jnp.asarray(engine.alloc.block_tables())
    width = engine.geom.max_pages_per_slot
    zeros = jnp.zeros(s["n_slots"], jnp.int32)
    kernels = {
        "prefill_chunk": engine._chunk_fn.lower(
            params, engine.pools, tables[:1],
            jnp.zeros((1, s["prefill_chunk"]), jnp.int32),
            zeros[:1], zeros[:1] + 1, *one, width,
        ),
        "decode": engine._decode_fn.lower(
            params, engine.pools, tables, zeros, zeros,
            zeros.astype(bool), *every, width,
        ),
    }
    kernels = {
        k: device.require_kernels(v.compile(), f"{mode} {k} step")
        for k, v in kernels.items()
    }
    server.stop()
    requests_ok = sum(
        len(out) == len(p) + s["max_new"]
        and all(0 <= tok < cfg.vocab_size for tok in out)
        for p, out in zip(prompts, outs)
    )

    # one request again, by hand, through the decoder's paged entry
    # points (what the engine's steps wrap) to see logits, not tokens
    prompt = np.asarray(prompts[0], np.int32)
    plen = len(prompt)
    geom = kvc.make_geometry(
        cfg, n_slots=1, max_len=s["max_len"], page_size=s["page_size"],
        mode=mode,
    )
    alloc = kvc.PageAllocator(geom, 1)
    alloc.admit(0, plen + 1)
    tab = jnp.asarray(alloc.block_tables())
    pools = kvc.init_pools(geom)
    chunk_fn = jax.jit(
        lambda p, tok, pl_, st, ln: decoder.prefill_chunk_paged(
            p, tok, pl_, tab, st, ln, cfg,
            max_pages=geom.max_pages_per_slot,
        ),
        donate_argnums=(2,),
    )
    c = s["prefill_chunk"]
    for start in range(0, plen, c):
        n = min(c, plen - start)
        chunk = np.zeros((1, c), np.int32)
        chunk[0, :n] = prompt[start:start + n]
        logits, pools = chunk_fn(
            params, jnp.asarray(chunk), pools,
            jnp.asarray([start], jnp.int32), jnp.asarray([n], jnp.int32),
        )
    prefill_logits = np.asarray(logits[0, n - 1])
    tok0 = int(prefill_logits.argmax())
    decode_logits, pools = jax.jit(
        lambda p, tok, pl_: decoder.decode_step_paged(
            p, tok, pl_, tab, jnp.asarray([plen], jnp.int32),
            jnp.asarray([True]), cfg, max_pages=geom.max_pages_per_slot,
        ),
        donate_argnums=(2,),
    )(params, jnp.asarray([tok0], jnp.int32), pools)
    decode_logits = np.asarray(decode_logits[0])
    del pools
    # the reference: plain jnp attention and norms over the same tokens
    ref_cfg = get_config(
        MODEL, param_dtype="bfloat16", fused_norm=False, **s["model_kw"]
    )
    ref = np.asarray(
        jax.jit(
            lambda p, tok: decoder.forward(
                p, tok, ref_cfg, attn_impl="reference"
            )
        )(params, jnp.asarray(np.append(prompt, tok0)[None]))[0]
    )

    def err(got, want):
        return float(np.abs(got - want).max() / np.abs(want).max())

    # the engine's own first two tokens for that prompt must sit within
    # tolerance of the reference's best logit at their positions
    tol = LOGIT_TOL[mode]
    gen = outs[0][plen:plen + 2]
    rows = (ref[plen - 1], ref[plen]) if gen[0] == tok0 else (ref[plen - 1],)
    engine_tokens_ok = all(
        row.max() - row[tok] <= tol * np.abs(row).max()
        for row, tok in zip(rows, gen)
    )
    emit(
        event="served", mode=mode, device=dev, jax=version,
        warmup_s_compile_included=warmup_s, serve_s=serve_s,
        requests_ok=requests_ok,
        prompt_lens=list(s["prompt_lens"]), max_new=s["max_new"],
        ttft_s=[r.first_token_t - r.submit_t for r in reqs],
        e2e_s=[r.done_t - r.submit_t for r in reqs],
        kv_pool_bytes=kvc.resident_bytes(engine.geom),
        step_time_s=stats["step_time_s"], host_time_s=stats["host_time_s"],
        prefill_chunks=stats["prefill_chunks"],
        cache_hits=watch.hits, cache_misses=watch.misses,
        tpu_custom_calls=kernels, hbm=hbm,
        kernel_check=_paged_kernel_check(mode, args.seed),
        prefill_logit_err=err(prefill_logits, ref[plen - 1]),
        decode_logit_err=err(decode_logits, ref[plen]),
        logit_tol=tol, engine_tokens_ok=bool(engine_tokens_ok),
        engine_first_token_matches=bool(gen[0] == tok0),
    )
    return 0


def _paged_kernel_check(mode, seed):
    """The paged kernel alone, at the engine's geometry (25 heads x 64,
    page 16, prefill chunk 256) — see KERNEL_TOL. One layer's pools,
    two slots of ragged length, filled with known rows through
    ``write_page_rows`` (the engine's writer)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.common import device
    from dlrover_tpu.models import get_config
    from dlrover_tpu.ops import pallas_paged
    from dlrover_tpu.serving import kv_cache as kvc

    s = SERVE
    cfg = get_config(MODEL, **dict(s["model_kw"], n_layer=1))
    c = s["prefill_chunk"]
    lens = np.asarray([s["max_len"] - 24, 2 * c + 7])
    b, t = len(lens), int(lens.max())
    h, hkv, d = cfg.n_head, cfg.n_kv_head or cfg.n_head, cfg.head_dim
    geom = kvc.make_geometry(
        cfg, n_slots=b, max_len=s["max_len"], page_size=s["page_size"],
        mode=mode,
    )
    alloc = kvc.PageAllocator(geom, b)
    for slot, n in enumerate(lens):
        alloc.admit(slot, int(n))
    tables = jnp.asarray(alloc.block_tables())
    keys = jax.random.split(jax.random.key(seed + 1), 3)
    # keys at twice unit scale: logits of std 4, so a query leans on a
    # few rows and their precision shows in the output
    k_rows = 2 * jax.random.normal(keys[0], (b, t, hkv, d), jnp.bfloat16)
    v_rows = jax.random.normal(keys[1], (b, t, hkv, d), jnp.bfloat16)
    q = 2 * jax.random.normal(keys[2], (b, c, h, d), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    valid = pos < jnp.asarray(lens)[:, None]
    pools = jax.jit(pallas_paged.write_page_rows)(
        {k: v[0] for k, v in kvc.init_pools(geom).items()},
        tables, pos, valid, k_rows, v_rows,
    )

    # what the pools hold, against what was written
    held = jax.jit(
        lambda p: pallas_paged.gather_pages(
            p, tables, kv_heads=hkv, dtype=jnp.bfloat16
        )
    )(pools)
    pool_err = 0.0
    for rows, back in zip((k_rows, v_rows), held):
        x = np.asarray(rows, np.float32)
        diff = np.abs(np.asarray(back[:, :t], np.float32) - x)
        if mode == "int8":
            half_step = np.abs(x).max(-1, keepdims=True) / 254
            diff = diff / (half_step + (np.abs(x) + half_step) / 256)
        else:
            diff = np.where(diff > 0, np.inf, 0.0)  # bf16: bit for bit
        pool_err = max(pool_err, float(diff[np.asarray(valid)].max()))
    out = {"written rows": {"pool": pool_err}}

    # the kernel against plain float64 attention over what the pools
    # hold (numpy on the host: nothing of the code under test)
    kf, vf = (np.asarray(x[:, :t], np.float64) for x in held)
    chunk_pos = lens[:, None] - c + np.arange(c)[None, :]

    def oracle(qs, where):
        qf = np.asarray(qs, np.float64).reshape(b, -1, hkv, h // hkv, d)
        scores = np.einsum("bckgd,btkd->bckgt", qf, kf) * d ** -0.5
        causal = np.arange(t)[None, None, :] <= where[:, :, None]
        scores = np.where(causal[:, :, None, None, :], scores, -np.inf)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        return np.einsum("bckgt,btkd->bckgd", p, vf).reshape(b, -1, h, d)

    def attend(variant, qs, pos):
        return jax.jit(
            lambda p: pallas_paged.paged_attention(
                qs, p, tables, jnp.asarray(pos), scale=d ** -0.5,
                kv_heads=hkv, variant=variant,
            )
        )

    for variant, qs, where in (
        ("chunk", q, chunk_pos), ("decode", q[:, -1:], chunk_pos[:, -1:]),
    ):
        # decode takes one position a slot, a chunk one a query
        kernel = attend(
            variant, qs, where if variant == "chunk" else where[:, 0]
        )
        device.require_kernels(
            kernel.lower(pools).compile(), f"paged {variant} check"
        )
        got = np.asarray(kernel(pools), np.float64)
        ref = oracle(qs, where)
        out[variant] = {
            "max": float(np.abs(got - ref).max() / np.abs(ref).max()),
            "rms": float(
                np.sqrt(np.mean((got - ref) ** 2) / np.mean(ref ** 2))
            ),
        }
    return out


def child_dp_worker(args):
    """ZeRO-1 over dp=``--devices`` through ElasticTrainer: with fewer
    data-parallel replicas it accumulates more microbatches, and the
    loss must not notice."""
    import jax

    from dlrover_tpu.agent.master_client import build_master_client
    from dlrover_tpu.common import compile_cache, device
    from dlrover_tpu.elastic import ElasticTrainer
    from dlrover_tpu.models import get_config
    from dlrover_tpu.parallel import MeshConfig, build_mesh
    from dlrover_tpu.parallel import sharding as shd
    from dlrover_tpu.train import (
        TrainStepBuilder, batch_sharding, init_train_state, make_optimizer,
    )
    from dlrover_tpu.train.data_utils import form_global_batch
    from dlrover_tpu.train.distributed import init_distributed

    d = DP
    dev, version = _device_record()
    compile_cache.enable_compile_cache()
    init_distributed()
    build_master_client()
    mesh = build_mesh(MeshConfig(dp=-1), devices=jax.devices()[:args.devices])
    cfg = get_config(
        MODEL, max_seq=d["seq"], remat=d["remat"],
        param_dtype=d["param_dtype"], **d["model_kw"],
    )
    opt = make_optimizer(
        learning_rate=1e-4, warmup_steps=10, decay_steps=1000
    )
    comm = shd.CommConfig(update_sharding="zero1")
    built = {}

    def build_step(accum):
        b = TrainStepBuilder(cfg, mesh, opt, grad_accum=accum, comm=comm)
        built["builder"] = b
        return b.build()

    trainer = ElasticTrainer(
        d["batch"], d["micro"], build_step,
        data_replicas_fn=lambda: mesh.shape["dp"],
    )
    builder = built["builder"]
    if args.devices > 1 and not builder.update_sharding:
        raise RuntimeError(
            "ZeRO-1 refused: " + str(builder.update_sharding_reason)
        )
    state = init_train_state(
        jax.random.key(args.seed), cfg, mesh, opt, comm=builder.comm_resolved
    )
    bsh = batch_sharding(mesh)
    batches = [
        form_global_batch(
            _synthetic_batch(i, d["batch"], d["seq"], cfg.vocab_size), bsh
        )
        for i in range(d["steps"])
    ]
    kernels = device.require_kernels(
        trainer._step_fn.lower(state, batches[0]).compile(),
        f"dp={args.devices} train step",
    )
    losses, step_s = [], []
    for batch in batches:
        t0 = time.perf_counter()
        state, metrics = trainer.step(state, batch)
        losses.append(float(metrics["loss"]))
        step_s.append(time.perf_counter() - t0)
    emit(
        event="dp_done", devices=args.devices, device=dev, jax=version,
        grad_accum=trainer.grad_accum,
        update_sharding=bool(builder.update_sharding), losses=losses,
        step_s=step_s, tpu_custom_calls=kernels, hbm=_hbm(),
    )
    return 0


CHILDREN = {
    "probe": child_probe,
    "train-worker": child_train_worker,
    "serve": child_serve,
    "dp-worker": child_dp_worker,
}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--phase", choices=sorted(CHILDREN), help=argparse.SUPPRESS)
    p.add_argument("--mode", default="int8", help=argparse.SUPPRESS)
    p.add_argument("--devices", type=int, default=1, help=argparse.SUPPRESS)
    p.add_argument("--ckpt-dir", default="", help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.phase:
        return CHILDREN[args.phase](args)
    return parent_main(args)


if __name__ == "__main__":
    sys.exit(main())
