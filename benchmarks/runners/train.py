"""Runner ``train``: the jitted train step in a loop, in this one
process, through the calls the example worker makes
(``examples/train_gpt_elastic.py``): ``build_mesh``, ``get_config``,
``make_optimizer``, ``TrainStepBuilder(...).build()``,
``init_train_state``, ``form_global_batch``. No master, no agent, no
checkpointer.

Set-up (everything before the first timed step): the state is made on
the device from ``--seed`` in one jitted call, the step is compiled (or
fetched from the persistent cache) once for this cell's one shape and
checked for ``tpu_custom_call``, a few warm-up steps run, and the
outputs are checked against the configuration's plain reference. Then
steps run for ``--seconds``; each ends in a host readback of its loss.

From the program the runner takes the system under test and nothing
that measures: clocks, spans, the FLOPs and the comparison
that decides ``correct`` are the benchmark's own.
"""

import importlib
import math
import os
import shutil
import time

from benchmarks.lib import device as devlib
from benchmarks.lib import flops as flopslib
from benchmarks.lib import trace as tracelib
from benchmarks.lib.spans import Spans
from benchmarks.lib.watch import CompileWatch, hbm, synthetic_batch

# The comparison that decides ``correct``, with the reason for each
# tolerance. A configuration names its kind (``"check": {"kind": ...}``;
# absent: ``dense``). ``dense`` holds the program's free-running logits
# to the reference's. ``routed`` (``benchmarks/lib/routed.py``) holds
# them to a reference sent to the experts the program chose, holds those
# choices to a regret limit, and the free-running logits are recorded
# only; ``selected`` (``benchmarks/lib/selected.py``) does the same with
# the keys each query attended to, and with the experts too where the
# model routes; the tolerances below are the same for all. Kernel path: the
# program's flash attention, fused norms and fused cross-entropy, bf16
# activations (and bf16 weights in the one-chip cells). Reference: the
# configuration's plain float32 forward under matmul precision
# "highest", same weights, same tokens.
#
# Logits, max |difference| over max |reference|. An extreme value over
# 8192 x vocabulary elements: 1.2e-2..1.9e-2 on a v5e in all three
# cells (PR 24's chip runs; PR 21 saw 1.3e-2..1.5e-2 in serving), and
# no smaller at 6 layers than at 48 — the bf16 rounding of the last
# hidden state and of the head's operands sets it, not the depth. Held
# to twice the largest seen. It catches a wrong mask, position, head
# grouping or table; it cannot tell bf16 from a coarser format.
LOGIT_TOL = 4e-2
# Logits, rms of the difference over rms of the reference: the same
# comparison as a mean over 4e8 elements, which hardly moves with the
# seed: 1.1e-2..1.4e-2 on the chip at 6, 24 and 48 layers, so it too is
# set by bf16 rounding near the head and not by depth. Held to 2.5e-2.
# A path computed wholesale in an 8-bit format (relative step 2**-4
# against bf16's 2**-8) does not meet it; one 8-bit matmul among many
# bf16 ones might.
LOGIT_RMS_TOL = 2.5e-2
# Mean loss of the step's own forward (fused cross-entropy) against the
# reference's, relative. Per-token errors average out over the 8192
# tokens compared: 1.2e-6..3.0e-5 on the chip. Held to 2e-4, which a
# dropped term of the loss (the max subtraction, a vocabulary chunk,
# the padded rows) does not meet.
LOSS_TOL = 2e-4
# First timed step's loss against the forward-only loss on the same
# weights and batch: the same arithmetic compiled into two programs
# (remat, fusion order), relative; 0..8e-6 on the chip.
STEP_LOSS_TOL = 1e-4
# warm-up batches come from indexes the window never reaches
WARMUP_INDEX = 10**9


def _seed_key(seed):
    """A jax key from any whole number: jax takes 32 signed bits, the
    driver's seeds are larger."""
    import jax

    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def _program_config(config):
    """The program's ModelConfig for this configuration, checked
    against the file's ``sizes`` so that the file is what ran."""
    from dlrover_tpu.models import get_config

    prog = config["program"]
    cfg = get_config(prog["model"], **prog["overrides"])
    for key, want in config["sizes"].items():
        if key == "norm_eps":
            continue  # fixed in the program's code, not a field
        if key in ("select_block", "select_groups") and not hasattr(cfg, key):
            # ``selected``'s contract: held against the shape of what the
            # program hands over (``lib/selected.py``), field or no field
            continue
        got = getattr(cfg, key)
        if got != want:
            raise ValueError(
                f"configuration says {key}={want!r}, the program's "
                f"{prog['model']} has {got!r}"
            )
    return cfg


def run(ctx):
    import jax

    cell, config, traffic = ctx["cell"], ctx["config"], ctx["traffic"]
    say = ctx["say"]
    devices, device_record, peaks = devlib.require_chips(cell["chips"])
    cache_dir = devlib.enable_compile_cache(ctx["root"])
    watch = CompileWatch()
    spans = Spans()

    from dlrover_tpu.parallel import MeshConfig, build_mesh
    from dlrover_tpu.parallel import sharding as shd
    from dlrover_tpu.train import (
        TrainStepBuilder, batch_sharding, init_train_state, make_optimizer,
    )
    from dlrover_tpu.train.data_utils import form_global_batch

    prog = config["program"]
    sizes = config["sizes"]
    cfg = _program_config(config)
    mesh = build_mesh(MeshConfig(**prog["mesh"]), devices=devices)
    opt = make_optimizer(**prog["optimizer"])
    comm = shd.CommConfig(**prog["comm"]) if prog.get("comm") else None
    builder = TrainStepBuilder(cfg, mesh, opt, comm=comm)
    if comm is not None and comm.update_sharding and not builder.update_sharding:
        raise devlib.Refused(
            "the configuration asks for a sharded update and the program "
            f"fell back: {builder.update_sharding_reason}"
        )
    step = builder.build()
    gb, seq = traffic["global_batch"], traffic["seq"]
    bsh = batch_sharding(mesh)
    # by the reference module's ``required_terms`` where it has one
    required_flops = flopslib.resolve(config, seq)

    def place(index):
        with spans.span("input"):
            batch = form_global_batch(
                synthetic_batch(ctx["seed"], index, gb, seq, cfg.vocab_size),
                bsh,
            )
            jax.block_until_ready(batch)
        return batch

    t0 = time.perf_counter()
    state = init_train_state(
        _seed_key(ctx["seed"]), cfg, mesh, opt, comm=builder.comm_resolved
    )
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t0
    batch0 = place(0)

    # the one program of this cell, compiled or fetched once; the
    # window calls this executable, so nothing can compile inside it
    mark = watch.since()
    t0 = time.perf_counter()
    compiled = step.lower(state, batch0).compile()
    step_hits, step_misses, step_compile_s, _ = watch.since(mark)
    kernels = devlib.require_kernels(compiled, "train step")
    say(
        event="compiled", cache_dir=cache_dir, tpu_custom_calls=kernels,
        step_cache_hits=step_hits, step_cache_misses=step_misses,
        step_compile_s=step_compile_s, wall_s=time.perf_counter() - t0,
        init_s=init_s, update_sharding=bool(builder.update_sharding),
        hbm=hbm(devices),
    )

    warm, step_metrics = [], []  # the latter: the program's own, per step
    for i in range(traffic["warmup_steps"]):
        batch = place(WARMUP_INDEX + i)
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        loss = float(metrics["loss"])
        warm.append({"loss": loss, "s": time.perf_counter() - t0})
        step_metrics.append(metrics)
    say(event="warmup", steps=warm)

    checks = _check_outputs(ctx, cfg, mesh, state, batch0, devices[0])

    set_up = watch.since()
    say(
        event="setup_done", cache_hits=set_up[0], cache_misses=set_up[1],
        compile_s=set_up[2], programs=set_up[3], hbm=hbm(devices),
    )

    # ---- the measured window -------------------------------------------
    losses, failed, attempted = [], 0, 0
    batch = batch0
    mark = watch.since()
    window_start = time.perf_counter()
    setup_s = time.time() - ctx["process_start"]
    while True:
        attempted += 1
        try:
            with spans.span("step"):
                state, metrics = compiled(state, batch)
                loss = float(metrics["loss"])  # readback: the step is over
        except Exception as exc:  # a failed step is counted, then fatal
            failed += 1
            say(event="step_failed", step=attempted, error=repr(exc))
            break
        losses.append(loss)
        failed += not math.isfinite(loss)
        if time.perf_counter() - window_start >= ctx["seconds"]:
            break
        batch = place(attempted)
    window_s = time.perf_counter() - window_start
    compiles_in_window = watch.since(mark)[3]
    done = len(losses)
    step_s = spans.durations("step", since=window_start)
    say(
        event="window", steps=done, window_s=window_s, step_s=step_s,
        losses=losses, compiles_in_window=compiles_in_window,
        hbm=hbm(devices),
    )

    step_err = (
        abs(losses[0] - checks["kernel_loss"]) / abs(checks["kernel_loss"])
        if losses else float("inf")
    )
    checks["results"] += [
        ("first_step_loss", step_err <= STEP_LOSS_TOL, step_err,
         STEP_LOSS_TOL),
        ("no_compile_in_window", compiles_in_window == 0,
         compiles_in_window, 0),
        ("no_failed_step", failed == 0, failed, 0),
    ]
    for name, ok, value, limit in checks["results"]:
        say(event="check", name=name, ok=bool(ok), value=value, limit=limit)

    memory = hbm(devices)  # before the profiler, which resets the peaks
    reduced = None
    if ctx["trace"]:
        reduced = _traced_window(
            ctx, spans, compiled, state, place, attempted, step_metrics
        )

    tokens = gb * seq
    device_record = dict(device_record, memory_peak_bytes=_peak_bytes(memory))
    if reduced is not None:
        device_record.update(
            busy_s=reduced["busy_s"], window_s=reduced["window_s"]
        )
    return {
        "correct": all(ok for _n, ok, _v, _l in checks["results"]),
        # (name, ok, value, limit): ``run.py`` ends standard error with them
        "checks": checks["results"],
        "attempted": attempted,
        "failed": failed,
        "device": device_record,
        "end_to_end": {
            "train_tokens_per_s": done * tokens / window_s,
            "setup_s": setup_s,
        },
        # what the per-layer readers read
        "spans": spans,
        "window_start": window_start,
        "window": {"steps": done, "seconds": window_s, "tokens": tokens},
        "sizes": sizes,
        "seq": seq,
        "required_flops_per_token": required_flops,
        "chips": cell["chips"],
        "peaks": peaks,
        "trace": reduced,
        # the program's step metrics of the warm-up and the traced steps
        # (the window reads back its loss and nothing else)
        "step_metrics": _by_name(step_metrics),
    }


def _by_name(step_metrics):
    """name -> one float per step, for the scalar metrics of the
    program's step."""
    import numpy as np

    return {
        name: [float(m[name]) for m in step_metrics]
        for name in (step_metrics[0] if step_metrics else ())
        if np.ndim(step_metrics[0][name]) == 0
    }


def _peak_bytes(memory):
    """Peak bytes the fullest chip had to hold. The TPU runtime counts
    arrays as "in use" and a running program's temporaries as
    "reserved", apart from each other: while the step runs the chip
    holds the state (in use after the window) plus the step's
    reservation. The larger of that and ``peak_bytes_in_use`` (which
    the check programs' logits set) is the peak."""
    return max(
        max(peak, used + reserved)
        for peak, used, reserved in zip(
            memory["peak_bytes_in_use"], memory["bytes_in_use"],
            memory["peak_bytes_reserved"],
        )
    )


def _check_outputs(ctx, cfg, mesh, state, batch0, first):
    """Kernel path against the plain reference, outside the window, on
    the weights the window starts from and its first batch.

    The global batch is cut into one share per data-parallel replica,
    and each share runs on the first device alone (on four chips the
    parameters are copied there once). Every share goes through the
    step's own forward (``decoder.loss_fn``: flash attention, fused
    norms, fused cross-entropy), and the mean of their
    ``metrics["loss"]`` is what the first timed step's ``metrics["loss"]``
    is held to: like with like, whatever else the objective adds. The
    first share also goes through ``decoder.forward`` for logits and
    through the reference, whose mean cross-entropy is held against the
    program's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models import decoder

    config, traffic, sizes = ctx["config"], ctx["traffic"], ctx["config"]["sizes"]
    kind = config.get("check", {}).get("kind", "dense")
    if kind not in ("dense", "routed", "selected"):
        raise ValueError(f"no comparison of kind {kind!r}")
    forcing = None  # a teacher-forced kind: ``benchmarks/lib/<kind>.py``
    if kind != "dense":
        forcing = importlib.import_module("benchmarks.lib." + kind)
    reference = importlib.import_module(
        "benchmarks.references." + config["reference"]
    )
    params = jax.device_put(state["params"], first)
    tokens = np.asarray(batch0["tokens"])
    targets = np.asarray(batch0["targets"])
    shares = mesh.shape["dp"]
    rows = tokens.shape[0] // shares

    def share(i):
        sl = slice(i * rows, (i + 1) * rows)
        return jax.device_put(
            {"tokens": tokens[sl], "targets": targets[sl]}, first
        )

    @jax.jit
    def kernel_loss(params, batch):
        return decoder.loss_fn(params, batch, cfg=cfg)[1]["loss"]

    @jax.jit
    def kernel_logits(params, batch):
        return decoder.forward(params, batch["tokens"], cfg)

    @jax.jit
    def against_reference(params, batch, logits):
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_logits = reference.loss_and_logits(
                params, batch, sizes, traffic["check"]["q_block"]
            )
        diff = logits - ref_logits
        return (
            ref_loss,
            jnp.max(jnp.abs(diff)) / jnp.max(jnp.abs(ref_logits)),
            jnp.sqrt(jnp.sum(diff * diff) / jnp.sum(ref_logits * ref_logits)),
        )

    t0 = time.perf_counter()
    batches = [share(i) for i in range(shares)]
    kernel_losses = [float(kernel_loss(params, b)) for b in batches]
    if forcing:
        logits, choices = forcing.program_logits_and_choices(
            params, batches[0]["tokens"], cfg, sizes
        )
        program = forcing.program_losses(params, batches[0], cfg)
        ce_loss = program.get("ce_loss", program["loss"])
    else:
        logits = kernel_logits(params, batches[0])
        ce_loss = kernel_losses[0]  # a dense objective is its cross-entropy
    ref_loss, logit_err, logit_rms = (
        float(x) for x in against_reference(params, batches[0], logits)
    )
    loss_err = abs(ce_loss - ref_loss) / abs(ref_loss)
    record = dict(
        event="reference", kernel_losses=kernel_losses, ref_loss=ref_loss,
        logit_err=logit_err, logit_rms=logit_rms, loss_err=loss_err,
        rows=rows, shares=shares,
    )
    if forcing:
        # free-running logits are recorded; what is judged of that
        # reference is the loss, which never saw the program's choices
        results, forced = forcing.compare(
            reference, params, batches[0], sizes,
            traffic["check"]["q_block"], logits, choices, program,
            (LOGIT_TOL, LOGIT_RMS_TOL, LOSS_TOL),
        )
        results.append((
            "loss_vs_free_reference", loss_err <= forcing.FREE_LOSS_TOL,
            loss_err, forcing.FREE_LOSS_TOL,
        ))
        compared = ("loss", "ce_loss", *forced.get("reference_terms", ()))
        record.update(
            kind=kind, **forced,
            program_losses={k: program[k] for k in compared if k in program},
        )
    else:
        results = [
            ("logits_vs_reference", logit_err <= LOGIT_TOL, logit_err,
             LOGIT_TOL),
            ("logits_rms_vs_reference", logit_rms <= LOGIT_RMS_TOL,
             logit_rms, LOGIT_RMS_TOL),
            ("loss_vs_reference", loss_err <= LOSS_TOL, loss_err, LOSS_TOL),
        ]
    del logits, params
    ctx["say"](**record, wall_s=time.perf_counter() - t0)
    return {
        "kernel_loss": sum(kernel_losses) / len(kernel_losses),
        "results": results,
    }


def _traced_window(ctx, spans, compiled, state, place, index, step_metrics):
    """A few more steps under the profiler, in a window of their own,
    reduced by the benchmark's own code. The trace is written inside
    the checkout and removed once it is reduced. Each step's metrics
    are appended to ``step_metrics`` as they are, on the device."""
    import jax

    out = os.path.join(
        ctx["root"], ".bench_work", "trace-" + ctx["cell"]["name"]
    )
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    spans.annotate = True
    try:
        jax.profiler.start_trace(out)
        try:
            with spans.span("traced_window"):
                for i in range(ctx["traffic"]["trace_steps"]):
                    batch = place(index + 1 + i)
                    with spans.span("dispatch"):
                        state, metrics = compiled(state, batch)
                    with spans.span("readback"):
                        float(metrics["loss"])
                    step_metrics.append(metrics)
        finally:
            jax.profiler.stop_trace()
        # the trace has no name stack per event; the compiled text has
        t0 = time.perf_counter()
        text = compiled.as_text()
        op_names = tracelib.op_names(text)
        op_names_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        planes = tracelib.load_xplane(tracelib.find_xplane(out))
        module = tracelib.module_name(text)
        reduced = tracelib.reduce(
            planes, window_span="bench.traced_window", op_names=op_names,
            module=module,
        )
        ctx["say"](
            event="trace", parse_s=time.perf_counter() - t0,
            op_names_s=op_names_s, compiled_text_bytes=len(text),
            op_names=len(op_names), module=module,
            # per device: self seconds that found a path, of busy_s
            named_s=reduced and [
                sum(sum(split.values()) for split in d["op_names"].values())
                for d in reduced["per_device"]
            ],
            planes=[p["name"] for p in planes],
            reduced=reduced and dict(reduced, per_device=[
                {k: v for k, v in d.items()
                 if k not in ("by_name", "op_names")}
                for d in reduced["per_device"]
            ]),
        )
        return reduced
    finally:
        spans.annotate = False
        shutil.rmtree(out, ignore_errors=True)
