"""End-to-end elastic training example.

Run under the elastic launcher (single host spawns a local master):

    python -m dlrover_tpu.agent.launcher --nnodes 1 -- \
        python examples/train_gpt_elastic.py --steps 50

Exercises: master rendezvous → jax.distributed bootstrap → device mesh →
dynamic data sharding from the master's TaskManager → jitted sharded train
step → flash checkpoint (memory stage + async disk persist) → resume after
restart.
"""

import argparse
import json
import sys

sys.path.insert(0, ".")  # repo-root run: `python examples/...`
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.agent.master_client import build_master_client
from dlrover_tpu.agent.sharding_client import ShardingClient
from dlrover_tpu.checkpoint import Checkpointer, StorageType
from dlrover_tpu.checkpoint.checkpointer import state_template
from dlrover_tpu.elastic import (
    ElasticTrainer,
    LiveResharder,
    PhaseBudgets,
    get_injector,
    reshard_train_state,
)
from dlrover_tpu.models import get_config
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.parallel import sharding as shd
from dlrover_tpu.train import (
    TrainStepBuilder,
    batch_sharding,
    make_optimizer,
    restore_or_init_train_state,
    state_shardings,
)
from dlrover_tpu.train.data_utils import form_global_batch, iter_shards_spmd
from dlrover_tpu.train.distributed import init_distributed


def synthetic_batch(start: int, end: int, batch: int, seq: int, vocab: int):
    rng = np.random.RandomState(start)
    n = batch * (seq + 1)
    data = rng.randint(0, vocab, size=n).reshape(batch, seq + 1)
    return {
        "tokens": jnp.asarray(data[:, :-1], jnp.int32),
        "targets": jnp.asarray(data[:, 1:], jnp.int32),
    }


def _live_reshard(args, client, ckpt, cfg, opt, comm, ctx, trainer, state):
    """Graceful host eviction: survivors keep their in-HBM state, the
    master issues a reshard directive, and training resumes at the new
    dp size without a restart or a disk restore. Every phase runs under
    a deadline budget; any failure degrades to the checkpoint ladder."""
    old_mesh = ctx["mesh"]
    old_dp = old_mesh.shape["dp"]
    lost = sorted(
        int(r) for r in args.evict_dp_ranks.split(",") if r.strip()
    )
    if not lost:
        lost = list(range(old_dp // 2, old_dp))
    old_plan = ctx["builder"]._plan
    old_shardings = jax.tree.map(lambda x: x.sharding, state)

    client.report_eviction(lost, dp_size=old_dp, reason="drill eviction")

    def detect(_):
        deadline = time.time() + 15.0
        while time.time() < deadline:
            directive = client.get_reshard_plan()
            if directive.version > 0:
                return directive
            time.sleep(0.05)
        raise RuntimeError("reshard directive never arrived")

    def replan(directive):
        lost_set = set(directive.lost_ranks if directive else lost)
        survivors = [
            d
            for i, d in enumerate(old_mesh.devices.flat)
            if i not in lost_set
        ]
        new_mesh = build_mesh(MeshConfig(dp=-1), devices=survivors)
        nb = TrainStepBuilder(cfg, new_mesh, opt, comm=comm)
        assert nb.update_sharding, nb.update_sharding_reason
        return {
            "mesh": new_mesh,
            "plan": nb._plan,
            "shardings": state_shardings(cfg, new_mesh, opt, comm=comm),
        }

    def migrate(rp):
        rp["state"] = reshard_train_state(
            state, old_plan, rp["plan"], rp["shardings"],
            faults=get_injector(),
        )
        return rp

    def rebuild(rp):
        ctx["mesh"] = rp["mesh"]
        trainer.on_membership_change()
        return rp

    def first_step(rp):
        batch = form_global_batch(
            synthetic_batch(
                int(rp["state"]["step"]) * args.batch,
                0,
                args.batch,
                args.seq,
                cfg.vocab_size,
            ),
            batch_sharding(rp["mesh"]),
        )
        rp["state"], metrics = trainer.step(rp["state"], batch)
        print(
            f"[reshard] first step loss={float(metrics['loss']):.4f}",
            flush=True,
        )
        return rp

    def fallback(exc):
        # tier ladder: restore at the OLD geometry from the checkpoint
        # stack, then repack to the survivor layout (no HBM donors
        # involved, so a dead donor cannot poison this path)
        print(
            f"[reshard] live path failed ({exc!r}); "
            "falling back to checkpoint ladder",
            flush=True,
        )
        restored = ckpt.load_checkpoint(
            state_template(state), shardings=old_shardings
        )
        if restored is None:
            raise RuntimeError("no checkpoint tier answered")
        rp = replan(None)
        rp["state"] = reshard_train_state(
            restored, old_plan, rp["plan"], rp["shardings"]
        )
        return first_step(rebuild(rp))

    out = LiveResharder(budgets=PhaseBudgets()).execute(
        [
            ("detect", detect),
            ("replan", replan),
            ("migrate", migrate),
            ("rebuild", rebuild),
            ("first_step", first_step),
        ],
        fallback=fallback,
    )
    print(
        "[reshard] done "
        + json.dumps(
            {
                "path": out.path,
                "recovery_s": round(out.recovery_s, 3),
                "dp": f"{old_dp}->{ctx['mesh'].shape['dp']}",
                "phases": {
                    k: round(v, 3) for k, v in out.phase_seconds.items()
                },
            }
        ),
        flush=True,
    )
    return out.result["state"]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--model", default="tiny")
    p.add_argument("--ckpt-dir", default="/tmp/dlrover_tpu_example_ckpt")
    p.add_argument("--ckpt-every", type=int, default=5)
    p.add_argument("--crash-at", type=int, default=-1,
                   help="deliberately crash at this step (failover demo)")
    p.add_argument(
        "--hosts-per-slice", type=int, default=0,
        help="build a hybrid multi-slice mesh: every hosts-per-slice "
        "processes form one emulated ICI slice, dp rides DCN across "
        "slices (num_slices = process_count // hosts_per_slice)",
    )
    p.add_argument(
        "--zero1", action="store_true",
        help="ZeRO-1 update sharding (bucketed flat optimizer state); "
        "required for --evict-at",
    )
    p.add_argument(
        "--evict-at", type=int, default=-1,
        help="at this step, simulate a graceful host eviction and "
        "live-reshard onto the survivors (no restart, no disk restore)",
    )
    p.add_argument(
        "--evict-dp-ranks", default="",
        help="comma-separated dp ranks lost at --evict-at "
        "(default: the top half of the mesh)",
    )
    args = p.parse_args()

    init_distributed()
    client = build_master_client()
    if args.hosts_per_slice > 0:
        # slice-grain elasticity: the mesh is rebuilt from the CURRENT
        # world every (re)start, so a world that shrank by a whole slice
        # re-meshes to fewer slices (dp shrinks, fsdp stays intra-slice)
        num_slices = max(1, jax.process_count() // args.hosts_per_slice)
        mesh = build_mesh(
            MeshConfig(dp=num_slices, fsdp=-1, num_slices=num_slices)
        )
        print(
            f"[worker] slice mesh: num_slices={num_slices} "
            f"dp={mesh.shape['dp']} fsdp={mesh.shape['fsdp']}",
            flush=True,
        )
    else:
        mesh = build_mesh(MeshConfig(dp=-1))
    cfg = get_config(args.model, max_seq=args.seq)
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=5, decay_steps=1000)

    # --zero1 routes stepping through ElasticTrainer so a live reshard
    # can rebuild the jitted step for the new (replicas, grad_accum)
    comm = (
        shd.CommConfig(update_sharding=True, bucket_mb=0.05)
        if args.zero1
        else None
    )
    ctx = {"mesh": mesh, "builder": None}

    def build_step(accum):
        b = TrainStepBuilder(
            cfg, ctx["mesh"], opt, grad_accum=accum, comm=comm
        )
        ctx["builder"] = b
        return b.build()

    trainer = None
    if args.zero1:
        micro = max(1, args.batch // mesh.shape["dp"])
        trainer = ElasticTrainer(
            args.batch,
            micro,
            build_step,
            data_replicas_fn=lambda: ctx["mesh"].shape["dp"],
        )
        run_step = trainer.step
    else:
        run_step = build_step(1)
    ckpt = Checkpointer(args.ckpt_dir, master_client=client)
    # restore first, initialise only if nothing answers: a fresh state
    # beside a restored one is the train state twice in HBM
    state, resumed = restore_or_init_train_state(
        ckpt, jax.random.key(0), cfg, mesh, opt,
        comm=ctx["builder"].comm_resolved,
    )
    if resumed:
        print(f"[worker] resumed from step {int(state['step'])}", flush=True)
    # SPMD: one shard = one GLOBAL step (batch rows × processes); rank 0
    # fetches from the master and broadcasts so all processes stay in
    # lockstep; each process slices its own rows out of the shard.
    nproc = jax.process_count()
    sharding = ShardingClient(
        client,
        "train",
        dataset_size=args.steps * args.batch * nproc,
        shard_size=args.batch * nproc,
    )

    bsh = batch_sharding(mesh)
    t0 = time.time()
    evicted = False
    for start, end in iter_shards_spmd(sharding):
        local_start = start + jax.process_index() * args.batch
        step = int(state["step"])
        if (
            args.crash_at >= 0
            and step >= args.crash_at
            and int(os.environ.get("DLROVER_TPU_RESTART_COUNT", "0")) == 0
        ):
            print(f"[worker] simulating crash at step {step}", flush=True)
            os._exit(17)
        if (
            args.evict_at >= 0
            and trainer is not None
            and not evicted
            and step >= args.evict_at
        ):
            state = _live_reshard(
                args, client, ckpt, cfg, opt, comm, ctx, trainer, state
            )
            evicted = True
            bsh = batch_sharding(ctx["mesh"])
        batch = form_global_batch(
            synthetic_batch(
                local_start,
                local_start + args.batch,
                args.batch,
                args.seq,
                cfg.vocab_size,
            ),
            bsh,
        )
        state, metrics = run_step(state, batch)
        step = int(state["step"])
        client.report_global_step(step)
        if step % args.ckpt_every == 0:
            kind = (
                StorageType.DISK
                if step % (2 * args.ckpt_every) == 0
                else StorageType.MEMORY
            )
            ckpt.save_checkpoint(step, state, kind)
        print(
            f"[worker] step={step} loss={float(metrics['loss']):.4f} "
            f"({(time.time() - t0):.1f}s)",
            flush=True,
        )
    ckpt.save_checkpoint(int(state["step"]), state, StorageType.DISK)
    ckpt.wait_for_persist(30)
    print(f"[worker] done at step {int(state['step'])}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
