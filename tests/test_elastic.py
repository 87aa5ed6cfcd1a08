"""Elastic sampler / dataloader / trainer tests."""

import numpy as np
import pytest

from dlrover_tpu.elastic import (
    ElasticDataLoader,
    ElasticDistributedSampler,
    ElasticTrainer,
)


def test_sampler_partition_disjoint_and_complete():
    n = 100
    replicas = 4
    seen = []
    for rank in range(replicas):
        s = ElasticDistributedSampler(
            n, num_replicas=replicas, rank=rank, shuffle=True, seed=7
        )
        seen.extend(list(s))
    assert sorted(set(seen)) == list(range(n))


def test_sampler_resume_different_world_size():
    n = 64
    # 4 replicas consume 2 steps of per-replica batch 4 → 32 samples done
    s0 = ElasticDistributedSampler(n, num_replicas=4, rank=0, shuffle=True)
    s0.record_batch(4)
    s0.record_batch(4)
    state = s0.state_dict()

    # resume with 2 replicas: remaining 32 samples split between them
    remaining = []
    for rank in range(2):
        s = ElasticDistributedSampler(n, num_replicas=2, rank=rank, shuffle=True)
        s.load_state_dict(state)
        remaining.extend(list(s))
    assert len(remaining) == 32
    # completed samples are not replayed
    all_epoch = ElasticDistributedSampler(
        n, num_replicas=1, rank=0, shuffle=True
    )
    all_epoch.load_state_dict({**state, "completed": 0})
    first32 = list(all_epoch)[:32]
    assert not (set(first32) & set(remaining))


def test_sampler_resume_fuzz_covers_epoch_exactly_once():
    """Property: across RANDOM resume points and world-size changes, an
    epoch's samples are consumed exactly once — no replay, no loss.
    This is the contract a mid-epoch scale event depends on (reference:
    sampler.py state_dict/load_state_dict)."""
    import numpy as np

    rng = np.random.RandomState(3)
    for trial in range(10):
        n = int(rng.randint(40, 200))
        world = int(rng.choice([1, 2, 4, 8]))
        s0 = ElasticDistributedSampler(
            n, num_replicas=world, rank=0, shuffle=True, seed=trial
        )
        per_rank_total = len(list(s0))
        # consume a random number of whole batches
        bs = int(rng.randint(1, 8))
        steps = int(rng.randint(0, max(1, per_rank_total // bs)))
        consumed = []
        ranks = [
            ElasticDistributedSampler(
                n, num_replicas=world, rank=r, shuffle=True, seed=trial
            )
            for r in range(world)
        ]
        iters = [iter(list(r)) for r in ranks]
        for _ in range(steps):
            for r in range(world):
                for _ in range(bs):
                    consumed.append(next(iters[r]))
            ranks[0].record_batch(bs)
        state = ranks[0].state_dict()

        new_world = int(rng.choice([1, 2, 4]))
        resumed = []
        for r in range(new_world):
            s = ElasticDistributedSampler(
                n, num_replicas=new_world, rank=r, shuffle=True,
                seed=trial,
            )
            s.load_state_dict(state)
            resumed.extend(list(s))
        # padding may duplicate a few tail samples WITHIN one phase,
        # but nothing consumed before the scale event is replayed
        assert not (set(consumed) & set(resumed)), (
            f"trial {trial}: replayed "
            f"{sorted(set(consumed) & set(resumed))[:5]}"
        )
        # and together both phases cover the whole epoch exactly
        assert set(consumed) | set(resumed) == set(range(n)), (
            f"trial {trial} lost samples"
        )


def test_dataloader_with_sampler_and_reconfig(tmp_path):
    cfg_path = tmp_path / "paral.json"
    cfg_path.write_text('{"version": 1, "batch_size": 8}')
    sampler = ElasticDistributedSampler(
        64, num_replicas=1, rank=0, shuffle=False
    )
    loader = ElasticDataLoader(
        fetch_fn=lambda idx: {"x": idx},
        sampler=sampler,
        batch_size=4,
        config_path=str(cfg_path),
    )
    batches = list(loader)
    # re-config to 8 picked up at construction
    assert all(len(b["x"]) == 8 for b in batches)
    assert len(batches) == 8
    assert sampler.completed == 64


def test_elastic_trainer_grad_accum_follows_world():
    replicas = {"n": 8}
    built = []

    def build_step(accum):
        built.append(accum)
        return lambda state, batch: (state, {"accum": accum})

    t = ElasticTrainer(
        global_batch_size=64,
        micro_batch_size=2,
        build_step=build_step,
        data_replicas_fn=lambda: replicas["n"],
    )
    assert t.grad_accum == 4  # 64 / (2*8)
    _, m = t.step({}, {})
    assert m["accum"] == 4

    replicas["n"] = 4  # world shrank
    t.on_membership_change()
    assert t.grad_accum == 8  # 64 / (2*4)
    assert built == [4, 8]


def test_sampler_short_tail_pads_equally():
    """Tail shorter than the pad: every rank must still yield the same
    count (lockstep SPMD deadlocks otherwise)."""
    from dlrover_tpu.elastic.sampler import ElasticDistributedSampler

    counts = []
    for rank in range(4):
        s = ElasticDistributedSampler(
            dataset_size=10, num_replicas=4, rank=rank, shuffle=False
        )
        s.load_state_dict({"epoch": 0, "completed": 9})
        counts.append(len(list(iter(s))))
    assert len(set(counts)) == 1 and counts[0] >= 1


def test_compile_cache_dir_from_job_config(monkeypatch):
    """--compile-cache-dir (job config) places the workers' cache where
    the environment does not (e.g. shared NFS so replacement hosts hit
    it); a JAX_COMPILATION_CACHE_DIR set from outside wins over it and
    is inherited, never overridden."""
    from dlrover_tpu.agent.agent import (
        ElasticLaunchConfig,
        ElasticTrainingAgent,
    )
    from dlrover_tpu.agent.launcher import parse_args
    from dlrover_tpu.agent.rendezvous import RendezvousOutcome

    args = parse_args(
        ["--compile-cache-dir", "/mnt/job-cache", "--", "python", "t.py"]
    )
    assert args.compile_cache_dir == "/mnt/job-cache"

    class _T:
        addr = "localhost:1"

    class _Client:
        _t = _T()
        node_rank = 0

    agent = ElasticTrainingAgent(
        ElasticLaunchConfig(compile_cache_dir="/mnt/job-cache"), _Client()
    )
    outcome = RendezvousOutcome(
        round=1, world={0: 1}, coordinator="localhost:7010",
        process_id=0, num_processes=1, global_chips=1,
    )
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    env = agent._worker_env(outcome)
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/mnt/job-cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/operator-env")
    env = agent._worker_env(outcome)
    assert "JAX_COMPILATION_CACHE_DIR" not in env  # inherited, not forced


_CACHE_STEP_SCRIPT = """
import json, os, sys, time
import jax, jax.numpy as jnp
sys.path.insert(0, os.environ["DLROVER_TPU_TEST_REPO"])
from dlrover_tpu.models import get_config
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.train import (
    TrainStepBuilder, batch_sharding, init_train_state, make_optimizer,
)

mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
cfg = get_config(
    "tiny", n_layer=2, d_model=64, d_ff=128, n_head=4,
    vocab_size=256, max_seq=64,
)
opt = make_optimizer(learning_rate=1e-3, warmup_steps=2, decay_steps=10)
state = init_train_state(jax.random.key(0), cfg, mesh, opt)
step = TrainStepBuilder(cfg, mesh, opt).build()
tokens = jnp.zeros((8, 64), dtype=jnp.int32)
batch = jax.device_put(
    {"tokens": tokens, "targets": tokens}, batch_sharding(mesh)
)
t0 = time.time()
state, metrics = step(state, batch)
loss = float(metrics["loss"])
print(json.dumps({"loss": loss, "step_wall_s": time.time() - t0}))
"""


@pytest.mark.slow  # tier-1 budget: prewarm pins the executable fast
def test_restart_hits_persistent_compile_cache(tmp_path):
    """The re-mesh recovery story end-to-end (VERDICT r4 ask #2): the
    SAME sharded train step run in two fresh subprocesses against a
    shared cache dir — the first populates the cache, the second adds
    ZERO new entries (pure deserialization, i.e. a restart does not pay
    the compile again)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cache = tmp_path / "jit-cache"
    cache.mkdir()
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "JAX_COMPILATION_CACHE_DIR": str(cache),
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
            "DLROVER_TPU_TEST_REPO": repo,
        }
    )
    script = _CACHE_STEP_SCRIPT

    def run():
        proc = subprocess.run(
            [sys.executable, "-c", script],
            env=env, cwd=repo, capture_output=True, text=True,
            timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        import json as json_mod

        return json_mod.loads(proc.stdout.strip().splitlines()[-1])

    first = run()
    entries_after_first = {
        p.name for p in cache.rglob("*") if p.is_file()
    }
    assert entries_after_first, "first run populated no cache entries"
    second = run()
    entries_after_second = {
        p.name for p in cache.rglob("*") if p.is_file()
    }
    # the restart compiled NOTHING new — every executable came from the
    # shared cache
    assert entries_after_second == entries_after_first
    assert second["loss"] == pytest.approx(first["loss"], rel=1e-6)


def test_worker_env_sets_persistent_compile_cache(monkeypatch):
    """Restarted workers must share an XLA compile cache — the re-mesh
    recovery-time lever (SURVEY §7): same-shape restarts skip recompile."""
    from dlrover_tpu.agent.agent import (
        ElasticLaunchConfig,
        ElasticTrainingAgent,
    )
    from dlrover_tpu.agent.rendezvous import RendezvousOutcome
    from dlrover_tpu.common import compile_cache

    class _T:
        addr = "localhost:1"

    class _Client:
        _t = _T()
        node_rank = 0

    agent = ElasticTrainingAgent(ElasticLaunchConfig(), _Client())
    outcome = RendezvousOutcome(
        round=1, world={0: 1}, coordinator="localhost:7010",
        process_id=0, num_processes=1, global_chips=1,
    )
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    env = agent._worker_env(outcome)
    assert env["JAX_COMPILATION_CACHE_DIR"] == compile_cache.DEFAULT_DIR
    # an operator-set cache dir wins (worker env inherits os.environ)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/custom")
    env = agent._worker_env(outcome)
    assert "JAX_COMPILATION_CACHE_DIR" not in env  # inherited, not forced


def test_comm_perf_test_reports_bandwidth():
    """--comm-perf-test sweep: positive GB/s per payload size on the
    8-device mesh, keyed by payload bytes."""
    from dlrover_tpu.agent.node_check import run_comm_perf_test

    res = run_comm_perf_test(sizes=(1 << 16, 1 << 18))
    # keys are the requested global element counts — per-device derived
    # byte sizes can collide between nearby requested sizes
    assert set(res) == {1 << 16, 1 << 18}
    assert all(v > 0 for v in res.values())
    # regression: sizes within a factor of device-count must not collide
    res2 = run_comm_perf_test(sizes=(1 << 16, 1 << 17))
    assert len(res2) == 2


@pytest.mark.slow
def test_prewarm_produces_the_exact_step_executable(tmp_path, monkeypatch):
    """Re-mesh pre-warming (SURVEY §7's 'pre-compile async where
    possible'): AOT-lowering the train step for a candidate world must
    produce the IDENTICAL persistent-cache entry the live job compiles
    — same content key — so a later re-mesh to that world deserializes
    instead of compiling. Proven by content-addressing: the largest
    entry a real run writes (the train-step executable) must already
    exist, byte-keyed, in a cache populated ONLY by prewarm."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # pin the AMBIENT env too: prewarm_worlds builds its child env from
    # os.environ, and cache keys embed XLA flags — an ambient
    # --xla_dump_to (common while debugging) would make the two
    # children's keys diverge for reasons unrelated to prewarm
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
    )
    monkeypatch.delenv("DLROVER_TPU_PREWARM_PLATFORM", raising=False)
    # an ambient cache dir would win over prewarm's cache_dir argument
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
            "DLROVER_TPU_TEST_REPO": repo,
        }
    )

    # NOTE: prewarm and the job must share ONE cache dir — this jax's
    # key embeds the cache path itself (the per-fusion autotune cache
    # dir rides in debug_options un-zeroed), so entries are only ever
    # portable within a directory. That matches production: the agent
    # points prewarm at the same dir it exports to workers.
    cache = tmp_path / "cache"
    cache.mkdir()

    # 1) prewarm ONLY (AOT — no arrays materialized) for the candidate
    #    world the job will later run at
    from dlrover_tpu.train.prewarm import prewarm_worlds

    ok = prewarm_worlds(
        "tiny",
        worlds=[{"n_devices": 8, "dp": 2, "fsdp": 2, "tp": 2}],
        batch_size=8,
        seq=64,
        model_kw=dict(n_layer=2, d_model=64, d_ff=128, n_head=4,
                      vocab_size=256, max_seq=64),
        opt_kw=dict(learning_rate=1e-3, warmup_steps=2, decay_steps=10),
        cache_dir=str(cache),
        timeout_s=600,
    )
    assert ok, "prewarm subprocess failed"
    prewarmed_steps = {
        p.name for p in cache.rglob("*jit_step_fn*") if p.is_file()
    }
    assert prewarmed_steps, "prewarm produced no train-step entry"

    # 2) the real job runs: its train step must be a pure cache HIT —
    #    no new jit_step_fn entry beyond what prewarm wrote
    env_run = dict(env, JAX_COMPILATION_CACHE_DIR=str(cache))
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_STEP_SCRIPT],
        env=env_run, cwd=repo, capture_output=True, text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    steps_after = {
        p.name for p in cache.rglob("*jit_step_fn*") if p.is_file()
    }
    assert steps_after == prewarmed_steps, (
        "the live job compiled a train step the prewarm missed: "
        f"{sorted(steps_after - prewarmed_steps)}"
    )


def test_elastic_trainer_shrink_grow_keeps_global_batch():
    """8→6→8 hosts with global batch 48: grad_accum re-derives to 3→4→3
    and the EFFECTIVE batch — what the LR schedule sees — never moves."""
    from dlrover_tpu.observability import telemetry

    replicas = {"n": 8}
    telemetry.reset_hub()
    hub = telemetry.configure_hub()
    events = []
    hub.subscribe(events.append)
    try:
        t = ElasticTrainer(
            global_batch_size=48,
            micro_batch_size=2,
            build_step=lambda accum: (lambda s, b: (s, {})),
            data_replicas_fn=lambda: replicas["n"],
        )
        seen = [(t.grad_accum, t.grad_accum * 2 * replicas["n"])]
        for n in (6, 8):
            replicas["n"] = n
            t.on_membership_change()
            seen.append((t.grad_accum, t.grad_accum * 2 * n))
        assert seen == [(3, 48), (4, 48), (3, 48)]
        # no drift: the schedule's global batch was preserved throughout
        assert not [e for e in events if e.kind == "effective_batch_drift"]
    finally:
        telemetry.reset_hub()


def test_elastic_trainer_drift_published_as_numeric_event():
    """global=50 is not reachable with micro=2 × replicas=8: accum
    rounds up to 4 → effective 64. The +14 drift must surface as a
    NumericEvent, not just a log line."""
    from dlrover_tpu.observability import telemetry

    telemetry.reset_hub()
    hub = telemetry.configure_hub()
    events = []
    hub.subscribe(events.append)
    try:
        t = ElasticTrainer(
            global_batch_size=50,
            micro_batch_size=2,
            build_step=lambda accum: (lambda s, b: (s, {})),
            data_replicas_fn=lambda: 8,
        )
        assert t.grad_accum == 4
        drifts = [e for e in events if e.kind == "effective_batch_drift"]
        assert len(drifts) == 1
        assert isinstance(drifts[0], telemetry.NumericEvent)
        assert drifts[0].value == 14.0  # 64 - 50
        assert "effective=64" in drifts[0].detail
    finally:
        telemetry.reset_hub()


@pytest.mark.parametrize("drop_last", [False, True])
@pytest.mark.parametrize("shuffle", [False, True])
def test_sampler_mid_epoch_eviction_no_loss_no_dup(drop_last, shuffle):
    """Property: an eviction mid-epoch (num_replicas 8→6, every rank
    re-assigned) neither drops nor duplicates samples. drop_last=False
    may duplicate only pad indices (tail tiling for lockstep SPMD);
    drop_last=True may drop only a tail shorter than the new world."""
    rng = np.random.RandomState(11)
    for trial in range(12):
        n = int(rng.randint(50, 300))
        r1 = int(rng.choice([4, 6, 8]))
        r2 = int(rng.choice([2, 3, 4, 6]))
        bs = int(rng.randint(1, 5))
        steps = int(rng.randint(1, max(2, n // (bs * r1))))

        ranks1 = [
            ElasticDistributedSampler(
                n, num_replicas=r1, rank=r, shuffle=shuffle,
                seed=7, drop_last=drop_last,
            )
            for r in range(r1)
        ]
        consumed = []
        iters = [iter(s) for s in ranks1]
        for _ in range(steps):
            for it in iters:
                for _ in range(bs):
                    consumed.append(next(it))
        for s in ranks1:
            for _ in range(steps):
                s.record_batch(bs)
        state = ranks1[0].state_dict()
        assert state["completed"] == steps * bs * r1

        remaining = []
        for r in range(r2):
            s = ElasticDistributedSampler(
                n, num_replicas=r2, rank=r, shuffle=shuffle,
                seed=0, drop_last=drop_last,
            )
            s.load_state_dict(state)
            remaining.extend(list(s))

        consumed_set, remaining_set = set(consumed), set(remaining)
        # nothing consumed pre-eviction is replayed post-eviction
        assert not (consumed_set & remaining_set), (trial, drop_last)
        if drop_last:
            # only a tail shorter than the new world may be dropped
            missed = set(range(n)) - consumed_set - remaining_set
            assert len(missed) < r2, (trial, len(missed), r2)
            assert len(remaining) == len(remaining_set)
        else:
            # full coverage; duplicates are exactly the lockstep pad
            assert consumed_set | remaining_set == set(range(n)), trial
            assert len(remaining) - len(remaining_set) == (
                (-(n - state["completed"])) % r2
            ), trial
