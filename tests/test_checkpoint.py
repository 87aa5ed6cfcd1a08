"""Flash Checkpoint tests: shm staging, persist/commit, resharded restore."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.checkpoint import Checkpointer, StorageType
from dlrover_tpu.checkpoint import core
from dlrover_tpu.checkpoint.checkpointer import state_template
from dlrover_tpu.checkpoint.storage import (
    KeepLatestStepStrategy,
    PosixStorage,
    read_tracker,
)
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.parallel import sharding as shd
from jax.sharding import NamedSharding, PartitionSpec as P


@pytest.fixture(autouse=True)
def _run_id(tmp_path, monkeypatch):
    monkeypatch.setenv("DLROVER_TPU_RUN_ID", f"test{os.getpid()}_{time.time_ns()}")


def _state(mesh=None):
    a = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    b = jnp.ones((16,), jnp.bfloat16)
    if mesh is not None:
        a = jax.device_put(a, NamedSharding(mesh, P(("dp", "fsdp"), "tp")))
        b = jax.device_put(b, NamedSharding(mesh, P("tp")))
    return {"params": {"w": a, "b": b}, "step": jnp.asarray(3)}


def test_pack_roundtrip_unsharded():
    state = _state()
    entries, payload = core.plan_pack(state)
    header = core.header_bytes(7, entries)
    buf = memoryview(bytearray(core.pack_size(header, payload)))
    used = core.write_pack(buf, 7, state, entries)
    idx = core.PackIndex()
    idx.add_pack(buf[:used])
    assert idx.step == 7
    out = core.restore_tree(state_template(state), idx)
    np.testing.assert_array_equal(
        np.asarray(out["params"]["w"]), np.asarray(state["params"]["w"])
    )
    assert out["params"]["b"].dtype == jnp.bfloat16
    assert int(out["step"]) == 3


def test_restore_casts_to_target_dtype():
    """A precision change between save and restore (bf16 run resumed in
    f32, or vice versa) must land in the TARGET dtype, sharded or not."""
    state = _state()
    entries, payload = core.plan_pack(state)
    header = core.header_bytes(1, entries)
    buf = memoryview(bytearray(core.pack_size(header, payload)))
    used = core.write_pack(buf, 1, state, entries)
    idx = core.PackIndex()
    idx.add_pack(buf[:used])
    target = {
        "params": {
            "w": jax.ShapeDtypeStruct((8, 8), jnp.bfloat16),  # was f32
            "b": jax.ShapeDtypeStruct((16,), jnp.float32),    # was bf16
        },
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    out = core.restore_tree(target, idx)
    assert out["params"]["w"].dtype == jnp.bfloat16
    assert out["params"]["b"].dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(out["params"]["w"], np.float32),
        np.asarray(state["params"]["w"]),
        rtol=1e-2,
    )
    # sharded path casts too
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    sh = {
        "params": {
            "w": NamedSharding(mesh, P(("dp", "fsdp"), "tp")),
            "b": NamedSharding(mesh, P("tp")),
        },
        "step": NamedSharding(mesh, P()),
    }
    out_s = core.restore_tree(target, idx, sh)
    assert out_s["params"]["w"].dtype == jnp.bfloat16
    assert out_s["params"]["b"].dtype == jnp.float32


def test_pack_roundtrip_sharded():
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    state = _state(mesh)
    entries, payload = core.plan_pack(state)
    header = core.header_bytes(1, entries)
    buf = memoryview(bytearray(core.pack_size(header, payload)))
    used = core.write_pack(buf, 1, state, entries)
    idx = core.PackIndex()
    idx.add_pack(buf[:used])
    # restore onto a DIFFERENT sharding (resharded restore)
    new_shardings = {
        "params": {
            "w": NamedSharding(mesh, P("tp", None)),
            "b": NamedSharding(mesh, P(None)),
        },
        "step": NamedSharding(mesh, P()),
    }
    out = core.restore_tree(state_template(state), idx, new_shardings)
    np.testing.assert_array_equal(
        np.asarray(out["params"]["w"]), np.asarray(state["params"]["w"])
    )
    assert out["params"]["w"].sharding.spec == P("tp", None)


def test_pack_reshard_fuzz():
    """Randomized pack→restore across sharding layouts: random shapes,
    dtypes, and source/target PartitionSpecs. Dims are kept divisible
    by every axis combo because jax's NamedSharding device_put rejects
    uneven dims outright — unevenly-sharded leaves cannot exist in this
    framework. Any offset/slice bug in the pack format shows up as a
    value mismatch here long before a multi-host scale event would
    find it."""
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    rng = np.random.RandomState(0)
    axes_pool = [None, "dp", "fsdp", "tp", ("dp", "fsdp")]

    def rand_spec(ndim):
        picked, used = [], set()
        for _ in range(ndim):
            ax = axes_pool[rng.randint(len(axes_pool))]
            names = (
                set()
                if ax is None
                else {ax} if isinstance(ax, str) else set(ax)
            )
            if names & used:
                ax = None
            used |= names
            picked.append(ax)
        return P(*picked)

    for trial in range(8):
        state, src_sh, dst_sh = {}, {}, {}
        for i in range(rng.randint(2, 6)):
            ndim = rng.randint(1, 4)
            # dims divisible by 4 so every axis combo divides evenly
            shape = tuple(4 * rng.randint(1, 5) for _ in range(ndim))
            dtype = [jnp.float32, jnp.bfloat16, jnp.int32][
                rng.randint(3)
            ]
            arr = jnp.asarray(
                rng.randint(-100, 100, size=shape), dtype=dtype
            )
            key = f"leaf{i}"
            state[key] = jax.device_put(
                arr, NamedSharding(mesh, rand_spec(ndim))
            )
            dst_sh[key] = NamedSharding(mesh, rand_spec(ndim))
        entries, payload = core.plan_pack(state)
        header = core.header_bytes(trial, entries)
        buf = memoryview(bytearray(core.pack_size(header, payload)))
        used = core.write_pack(buf, trial, state, entries)
        idx = core.PackIndex()
        idx.add_pack(buf[:used])
        out = core.restore_tree(state_template(state), idx, dst_sh)
        for key in state:
            np.testing.assert_array_equal(
                np.asarray(out[key]),
                np.asarray(state[key]),
                err_msg=f"trial {trial} {key} "
                f"{state[key].sharding.spec}->{dst_sh[key].spec}",
            )
            assert out[key].sharding.spec == dst_sh[key].spec


def test_checkpointer_disk_roundtrip(tmp_path):
    ckpt = Checkpointer(str(tmp_path / "ckpt"), use_agent=False)
    state = _state()
    assert ckpt.save_checkpoint(10, state, StorageType.DISK)
    ckpt.wait_for_persist()
    assert ckpt.latest_committed_step() == 10
    out = ckpt.load_checkpoint(state_template(state))
    np.testing.assert_array_equal(
        np.asarray(out["params"]["w"]), np.asarray(state["params"]["w"])
    )


def test_checkpointer_memory_then_load(tmp_path):
    ckpt = Checkpointer(str(tmp_path / "ckpt"), use_agent=False)
    state = _state()
    assert ckpt.save_checkpoint(5, state, StorageType.MEMORY)
    # nothing persisted to disk
    assert ckpt.latest_committed_step() is None
    out = ckpt.load_checkpoint(state_template(state))
    assert out is not None
    np.testing.assert_array_equal(
        np.asarray(out["params"]["w"]), np.asarray(state["params"]["w"])
    )


def test_restored_state_lowers_the_step_as_an_initialised_one_does(tmp_path):
    """A worker restarted after a crash must find the step it compiled
    before in the persistent cache, and the cache's key is the lowered
    program's text: the initialised state, the abstract template and a
    state restored into the template have to lower alike. The compiler
    spells a sharding over size-one mesh axes as ``P()``, the rules
    spell it ``P('tp', 'fsdp')`` — restore also comes before init, so
    nothing is ever held twice."""
    from dlrover_tpu.models import get_config
    from dlrover_tpu.parallel.mesh import single_device_mesh
    from dlrover_tpu.train import (
        TrainStepBuilder,
        make_optimizer,
        restore_or_init_train_state,
    )
    from dlrover_tpu.train.train_step import abstract_train_state

    cfg = get_config(
        "tiny", n_layer=2, d_model=64, d_ff=128, n_head=2, vocab_size=256,
        max_seq=32,
    )
    mesh = single_device_mesh()
    opt = make_optimizer(learning_rate=1e-3)
    ckpt = Checkpointer(str(tmp_path / "ckpt"), use_agent=False)

    def start():
        return restore_or_init_train_state(
            ckpt, jax.random.key(0), cfg, mesh, opt
        )

    fresh, resumed = start()
    assert not resumed
    assert ckpt.save_checkpoint(7, fresh, StorageType.MEMORY)
    restored, resumed = start()
    assert resumed and int(restored["step"]) == int(fresh["step"])
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    batch = {"tokens": tok, "targets": tok}
    step = TrainStepBuilder(cfg, mesh, opt).build()
    texts = {
        step.lower(state, batch).as_text()
        for state in (fresh, restored, abstract_train_state(cfg, mesh, opt))
    }
    assert len(texts) == 1


def test_agent_saver_flow(tmp_path):
    """Worker stages via shm IPC; agent daemon persists + commits."""
    from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver

    saver = AsyncCheckpointSaver.start_async_saving_ckpt()
    try:
        ckpt = Checkpointer(str(tmp_path / "ckpt"), use_agent=True)
        state = _state()
        assert ckpt.save_checkpoint(20, state, StorageType.DISK)
        deadline = time.time() + 10
        while time.time() < deadline:
            if read_tracker(str(tmp_path / "ckpt"), PosixStorage()) == 20:
                break
            time.sleep(0.05)
        assert ckpt.latest_committed_step() == 20

        # memory-only stage + emergency persist (worker-failure path)
        state2 = jax.tree.map(lambda x: x + 1, state)
        assert ckpt.save_checkpoint(21, state2, StorageType.MEMORY)
        saver.save_shm_to_storage()
        assert ckpt.latest_committed_step() == 21
        out = ckpt.engine.load_from_storage(state_template(state))
        np.testing.assert_array_equal(
            np.asarray(out["params"]["w"]),
            np.asarray(state2["params"]["w"]),
        )
    finally:
        saver.close()


def test_deletion_strategy(tmp_path):
    ckpt_dir = str(tmp_path / "ckpt")
    ckpt = Checkpointer(ckpt_dir, use_agent=False)
    state = _state()
    for step in (1, 2, 3, 4):
        ckpt.save_checkpoint(step, state, StorageType.DISK)
        ckpt.wait_for_persist()
    KeepLatestStepStrategy(max_to_keep=2).clean_up(ckpt_dir, PosixStorage())
    remaining = sorted(
        d for d in os.listdir(ckpt_dir) if d.startswith("step_")
    )
    assert remaining == ["step_3", "step_4"]


def test_orbax_roundtrip(tmp_path):
    """Native pack ⇄ orbax conversion preserves values and shardings."""
    from dlrover_tpu.checkpoint.orbax_compat import (
        load_orbax,
        orbax_to_pack,
        pack_to_orbax,
        save_orbax,
    )
    from dlrover_tpu.checkpoint.engine import CheckpointEngine

    state = _state()
    # native save (committed to disk)
    engine = CheckpointEngine(str(tmp_path / "native"), use_agent=False)
    assert engine.save_to_storage(5, state)
    engine.wait_for_persist()

    # native → orbax
    out = str(tmp_path / "orbax_out")
    pack_to_orbax(
        str(tmp_path / "native"), out, state_template(state), step=5
    )
    restored = load_orbax(out)
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]), np.asarray(state["params"]["w"])
    )

    # orbax → native (fresh dir), then native restore
    orbax_to_pack(out, str(tmp_path / "native2"), step=9)
    engine2 = CheckpointEngine(str(tmp_path / "native2"), use_agent=False)
    back = engine2.load_from_storage(state_template(state))
    assert back is not None
    np.testing.assert_array_equal(
        np.asarray(back["params"]["w"]), np.asarray(state["params"]["w"])
    )
    assert int(back["step"]) == 3  # the stored scalar, not the ckpt step


def test_orbax_save_load_direct(tmp_path):
    from dlrover_tpu.checkpoint.orbax_compat import load_orbax, save_orbax

    state = {"a": jnp.arange(8.0), "b": {"c": jnp.ones((2, 2))}}
    save_orbax(str(tmp_path / "o"), state)
    out = load_orbax(str(tmp_path / "o"))
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(state["a"]))


def test_partial_restore_keeps_fresh_leaves_for_grown_tree(tmp_path):
    """State-tree upgrade path (ADVICE r4): a checkpoint saved BEFORE a
    state tree grew (e.g. fp8 gaining attention-projection amax slots)
    restores the stored leaves and keeps the live state's fresh values
    for the new ones — instead of failing the whole restore. Params
    must still restore exactly (a missing param leaf refuses even with
    partial); an abstract template with missing leaves raises; a grown
    tree without partial raises instead of reading as "no checkpoint"
    — and all of it holds on the DISK path (fresh engine, no shm
    meta), not just the shm cache."""
    from dlrover_tpu.checkpoint.core import RestoreMismatchError

    ckpt = Checkpointer(str(tmp_path / "ckpt"), use_agent=False)
    old_state = _state()
    assert ckpt.save_checkpoint(7, old_state, StorageType.DISK)
    ckpt.wait_for_persist()

    # the tree grew: a new subtree exists in the live state only
    new_state = dict(old_state)
    new_state["fp8"] = {"wq": {"amax_x": jnp.ones((16,), jnp.float32) * 3}}

    # a FRESH Checkpointer: no shm meta, restore must come from disk
    reader = Checkpointer(str(tmp_path / "ckpt"), use_agent=False)
    out = reader.load_checkpoint(new_state, partial=True)
    assert out is not None
    np.testing.assert_array_equal(
        np.asarray(out["params"]["w"]),
        np.asarray(old_state["params"]["w"]),
    )
    # the new leaves kept their fresh (initialized) values
    np.testing.assert_array_equal(
        np.asarray(out["fp8"]["wq"]["amax_x"]),
        np.asarray(new_state["fp8"]["wq"]["amax_x"]),
    )
    # ...and the shm path of the ORIGINAL engine agrees
    out2 = ckpt.load_checkpoint(new_state, partial=True)
    np.testing.assert_array_equal(
        np.asarray(out2["fp8"]["wq"]["amax_x"]),
        np.asarray(new_state["fp8"]["wq"]["amax_x"]),
    )
    # an abstract template cannot provide values for missing leaves
    with pytest.raises(RestoreMismatchError):
        reader.load_checkpoint(state_template(new_state), partial=True)
    # without partial, a grown tree fails loudly (never reads as
    # "no checkpoint → fresh start")
    with pytest.raises(RestoreMismatchError):
        reader.load_checkpoint(new_state)
    # a missing PARAM leaf refuses even under partial: substituting
    # fresh weights is a rename/corruption, not an upgrade
    renamed = dict(new_state)
    renamed["params"] = dict(old_state["params"])
    renamed["params"]["w_renamed"] = renamed["params"].pop("w")
    with pytest.raises(RestoreMismatchError):
        reader.load_checkpoint(renamed, partial=True)


def test_restore_tree_returns_owned_buffers(monkeypatch):
    """Restored leaves must be jax-OWNED copies, never zero-copy
    aliases of the numpy arrays assembled from the pack: the train step
    donates the restored state, and XLA releasing a buffer that numpy's
    malloc owns corrupts the glibc heap (flakily — jax's CPU backend
    only aliases 64-byte-aligned buffers, so the elastic resume crashed
    on roughly the malloc alignment rate). Pin the ownership contract
    by forcing read_slice to hand back guaranteed-aligned arrays and
    asserting the restored jax buffers live elsewhere."""
    state = _state()
    entries, payload = core.plan_pack(state)
    header = core.header_bytes(7, entries)
    buf = memoryview(bytearray(core.pack_size(header, payload)))
    used = core.write_pack(buf, 7, state, entries)
    idx = core.PackIndex()
    idx.add_pack(buf[:used])

    def _aligned(a):
        # view into an oversized buffer at a 64-byte-aligned offset —
        # the deterministic worst case for the zero-copy alias
        raw = np.empty(a.nbytes + 64, np.uint8)
        off = (-raw.ctypes.data) % 64
        v = raw[off : off + a.nbytes].view(a.dtype).reshape(a.shape)
        v[...] = a
        assert v.ctypes.data % 64 == 0
        return v

    src_ptrs = []
    orig = core.PackIndex.read_slice

    def read_aligned(self, path, index):
        v = _aligned(orig(self, path, index))
        src_ptrs.append((v.ctypes.data, v))  # keep alive for the check
        return v

    monkeypatch.setattr(core.PackIndex, "read_slice", read_aligned)
    out = core.restore_tree(state_template(state), idx)
    restored = [
        leaf.unsafe_buffer_pointer() for leaf in jax.tree.leaves(out)
    ]
    assert not (set(restored) & {p for p, _ in src_ptrs})
    np.testing.assert_array_equal(
        np.asarray(out["params"]["w"]), np.asarray(state["params"]["w"])
    )

    # the resharding path (make_array_from_callback) must not alias its
    # callback arrays either
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    sh = {
        "params": {
            "w": NamedSharding(mesh, P(("dp", "fsdp"), "tp")),
            "b": NamedSharding(mesh, P("tp")),
        },
        "step": NamedSharding(mesh, P()),
    }
    src_ptrs.clear()
    out_s = core.restore_tree(state_template(state), idx, sh)
    shard_ptrs = {
        s.data.unsafe_buffer_pointer()
        for leaf in jax.tree.leaves(out_s)
        for s in leaf.addressable_shards
    }
    assert not (shard_ptrs & {p for p, _ in src_ptrs})
    np.testing.assert_array_equal(
        np.asarray(out_s["params"]["w"]), np.asarray(state["params"]["w"])
    )


def test_wait_for_persist_timeout_publishes_failure(tmp_path):
    """A blown persist deadline must return False and leave a failed
    ``persist_wait`` CheckpointRecord — a silent return here let callers
    tear down hosts believing the disk tier was durable."""
    import threading

    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.observability import telemetry

    telemetry.reset_hub()
    hub = telemetry.configure_hub()
    events = []
    hub.subscribe(events.append)
    try:
        engine = CheckpointEngine(str(tmp_path / "ckpt"), use_agent=False)
        engine._local_step = 42
        # a persist that will not finish inside the deadline
        engine._persist_thread = threading.Thread(
            target=time.sleep, args=(1.5,), daemon=True
        )
        engine._persist_thread.start()
        assert engine.wait_for_persist(timeout=0.05) is False
        fails = [
            e
            for e in events
            if isinstance(e, telemetry.CheckpointRecord)
            and e.kind == "persist_wait"
        ]
        assert len(fails) == 1
        assert fails[0].ok is False
        assert fails[0].step == 42 and fails[0].tier == "storage"
        # once the thread finishes, the wait succeeds and stays quiet
        engine._persist_thread.join()
        assert engine.wait_for_persist(timeout=0.05) is True
        assert len([e for e in events if e.kind == "persist_wait"]) == 1
    finally:
        telemetry.reset_hub()


def test_stale_broker_socket_heals_to_standalone(tmp_path, monkeypatch):
    """A SIGKILLed agent leaves its IPC socket file behind; the next
    engine in that namespace must NOT become a client of the dead
    broker — it probes the socket, unlinks the corpse, and runs
    standalone."""
    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.common import multi_process as mp

    monkeypatch.setenv("DLROVER_TPU_RUN_ID", f"stale{os.getpid()}")
    path = mp._socket_path("queue_ckpt")
    # the corpse: a bound-then-abandoned unix socket (no listener)
    import socket as socket_mod

    s = socket_mod.socket(socket_mod.AF_UNIX, socket_mod.SOCK_STREAM)
    s.bind(path)
    s.close()
    assert os.path.exists(path)

    eng = CheckpointEngine(str(tmp_path))
    assert eng._use_agent is False
    assert not os.path.exists(path), "stale socket should be unlinked"

    # a LIVE broker still routes the engine into client mode
    from dlrover_tpu.common.multi_process import SharedQueue

    broker = SharedQueue("ckpt")
    try:
        assert mp.broker_alive("queue_ckpt") is True
        eng2 = CheckpointEngine(str(tmp_path))
        assert eng2._use_agent is True
    finally:
        broker.close()
