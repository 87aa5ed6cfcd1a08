"""Sparse embedding tier tests (C++ KvTable + group optimizers + JAX glue).

Mirrors the reference's gtest coverage for KvVariable
(tfplus/tfplus/kv_variable/kernels/kv_variable_test.cc) and the python op
tests in tfplus/py_ut, on the TPU-native surface.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.sparse import (
    EmbeddingCollection,
    EmbeddingSpec,
    GroupAdagrad,
    GroupAdam,
    KvTable,
    ScatterOp,
    SparseGroupFtrl,
    SparseMomentum,
    SparseSGD,
)
from dlrover_tpu.sparse.embedding import lookup_callback, take_rows


@pytest.fixture
def table():
    t = KvTable("t", 4, n_slots=2, initializer="zeros")
    yield t
    t.close()


class TestKvTable:
    def test_gather_or_zeros_missing(self, table):
        out = table.gather_or_zeros([1, 2, 3])
        assert out.shape == (3, 4)
        np.testing.assert_array_equal(out, 0.0)
        assert len(table) == 0  # gather_or_zeros must not insert

    def test_gather_or_insert_creates_and_counts(self, table):
        table.gather_or_insert([7, 8])
        assert len(table) == 2
        table.gather_or_insert([7])
        np.testing.assert_array_equal(table.frequency([7, 8, 99]), [2, 1, 0])

    def test_random_init_deterministic(self):
        a = KvTable("a", 8, n_slots=0, initializer="uniform", seed=42)
        b = KvTable("b", 8, n_slots=0, initializer="uniform", seed=42)
        ra = a.gather_or_insert([3, 5])
        rb = b.gather_or_insert([3, 5])
        np.testing.assert_array_equal(ra, rb)
        assert np.abs(ra).max() <= 0.05
        assert np.abs(ra).max() > 0  # actually random
        # different keys → different rows
        assert not np.array_equal(ra[0], ra[1])
        a.close(); b.close()

    def test_insert_and_scatter_ops(self, table):
        table.insert([1], np.full((1, 4), 2.0))
        table.scatter([1], np.full((1, 4), 3.0), ScatterOp.ADD)
        np.testing.assert_allclose(table.gather_or_zeros([1]), 5.0)
        table.scatter([1], np.full((1, 4), 2.0), ScatterOp.DIV)
        np.testing.assert_allclose(table.gather_or_zeros([1]), 2.5)
        table.scatter([1], np.full((1, 4), 1.0), ScatterOp.MIN)
        np.testing.assert_allclose(table.gather_or_zeros([1]), 1.0)
        table.scatter([1], np.full((1, 4), 9.0), ScatterOp.UPDATE)
        np.testing.assert_allclose(table.gather_or_zeros([1]), 9.0)

    def test_delete_and_ttl(self, table):
        table.gather_or_insert([1, 2], now_ts=100)
        table.gather_or_insert([3], now_ts=200)
        assert table.delete([1]) == 1
        assert len(table) == 2
        # TTL: evict keys last touched before ts=150
        assert table.delete_before_timestamp(150) == 1
        assert len(table) == 1
        assert table.gather_or_zeros([3]).shape == (1, 4)

    def test_slot_reuse_after_delete(self, table):
        table.insert([1], np.full((1, 4), 7.0))
        table.delete([1])
        table.gather_or_insert([2])  # should reuse slot, zero-initialized
        np.testing.assert_array_equal(table.gather_or_zeros([2]), 0.0)

    def test_export_import_full(self, table, tmp_path):
        keys = np.arange(10, dtype=np.int64)
        table.insert(keys, np.arange(40, dtype=np.float32).reshape(10, 4))
        path = str(tmp_path / "snap.npz")
        assert table.save(path) == 10
        other = KvTable("o", 4, n_slots=2, initializer="zeros")
        assert other.restore(path) == 10
        np.testing.assert_array_equal(
            other.gather_or_zeros(keys), table.gather_or_zeros(keys)
        )
        np.testing.assert_array_equal(other.timestamp(keys), table.timestamp(keys))
        other.close()

    def test_delta_export_incremental(self, table, tmp_path):
        """full-or-delta export parity (ops/kv_variable_ops.cc:576-680):
        delta contains only rows touched since the last export."""
        table.insert([1, 2, 3], np.ones((3, 4)))
        full = str(tmp_path / "full.npz")
        table.save(full)  # clears dirty bits
        table.insert([3], np.full((1, 4), 5.0))  # touch one row
        table.insert([9], np.full((1, 4), 9.0))  # new row
        delta = str(tmp_path / "delta.npz")
        assert table.save(delta, delta_only=True) == 2
        # restore full then delta elsewhere
        other = KvTable("o2", 4, n_slots=2, initializer="zeros")
        other.restore(full)
        other.restore(delta, clear_table=False)
        np.testing.assert_allclose(other.gather_or_zeros([3])[0], 5.0)
        np.testing.assert_allclose(other.gather_or_zeros([9])[0], 9.0)
        np.testing.assert_allclose(other.gather_or_zeros([1])[0], 1.0)
        assert len(other) == 4
        other.close()

    def test_delta_is_cumulative_since_full(self, table, tmp_path):
        """Overwriting the delta file between saves must lose nothing:
        each delta carries ALL changes since the last full snapshot."""
        table.insert([1, 2], np.ones((2, 4)))
        full = str(tmp_path / "full.npz")
        table.save(full)
        delta = str(tmp_path / "delta.npz")
        table.insert([3], np.full((1, 4), 3.0))
        assert table.save(delta, delta_only=True) == 1
        table.insert([4], np.full((1, 4), 4.0))
        # second delta OVERWRITES the first; key 3 must still be in it
        assert table.save(delta, delta_only=True) == 2
        other = KvTable("cum", 4, n_slots=2, initializer="zeros")
        other.restore(full)
        other.restore(delta, clear_table=False)
        np.testing.assert_allclose(other.gather_or_zeros([3])[0], 3.0)
        np.testing.assert_allclose(other.gather_or_zeros([4])[0], 4.0)
        assert len(other) == 4
        other.close()

    def test_delta_carries_deletions(self, table, tmp_path):
        """TTL eviction / deletes must survive a full+delta restore
        (the reference's full-or-delta export tracks deleted keys)."""
        table.insert([1, 2, 3], np.ones((3, 4)), now_ts=100)
        full = str(tmp_path / "full.npz")
        table.save(full)
        table.insert([9], np.full((1, 4), 9.0), now_ts=300)
        assert table.delete_before_timestamp(200) == 3  # evict 1,2,3
        delta = str(tmp_path / "delta.npz")
        table.save(delta, delta_only=True)
        other = KvTable("tomb", 4, n_slots=2, initializer="zeros")
        other.restore(full)
        other.restore(delta, clear_table=False)
        assert len(other) == 1  # 1,2,3 stay dead
        np.testing.assert_allclose(other.gather_or_zeros([1])[0], 0.0)
        np.testing.assert_allclose(other.gather_or_zeros([9])[0], 9.0)
        other.close()
        # a re-inserted key is not resurrection-deleted by the tombstone
        table.insert([2], np.full((1, 4), 2.0), now_ts=400)
        delta2 = str(tmp_path / "delta2.npz")
        table.save(delta2, delta_only=True)
        other2 = KvTable("tomb2", 4, n_slots=2, initializer="zeros")
        other2.restore(full)
        other2.restore(delta2, clear_table=False)
        np.testing.assert_allclose(other2.gather_or_zeros([2])[0], 2.0)
        assert len(other2) == 2  # keys 2 and 9
        other2.close()

    def test_delta_survives_restart_cycle(self, table, tmp_path):
        """Restored delta rows must stay dirty: after a crash+restore,
        the next cumulative delta still carries them."""
        table.insert([1], np.ones((1, 4)))
        full = str(tmp_path / "full.npz")
        table.save(full)
        table.insert([2], np.full((1, 4), 2.0))
        delta = str(tmp_path / "delta.npz")
        table.save(delta, delta_only=True)
        # "restart": fresh table restores full + delta
        t2 = KvTable("restart", 4, n_slots=2, initializer="zeros")
        t2.restore(full)
        t2.restore(delta, clear_table=False)
        # train on, touching only key 3; overwrite the delta file
        t2.insert([3], np.full((1, 4), 3.0))
        t2.save(delta, delta_only=True)
        # second restart: key 2 must still be recoverable from full+delta
        t3 = KvTable("restart2", 4, n_slots=2, initializer="zeros")
        t3.restore(full)
        t3.restore(delta, clear_table=False)
        np.testing.assert_allclose(t3.gather_or_zeros([2])[0], 2.0)
        np.testing.assert_allclose(t3.gather_or_zeros([3])[0], 3.0)
        t2.close(); t3.close()

    def test_gather_or_insert_rows_reach_delta(self, table, tmp_path):
        """Rows created by gather_or_insert (the train-path insert) must
        be dirty, else delta checkpoints silently drop new features."""
        table.save(str(tmp_path / "full.npz"))  # clears dirty
        table.gather_or_insert([7, 8])
        keys, _, _, _ = table.export(delta_only=True)
        assert set(keys.tolist()) == {7, 8}

    def test_export_capacity_bound(self, table):
        """kv_export never writes past the caller's buffer size."""
        import ctypes

        table.insert(np.arange(10, dtype=np.int64), np.ones((10, 4)))
        cap = 4
        keys = np.empty(cap, dtype=np.int64)
        values = np.empty((cap, table.width), dtype=np.float32)
        freqs = np.empty(cap, dtype=np.uint32)
        ts = np.empty(cap, dtype=np.uint32)
        written = int(table._lib.kv_export(
            table._h, 0, 0,
            table._ptr(keys, ctypes.c_int64),
            table._ptr(values, ctypes.c_float),
            table._ptr(freqs, ctypes.c_uint32),
            table._ptr(ts, ctypes.c_uint32),
            cap,
        ))
        assert written == cap

    def test_import_layout_mismatch_raises(self, table, tmp_path):
        table.insert([1], np.ones((1, 4)))
        path = str(tmp_path / "snap.npz")
        table.save(path)
        other = KvTable("o3", 8, n_slots=2)
        with pytest.raises(ValueError):
            other.restore(path)
        other.close()


class TestSparseOptimizers:
    def _numpy_adam(self, w, g, steps, lr=0.1, b1=0.9, b2=0.999, eps=1e-8):
        m = np.zeros_like(w); v = np.zeros_like(w)
        for t in range(1, steps + 1):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mhat = m / (1 - b1 ** t)
            vhat = v / (1 - b2 ** t)
            w = w - lr * mhat / (np.sqrt(vhat) + eps)
        return w

    def test_adam_matches_numpy(self):
        t = KvTable("adam", 6, n_slots=2, initializer="zeros")
        opt = GroupAdam(lr=0.1)
        g = np.linspace(-1, 1, 6, dtype=np.float32).reshape(1, 6)
        for _ in range(5):
            opt.apply(t, [42], g)
        expected = self._numpy_adam(np.zeros((1, 6), np.float32), g, 5)
        np.testing.assert_allclose(t.gather_or_zeros([42]), expected, atol=1e-5)
        t.close()

    def test_adagrad_matches_numpy(self):
        t = KvTable("ag", 4, n_slots=1, initializer="zeros")
        opt = GroupAdagrad(lr=0.5)
        g = np.full((1, 4), 2.0, dtype=np.float32)
        acc = np.zeros((1, 4)); w = np.zeros((1, 4))
        for _ in range(3):
            opt.apply(t, [1], g)
            acc += g * g
            w -= 0.5 * g / (np.sqrt(acc) + 1e-10)
        np.testing.assert_allclose(t.gather_or_zeros([1]), w, atol=1e-6)
        t.close()

    def test_sgd_and_momentum(self):
        t = KvTable("sgd", 4, n_slots=1, initializer="zeros")
        SparseSGD(lr=1.0).apply(t, [1], np.ones((1, 4)))
        np.testing.assert_allclose(t.gather_or_zeros([1]), -1.0)
        t2 = KvTable("mom", 4, n_slots=1, initializer="zeros")
        opt = SparseMomentum(lr=1.0, momentum=0.5)
        opt.apply(t2, [1], np.ones((1, 4)))
        opt.apply(t2, [1], np.ones((1, 4)))
        # buf: 1 then 1.5 → w = -(1 + 1.5) = -2.5
        np.testing.assert_allclose(t2.gather_or_zeros([1]), -2.5)
        t.close(); t2.close()

    def test_ftrl_l1_gives_exact_zeros(self):
        t = KvTable("ftrl", 4, n_slots=2, initializer="zeros")
        opt = SparseGroupFtrl(lr=0.5, l1=10.0)  # huge l1 → everything clips
        opt.apply(t, [1], np.full((1, 4), 0.1, dtype=np.float32))
        np.testing.assert_array_equal(t.gather_or_zeros([1]), 0.0)
        t.close()

    def test_group_lasso_zeroes_whole_row(self):
        t = KvTable("gl", 4, n_slots=2, initializer="zeros")
        opt = GroupAdam(lr=0.01, l21=100.0)  # brutal group penalty
        opt.apply(t, [1], np.full((1, 4), 0.5, dtype=np.float32))
        np.testing.assert_array_equal(t.gather_or_zeros([1]), 0.0)
        t.close()

    def test_enter_threshold_gates_updates(self):
        """Low-frequency admission: keys below enter_threshold keep their
        value under optimizer updates (reference freq filtering)."""
        t = KvTable("thr", 4, n_slots=2, initializer="zeros",
                    enter_threshold=3)
        opt = SparseSGD(lr=1.0)
        applied = opt.apply(t, [5], np.ones((1, 4)))
        assert applied == 0
        np.testing.assert_array_equal(t.gather_or_zeros([5]), 0.0)
        # bump frequency past the threshold → updates apply
        t.increase_count([5], 5)
        assert opt.apply(t, [5], np.ones((1, 4))) == 1
        np.testing.assert_allclose(t.gather_or_zeros([5]), -1.0)
        t.close()

    def test_slot_mismatch_raises(self):
        t = KvTable("sm", 4, n_slots=1)
        with pytest.raises(ValueError):
            GroupAdam().apply(t, [1], np.ones((1, 4)))
        t.close()


class TestEmbeddingCollection:
    def test_pull_step_push_learns(self):
        """End-to-end: jitted regression step over host-pulled rows; the
        host-side GroupAdam must drive the loss down."""
        coll = EmbeddingCollection(
            [EmbeddingSpec("feat", dim=4, initializer="zeros")],
            optimizer=GroupAdam(lr=0.05),
        )
        ids = np.array([[3, 7], [3, 11]], dtype=np.int64)  # dup key 3
        target = jnp.ones((2,), dtype=jnp.float32)

        @jax.jit
        def step(rows, inverse, target):
            def loss_fn(rows):
                emb = take_rows(rows, inverse)   # [2, 2, 4]
                pred = emb.sum(axis=(1, 2))
                return jnp.mean((pred - target) ** 2)

            loss, grads = jax.value_and_grad(loss_fn)(rows)
            return loss, grads

        losses = []
        for _ in range(60):
            dev, host = coll.pull({"feat": ids})
            rows, inverse = dev["feat"]
            loss, gr = step(rows, inverse, target)
            coll.push(host, {"feat": gr})
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.05
        coll.close()

    def test_per_table_optimizer_steps(self):
        """One optimizer over two tables: each table's bias correction
        must see its own step count, not the interleaved total."""
        from dlrover_tpu.sparse.kv_table import GroupAdam, KvTable

        shared = GroupAdam(lr=0.1)
        solo = GroupAdam(lr=0.1)
        ta = KvTable("ta", 4, n_slots=2, initializer="zeros")
        tb = KvTable("tb", 4, n_slots=2, initializer="zeros")
        tc = KvTable("tc", 4, n_slots=2, initializer="zeros")
        g = np.full((1, 4), 0.5, dtype=np.float32)
        for _ in range(3):
            shared.apply(ta, [1], g)   # interleaved: ta, tb, ta, tb, ...
            shared.apply(tb, [1], g)
            solo.apply(tc, [1], g)     # tc sees steps 1,2,3
        np.testing.assert_allclose(
            ta.gather_or_zeros([1]), tc.gather_or_zeros([1]), rtol=1e-6
        )
        np.testing.assert_allclose(
            tb.gather_or_zeros([1]), tc.gather_or_zeros([1]), rtol=1e-6
        )
        assert shared.state_dict()["steps"] == {"ta": 3, "tb": 3}
        for t in (ta, tb, tc):
            t.close()

    def test_pull_frozen_does_not_mutate(self):
        coll = EmbeddingCollection([EmbeddingSpec("f", dim=4)])
        coll.pull({"f": np.array([1, 2])})
        n0 = len(coll.tables["f"])
        f0 = coll.tables["f"].frequency([1, 2]).copy()
        dev = coll.pull_frozen({"f": np.array([1, 2, 777])})
        rows, inv = dev["f"]
        assert len(coll.tables["f"]) == n0          # no insert of 777
        np.testing.assert_array_equal(
            coll.tables["f"].frequency([1, 2]), f0  # no freq bump
        )
        # unseen id gets the cold-start zero row
        np.testing.assert_allclose(np.asarray(rows)[int(inv[2])], 0.0)
        coll.close()

    def test_save_restore_roundtrip(self, tmp_path):
        coll = EmbeddingCollection([EmbeddingSpec("f", dim=4)])
        coll.pull({"f": np.array([1, 2, 3])})
        coll.save(str(tmp_path))
        vals = coll.tables["f"].gather_or_zeros([1, 2, 3])
        coll2 = EmbeddingCollection([EmbeddingSpec("f", dim=4)])
        coll2.restore(str(tmp_path))
        np.testing.assert_array_equal(
            coll2.tables["f"].gather_or_zeros([1, 2, 3]), vals
        )
        coll.close(); coll2.close()

    def test_lookup_callback_in_jit(self):
        t = KvTable("cb", 4, n_slots=0, initializer="zeros")
        t.insert([5], np.full((1, 4), 2.0))

        @jax.jit
        def f(ids):
            return lookup_callback(t, ids).sum(axis=-1)

        out = f(jnp.array([[5, 6]], dtype=jnp.int64))
        np.testing.assert_allclose(np.asarray(out), [[8.0, 0.0]])
        t.close()


class TestTieredTable:
    """Hybrid storage: hot KvTable + cold file tier.

    Reference behaviors: hybrid_embedding TableManager + StorageTable."""

    def _tiered(self, tmp_path, dim=4):
        from dlrover_tpu.sparse.kv_table import KvTable
        from dlrover_tpu.sparse.tiered import FileColdStore, TieredTable

        table = KvTable("tier_t", dim=dim, n_slots=0)
        cold = FileColdStore(str(tmp_path / "cold"), width=dim)
        return TieredTable(table, cold), table, cold

    def test_demote_then_fault_back(self, tmp_path):
        import numpy as np

        tiered, hot, cold = self._tiered(tmp_path)
        keys = np.array([1, 2, 3], dtype=np.int64)
        rows = tiered.gather_or_insert(keys, now_ts=100)
        assert tiered.hot_size == 3 and tiered.cold_size == 0

        # keys 1,2 go stale; key 3 stays warm
        hot.insert([3], rows[2:3], now_ts=500)
        moved = tiered.demote_before_timestamp(400)
        assert moved == 2
        assert tiered.hot_size == 1 and tiered.cold_size == 2
        assert len(tiered) == 3

        # lookup faults the cold rows back with identical values
        back = tiered.gather_or_insert(keys, now_ts=600)
        np.testing.assert_allclose(back, rows, rtol=1e-6)
        assert tiered.cold_size == 0 and tiered.hot_size == 3

    def test_cold_store_survives_restart(self, tmp_path):
        import numpy as np

        from dlrover_tpu.sparse.tiered import FileColdStore

        cold = FileColdStore(str(tmp_path / "c"), width=2)
        cold.put(
            np.array([7, 9]),
            np.array([[1.0, 2.0], [3.0, 4.0]], np.float32),
            np.array([5, 6], np.uint32),
            np.array([10, 11], np.uint32),
        )
        cold2 = FileColdStore(str(tmp_path / "c"), width=2)
        found, values, freqs, ts = cold2.get(np.array([9, 8]))
        assert found.tolist() == [True, False]
        np.testing.assert_allclose(values[0], [3.0, 4.0])
        assert freqs[0] == 6 and ts[0] == 11

    def test_new_keys_skip_cold_lookup(self, tmp_path):
        import numpy as np

        tiered, _, cold = self._tiered(tmp_path)
        out = tiered.gather_or_zeros(np.array([42], dtype=np.int64))
        np.testing.assert_array_equal(out, np.zeros((1, 4), np.float32))
        assert tiered.cold_size == 0

    def test_width_mismatch_rejected_and_slots_roundtrip(self, tmp_path):
        import numpy as np

        from dlrover_tpu.sparse.kv_table import GroupAdam, KvTable
        from dlrover_tpu.sparse.tiered import FileColdStore, TieredTable

        table = KvTable("tier_slots", dim=4, n_slots=2)  # Adam m+v slots
        with pytest.raises(ValueError, match="width"):
            TieredTable(table, FileColdStore(str(tmp_path / "bad"), width=4))
        tiered = TieredTable(
            table, FileColdStore(str(tmp_path / "ok"), width=table.width)
        )
        keys = np.array([11, 12], dtype=np.int64)
        tiered.gather_or_insert(keys, now_ts=10)
        opt = GroupAdam(lr=0.1)
        opt.apply(table, keys, np.ones((2, 4), np.float32), now_ts=20)
        rows_before = table.gather_full(keys)
        assert tiered.demote_before_timestamp(100) == 2
        back = tiered.gather_or_insert(keys, now_ts=200)
        # full rows (values + optimizer slots) survive the round-trip
        np.testing.assert_allclose(
            np.asarray(table.gather_full(keys)),
            np.asarray(rows_before),
            rtol=1e-6,
        )
        assert back.shape == (2, 4)

    def test_demotion_sweep_touches_o_stale_rows(self, tmp_path):
        """Regression pin for the incremental sweep: row I/O is bounded
        by the STALE candidate count — a big warm working set costs the
        sweep nothing, and the hot table is never exported."""
        import numpy as np

        tiered, hot, _ = self._tiered(tmp_path)
        stale_keys = np.arange(1000, 1005, dtype=np.int64)
        warm_keys = np.arange(100, dtype=np.int64)
        tiered.gather_or_insert(stale_keys, now_ts=10)
        tiered.gather_or_insert(warm_keys, now_ts=1000)

        counts = {"gather_full": 0, "timestamp": 0, "frequency": 0}
        orig = {m: getattr(hot, m) for m in counts}

        def _wrap(m):
            def inner(keys):
                counts[m] += int(np.asarray(keys).size)
                return orig[m](keys)
            return inner

        for m in counts:
            setattr(hot, m, _wrap(m))

        def _no_export(*a, **kw):
            raise AssertionError("sweep must not export the hot table")

        hot.export = _no_export
        try:
            moved = tiered.demote_before_timestamp(500)
        finally:
            for m in counts:
                setattr(hot, m, orig[m])
            del hot.export
        assert moved == 5
        # O(stale), not O(hot): only the 5 stale candidates were read
        assert counts["gather_full"] == 5
        assert counts["timestamp"] == 5
        assert counts["frequency"] == 5
        assert tiered.hot_size == 100 and tiered.cold_size == 5

    def test_frozen_gather_promotions_stay_demotable(self, tmp_path):
        """Rows promoted by a FROZEN gather (the serve path — it never
        records touches itself) must re-enter the touch ring at
        promotion time, or they could never be demoted again."""
        import numpy as np

        tiered, _, _ = self._tiered(tmp_path)
        keys = np.array([1, 2, 3], dtype=np.int64)
        rows = tiered.gather_or_insert(keys, now_ts=100)
        assert tiered.demote_before_timestamp(200) == 3
        # frozen fault-back (gather_or_zeros = pull_frozen path); the
        # promotion stamps wall-clock time, so sweep with a max threshold
        back = tiered.gather_or_zeros(keys)
        np.testing.assert_allclose(back, rows, rtol=1e-6)
        assert tiered.cold_size == 0
        # the promotion recorded the touch: a later sweep spills again
        assert tiered.demote_before_timestamp(2**60) == 3
        assert tiered.cold_size == 3

    def test_frozen_gather_retries_past_racing_demotion(self, tmp_path):
        """Read/demote race regression: a sweep running cold.put →
        hot.delete between the residency check and the lock-free hot
        read must not turn a trained row into zeros — the reader sees
        the demotion epoch moved and retries the fault path."""
        import numpy as np

        tiered, hot, _ = self._tiered(tmp_path)
        keys = np.array([1, 2, 3], dtype=np.int64)
        rows = tiered.gather_or_insert(keys, now_ts=100)

        orig = hot.gather_or_zeros
        fired = []

        def racing_gather(k):
            # the sweep lands exactly in the race window (first read
            # only): after _fault_in saw the keys resident, before the
            # hot gather runs
            if not fired:
                fired.append(True)
                assert tiered.demote_before_timestamp(2**60) == 3
            return orig(k)

        hot.gather_or_zeros = racing_gather
        try:
            out = tiered.gather_or_zeros(keys)
        finally:
            hot.gather_or_zeros = orig
        np.testing.assert_allclose(out, rows, rtol=1e-6)
        assert tiered.cold_size == 0  # retried fault promoted them back

    def test_train_gather_fences_out_racing_demotion(self, tmp_path):
        """gather_or_insert's insert side effect can't be fixed by a
        retry, so it takes the begin_update fence: the touch lands
        before the hot read, and a sweep racing in re-reads the ring
        post-claim, sees the keys fresh, and backs off — no fresh init
        row is inserted over (and later demoted over) the real row."""
        import numpy as np

        tiered, hot, _ = self._tiered(tmp_path)
        keys = np.array([1, 2, 3], dtype=np.int64)
        rows = tiered.gather_or_insert(keys, now_ts=100)

        orig = hot.gather_or_insert
        moved = []

        def racing_gather(k, now_ts=None):
            # sweep cutoff beats the keys' OLD touches (100) but not the
            # in-flight read's touch (300): pre-fence it spilled the
            # rows and the gather re-inserted fresh init rows over them
            if not moved:
                moved.append(tiered.demote_before_timestamp(200))
            return orig(k, now_ts=now_ts)

        hot.gather_or_insert = racing_gather
        try:
            out = tiered.gather_or_insert(keys, now_ts=300)
        finally:
            hot.gather_or_insert = orig
        assert moved == [0]  # the sweep saw fresh touches and backed off
        np.testing.assert_allclose(out, rows, rtol=1e-6)
        assert tiered.cold_size == 0

    def test_concurrent_faults_promote_each_key_once(self, tmp_path):
        """Promotion-epoch concurrency: N threads faulting the same cold
        keys cost ONE cold read per key — the first fault claims, racers
        wait on the claimant's event — and every thread sees the exact
        row values."""
        import threading

        import numpy as np

        tiered, _, cold = self._tiered(tmp_path)
        keys = np.arange(20, dtype=np.int64)
        rows = tiered.gather_or_insert(keys, now_ts=10)
        assert tiered.demote_before_timestamp(100) == 20

        hit_keys = []
        orig_get = cold.get

        def counting_get(k):
            res = orig_get(k)
            # a racer whose residency check lost to a finished promotion
            # may re-read an already-moved key and find nothing; the
            # invariant is one SUCCESSFUL cold row fetch per key
            hit_keys.extend(np.asarray(k)[res[0]].tolist())
            return res

        cold.get = counting_get
        results, errors = [None] * 8, []
        barrier = threading.Barrier(8)

        def fault(i):
            try:
                barrier.wait()
                results[i] = tiered.gather_or_zeros(keys)
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [
            threading.Thread(target=fault, args=(i,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        cold.get = orig_get
        assert not errors
        # each key's row left the cold tier exactly once across threads
        assert sorted(hit_keys) == keys.tolist()
        assert tiered.stats.snapshot()["cold_faults"] == 20
        for r in results:
            np.testing.assert_allclose(r, rows, rtol=1e-6)

    def test_int8_codec_roundtrip_and_resident_bytes(self, tmp_path):
        """codec="int8" cuts resident payload bytes ~4x vs f32 with
        block-scaled quantization error, survives restart (the on-disk
        base stays f32), and the default f32 codec stays exact."""
        import numpy as np

        from dlrover_tpu.sparse.tiered import FileColdStore

        width = 32
        rng = np.random.default_rng(0)
        keys = np.arange(64, dtype=np.int64)
        rows = rng.normal(size=(64, width)).astype(np.float32)
        freqs = np.arange(64, dtype=np.uint32)
        ts = np.arange(100, 164, dtype=np.uint32)

        f32 = FileColdStore(str(tmp_path / "f32"), width=width)
        f32.put(keys, rows, freqs, ts)
        _, exact, _, _ = f32.get(keys)
        np.testing.assert_array_equal(exact, rows)  # f32 path is exact

        q8 = FileColdStore(
            str(tmp_path / "q8"), width=width, codec="int8"
        )
        q8.put(keys, rows, freqs, ts)
        found, deq, gfr, gts = q8.get(keys)
        assert found.all()
        np.testing.assert_array_equal(gfr, freqs)
        np.testing.assert_array_equal(gts, ts)
        # block-scaled error bound: one scale step per element
        step = np.abs(rows).max() / 127.0
        assert np.abs(deq - rows).max() <= step + 1e-7
        # the measurable win: int8 payloads hold ~1 byte/elem + scales
        assert q8.resident_bytes < f32.resident_bytes / 2
        # restart replays the f32 WAL/base into the SAME quantized form
        q8.flush()
        q8b = FileColdStore(
            str(tmp_path / "q8"), width=width, codec="int8"
        )
        _, deq2, _, _ = q8b.get(keys)
        np.testing.assert_array_equal(deq2, deq)
        # and an f32 reader loads the int8-written base unchanged
        # (the on-disk format is codec-independent)
        f32b = FileColdStore(str(tmp_path / "q8"), width=width)
        _, deq3, _, _ = f32b.get(keys)
        np.testing.assert_allclose(deq3, deq, atol=step + 1e-7)

    def test_wal_torn_tail_and_compaction(self, tmp_path):
        """Crash-shaped durability: a torn tail record is dropped on
        replay (everything before it applies); hitting ``flush_every``
        compacts the WAL into an atomically-replaced base npz."""
        import os

        import numpy as np

        from dlrover_tpu.sparse.tiered import FileColdStore

        path = str(tmp_path / "c")
        cold = FileColdStore(path, width=2, flush_every=1000)
        k = np.arange(6, dtype=np.int64)
        rows = np.arange(12, dtype=np.float32).reshape(6, 2)
        cold.put(k, rows, np.ones(6, np.uint32), np.ones(6, np.uint32))
        cold.delete(np.array([5], np.int64))
        # no compaction yet: everything lives in the WAL only
        assert not os.path.exists(os.path.join(path, "cold.npz"))
        # simulate a crash mid-append: torn put record (header, no row)
        cold._wal.close()
        with open(os.path.join(path, "wal.log"), "ab") as fh:
            from dlrover_tpu.sparse.tiered import _WAL_HEADER

            fh.write(_WAL_HEADER.pack(b"P", 99, 1, 1) + b"\x00\x00")
        cold2 = FileColdStore(path, width=2, flush_every=2)
        found, vals, _, _ = cold2.get(np.arange(7, dtype=np.int64))
        assert found.tolist() == [True] * 5 + [False, False]  # no 99
        np.testing.assert_array_equal(vals[:5], rows[:5])
        # two mutation batches trigger compaction: base written, WAL cut
        cold2.put(
            np.array([7], np.int64),
            np.full((1, 2), 7.0, np.float32),
            np.array([1], np.uint32),
            np.array([1], np.uint32),
        )
        cold2.put(
            np.array([8], np.int64),
            np.full((1, 2), 8.0, np.float32),
            np.array([1], np.uint32),
            np.array([1], np.uint32),
        )
        assert os.path.exists(os.path.join(path, "cold.npz"))
        assert not os.path.exists(os.path.join(path, "cold_tmp.npz"))
        assert os.path.getsize(os.path.join(path, "wal.log")) == 0
        cold3 = FileColdStore(path, width=2)
        assert len(cold3) == 7
        f3, v3, _, _ = cold3.get(np.array([0, 7, 8], np.int64))
        assert f3.all()
        np.testing.assert_array_equal(v3[1], [7.0, 7.0])

    def test_wal_torn_tail_truncated_before_reappend(self, tmp_path):
        """Double-crash regression: replay must TRUNCATE a torn tail,
        not just skip it — __init__ reopens the log for append, so
        without the truncate new records land after the partial bytes
        and the NEXT replay misparses them (the torn put's row bytes
        swallow the following record: garbage row, silent drops)."""
        import os

        import numpy as np

        from dlrover_tpu.sparse.tiered import FileColdStore, _WAL_HEADER

        path = str(tmp_path / "c")
        cold = FileColdStore(path, width=2, flush_every=1000)
        k = np.arange(4, dtype=np.int64)
        rows = np.arange(8, dtype=np.float32).reshape(4, 2)
        cold.put(k, rows, np.ones(4, np.uint32), np.ones(4, np.uint32))
        cold._wal.close()
        wal = os.path.join(path, "wal.log")
        good_size = os.path.getsize(wal)
        # crash mid-append: torn put record (header + half a row)
        with open(wal, "ab") as fh:
            fh.write(_WAL_HEADER.pack(b"P", 99, 1, 1) + b"\x00\x00")
        # unclean restart 1: good records replay, torn tail cut from disk
        cold2 = FileColdStore(path, width=2, flush_every=1000)
        assert os.path.getsize(wal) == good_size
        cold2.put(
            np.array([7], np.int64),
            np.full((1, 2), 7.0, np.float32),
            np.array([1], np.uint32),
            np.array([1], np.uint32),
        )
        cold2._wal.close()
        # unclean restart 2: the record appended after the crash parses —
        # no garbage row for key 99, nothing silently dropped
        cold3 = FileColdStore(path, width=2, flush_every=1000)
        assert len(cold3) == 5
        found, vals, _, _ = cold3.get(
            np.array([0, 1, 2, 3, 7, 99], np.int64)
        )
        assert found.tolist() == [True] * 5 + [False]
        np.testing.assert_array_equal(vals[:4], rows)
        np.testing.assert_array_equal(vals[4], [7.0, 7.0])
        # corrupt-record tails (bad opcode) truncate the same way
        cold3._wal.close()
        with open(wal, "ab") as fh:
            fh.write(b"XXXX")
        pre = os.path.getsize(wal) - 4
        cold4 = FileColdStore(path, width=2, flush_every=1000)
        assert os.path.getsize(wal) == pre
        assert len(cold4) == 5

    def test_wal_fsync_interval(self, tmp_path):
        """fsync_every syncs the log to disk every N append batches and
        the synced records replay on restart (smoke for the opt-in
        power-loss durability knob)."""
        import numpy as np

        from dlrover_tpu.sparse.tiered import FileColdStore

        cold = FileColdStore(
            str(tmp_path / "c"), width=2, flush_every=1000, fsync_every=1
        )
        cold.put(
            np.array([1], np.int64),
            np.array([[1.0, 2.0]], np.float32),
            np.array([1], np.uint32),
            np.array([1], np.uint32),
        )
        assert cold._unsynced == 0  # batch was synced, counter reset
        cold2 = FileColdStore(str(tmp_path / "c"), width=2)
        found, vals, _, _ = cold2.get(np.array([1], np.int64))
        assert found.all()
        np.testing.assert_array_equal(vals[0], [1.0, 2.0])


class TestLookaheadPrefetcher:
    """sparse/prefetch.py: queue-peeking promotion off the request path."""

    class _Req:
        def __init__(self, keys):
            self.keys = np.asarray(keys, np.int64)

    def _tiered(self, tmp_path, dim=4):
        from dlrover_tpu.sparse.kv_table import KvTable
        from dlrover_tpu.sparse.tiered import FileColdStore, TieredTable

        table = KvTable("pf_t", dim=dim, n_slots=0)
        cold = FileColdStore(str(tmp_path / "cold"), width=dim)
        return TieredTable(table, cold)

    def test_prefetch_promotes_queued_keys(self, tmp_path):
        tiered = self._tiered(tmp_path)
        keys = np.arange(40, dtype=np.int64)
        rows = tiered.gather_or_insert(keys, now_ts=10)
        assert tiered.demote_before_timestamp(100) == 40

        from dlrover_tpu.sparse.prefetch import LookaheadPrefetcher

        queue = [self._Req(keys[i:i + 8]) for i in range(0, 40, 8)]
        pf = LookaheadPrefetcher(
            tiered, lambda n=1: queue[:n], lambda r: r.keys,
            lookahead=8,
        )
        pf.start()
        try:
            pf.notify()
            assert pf.drain(timeout=30.0)
        finally:
            pf.stop()
        snap = tiered.stats.snapshot()
        # everything the peek window exposed was promoted OFF the
        # gather path...
        assert snap["prefetched"] == 40
        assert snap["prefetch_coverage"] == 1.0
        st = pf.stats()
        assert st["keys_promoted"] == 40
        assert st["batches"] >= 1
        # ...so the serve-time gather is all hot hits (fresh gauges to
        # isolate the serve window, as the engine does per publish arm)
        from dlrover_tpu.sparse.tiered import TierStats

        tiered.stats = TierStats()
        back = tiered.gather_or_zeros(keys)
        np.testing.assert_allclose(back, rows, rtol=1e-6)
        snap = tiered.stats.snapshot()
        assert snap["cold_faults"] == 0
        assert snap["hot_hit_rate"] == 1.0

    def test_prefetch_dedups_recent_keys(self, tmp_path):
        tiered = self._tiered(tmp_path)
        keys = np.arange(10, dtype=np.int64)
        tiered.gather_or_insert(keys, now_ts=10)
        assert tiered.demote_before_timestamp(100) == 10

        from dlrover_tpu.sparse.prefetch import LookaheadPrefetcher

        staged = []
        orig_prefetch = tiered.prefetch

        def counting_prefetch(k, now_ts=None):
            staged.extend(np.asarray(k).tolist())
            return orig_prefetch(k, now_ts)

        tiered.prefetch = counting_prefetch
        queue = [self._Req(keys)]
        pf = LookaheadPrefetcher(
            tiered, lambda n=1: queue[:n], lambda r: r.keys,
            lookahead=4,
        )
        pf.start()
        try:
            # drain() is true of a worker that has not run yet: wait for
            # its first batch, or a loaded host stops it before it peeks
            deadline = time.monotonic() + 30.0
            while pf.batches == 0 and time.monotonic() < deadline:
                pf.notify()
                time.sleep(0.001)
            for _ in range(5):  # the same head peeked repeatedly
                pf.notify()
                assert pf.drain(timeout=30.0)
        finally:
            pf.stop()
            tiered.prefetch = orig_prefetch
        # recent-key dedup: repeated peeks of the same head stage each
        # key once, not once per wakeup
        assert sorted(staged) == keys.tolist()
