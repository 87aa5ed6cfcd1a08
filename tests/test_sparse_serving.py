"""Multi-host sparse serving e2e (VERDICT r2 #7).

Two real KvServer PROCESSES serve the embedding tier over TCP while a
DeepFM trains against them through DistributedEmbedding; mid-run the
server set changes (scale-out, then scale-in) and the HRW rebalance
migrates only the owner-changed keys — values, optimizer slots and
admission state included — without interrupting convergence.

Reference capability: dlrover's elastic TF PS jobs keep training while
PS instances migrate (trainer/tensorflow/failover/tensorflow_failover.py:33);
here the PS role is the sparse tier's KvServer ring.
"""

import multiprocessing as mp
import os
import threading
import time

import numpy as np
import pytest

from dlrover_tpu.models.deepfm import DeepFM, DeepFMConfig
from dlrover_tpu.sparse import GroupAdam
from dlrover_tpu.sparse.embedding import EmbeddingSpec
from dlrover_tpu.sparse.server import (
    DistributedEmbedding,
    KvClient,
    KvServer,
)


def _specs(emb_dim=8):
    return [
        EmbeddingSpec("emb", emb_dim, initializer="normal",
                      init_scale=0.01, seed=3),
        EmbeddingSpec("wide", 1, initializer="zeros"),
    ]


def _server_main(port_q, emb_dim, lr):
    server = KvServer(_specs(emb_dim), optimizer=GroupAdam(lr=lr))
    port_q.put(server.address[1])
    threading.Event().wait()  # park; the parent terminates us


def _spawn_server(ctx, emb_dim=8, lr=5e-3):
    q = ctx.Queue()
    p = ctx.Process(target=_server_main, args=(q, emb_dim, lr), daemon=True)
    p.start()
    port = q.get(timeout=60)
    return p, ("127.0.0.1", port)


@pytest.fixture()
def two_servers():
    ctx = mp.get_context("spawn")
    procs, addrs = [], {}
    for name in ("s0", "s1"):
        p, addr = _spawn_server(ctx)
        procs.append(p)
        addrs[name] = addr
    yield ctx, procs, addrs
    for p in procs:
        if p.is_alive():
            p.terminate()
        p.join(timeout=10)


def _synthetic_ctr(rng, n, cfg):
    cat = rng.integers(0, 50, size=(n, cfg.n_fields))
    dense = rng.normal(size=(n, cfg.n_dense)).astype(np.float32)
    hot = (cat % 7 == 0).sum(axis=1) + dense[:, 0]
    p = 1.0 / (1.0 + np.exp(-(hot - 2.0)))
    labels = (rng.random(n) < p).astype(np.float32)
    return cat.astype(np.int64), dense, labels


def test_lookup_update_over_wire(two_servers):
    """Basic wire ops: pull inserts rows on the OWNING server; push
    updates move the values; routing is disjoint and complete."""
    _, _, addrs = two_servers
    demb = DistributedEmbedding(_specs(), addrs)
    ids = np.arange(100, dtype=np.int64).reshape(10, 10)
    dev, host = demb.pull({"emb": ids})
    rows0 = np.asarray(dev["emb"][0])
    assert rows0.shape == (100, 8)
    # rows landed on both servers, partitioned disjointly
    stats = demb.stats()
    counts = [s["emb"] for s in stats.values()]
    assert sum(counts) == 100 and all(c > 0 for c in counts)
    # a push changes what the next pull returns
    demb.push(host, {"emb": np.ones((100, 8), np.float32)})
    dev2, _ = demb.pull({"emb": ids})
    assert not np.allclose(rows0, np.asarray(dev2["emb"][0]))
    demb.close()


@pytest.mark.slow
def test_deepfm_trains_and_survives_rebalance(two_servers):
    """The headline drive: train -> scale OUT (migrate) -> train ->
    scale IN (migrate back) -> train; convergence must continue and
    migration stay bounded to the HRW-moved share."""
    ctx, procs, addrs = two_servers
    cfg = DeepFMConfig(n_fields=6, n_dense=4, emb_dim=8, mlp_dims=(32,))
    rng = np.random.default_rng(0)
    cat, dense, labels = _synthetic_ctr(rng, 512, cfg)

    model = DeepFM(cfg, optimizer=GroupAdam(lr=5e-3), dense_lr=5e-3)
    model.coll.close()
    demb = DistributedEmbedding(_specs(cfg.emb_dim), addrs)
    model.coll = demb

    first = model.train_step(cat, dense, labels)
    for _ in range(20):
        mid = model.train_step(cat, dense, labels)
    assert mid < first * 0.9, (first, mid)

    total_before = sum(s["emb"] for s in demb.stats().values())

    # ---- scale OUT: add s2; only ~1/3 of keys may move --------------
    p2, addr2 = _spawn_server(ctx)
    procs.append(p2)
    moved = demb.set_servers(dict(addrs, s2=addr2))
    stats = demb.stats()
    assert "s2" in stats and stats["s2"]["emb"] > 0
    assert sum(s["emb"] for s in stats.values()) == total_before
    # bounded migration: HRW moves ~1/3 on 2->3 growth, never most keys
    assert 0 < moved < total_before * 2 * 0.6  # emb + wide tables

    for _ in range(10):
        after_grow = model.train_step(cat, dense, labels)
    # optimizer slots moved with the rows: convergence continues, no
    # re-warmup spike
    assert after_grow < first * 0.9

    # ---- scale IN: drop s0; its keys must migrate before routing ----
    new_set = {"s1": addrs["s1"], "s2": addr2}
    moved_in = demb.set_servers(new_set)
    stats = demb.stats()
    assert sorted(stats) == ["s1", "s2"]
    assert sum(s["emb"] for s in stats.values()) == total_before
    assert moved_in > 0

    for _ in range(10):
        final = model.train_step(cat, dense, labels)
    assert final < first * 0.9

    # inference path over the wire (frozen: no inserts)
    preds = model.predict(cat, dense)
    assert preds.shape == (512,)
    total_after = sum(s["emb"] for s in demb.stats().values())
    assert total_after == total_before
    demb.close()
    model.dense_params = None  # model.close() would close demb twice


@pytest.mark.slow  # tier-1 budget: crash drills live on the slow tier
def test_server_crash_failover_without_migration(two_servers):
    """Unplanned PS death: the dead server cannot export, so workers
    adopt the survivor ring with migrate=False — lookups keep working,
    keys the dead server owned re-initialize on demand
    (gather-or-insert), and training continues. Availability over
    durability for rows not yet checkpointed, matching the elastic-PS
    failover story (TTL'd rows re-learn)."""
    ctx, procs, addrs = two_servers
    cfg = DeepFMConfig(n_fields=6, n_dense=4, emb_dim=8, mlp_dims=(32,))
    rng = np.random.default_rng(1)
    cat, dense, labels = _synthetic_ctr(rng, 256, cfg)

    model = DeepFM(cfg, optimizer=GroupAdam(lr=5e-3), dense_lr=5e-3)
    model.coll.close()
    demb = DistributedEmbedding(_specs(cfg.emb_dim), addrs)
    model.coll = demb

    first = model.train_step(cat, dense, labels)
    for _ in range(10):
        model.train_step(cat, dense, labels)
    s0_rows = demb.stats()["s0"]["emb"]
    assert s0_rows > 0

    # hard-kill s0 (no drain, no export possible)
    procs[0].kill()
    procs[0].join(timeout=10)

    demb.set_servers({"s1": addrs["s1"]}, migrate=False)
    # the survivor still holds its share; the dead server's rows are
    # gone and will re-initialize on first touch
    stats = demb.stats()
    assert sorted(stats) == ["s1"]
    dev, _ = demb.pull({"emb": np.arange(300, dtype=np.int64)})
    assert np.asarray(dev["emb"][0]).shape == (300, cfg.emb_dim)

    # training continues through the loss bump from the lost rows
    for _ in range(15):
        after = model.train_step(cat, dense, labels)
    assert np.isfinite(after)
    assert after < first, (first, after)
    demb.close()
    model.dense_params = None


def test_migration_preserves_row_values(two_servers):
    """Row-level proof: a migrated key's value/freq round-trips exactly
    (the optimizer slab rides along in gather_full width)."""
    _, _, addrs = two_servers
    demb = DistributedEmbedding(_specs(), addrs)
    ids = np.arange(40, dtype=np.int64)
    demb.pull({"emb": ids})  # insert
    demb.push(
        {"emb": ids}, {"emb": np.full((40, 8), 0.25, np.float32)}
    )
    dev, _ = demb.pull({"emb": ids})
    before = np.asarray(dev["emb"][0]).copy()

    # force migration by renaming the ring (new server NAMES re-hash
    # every key even on the same processes)
    moved = demb.set_servers(
        {"a0": addrs["s0"], "a1": addrs["s1"]}
    )
    assert moved > 0
    dev2, _ = demb.pull({"emb": ids})
    np.testing.assert_allclose(
        before, np.asarray(dev2["emb"][0]), atol=1e-6
    )
    demb.close()


def test_sync_with_master_reroutes(two_servers):
    """Trainer-side version poll: when the master's ElasticPsService
    bumps the sparse-tier version, the client resolves addresses from
    the KV store and reroutes (tensorflow_failover.py:33 capability)."""
    from dlrover_tpu.common import messages as msgs
    from dlrover_tpu.sparse.server import register_server, sync_with_master

    ctx, procs, addrs = two_servers

    class FakeClient:
        def __init__(self):
            self.kv = {}
            self.version = 0
            self.servers = []

        def kv_store_set(self, k, v):
            self.kv[k] = v
            return True

        def kv_store_get(self, k):
            return self.kv.get(k, "")

        def get_ps_version(self, version_type="global"):
            return msgs.PsVersionResponse(
                version=self.version, servers=self.servers
            )

    client = FakeClient()
    for name, addr in addrs.items():
        register_server(client, name, addr)
    demb = DistributedEmbedding(_specs(), {"s0": addrs["s0"]})
    demb.pull({"emb": np.arange(30, dtype=np.int64)})
    base_version = demb.version

    # no version change -> no reroute
    assert sync_with_master(demb, client) is False

    # master announces the 2-server set
    client.version = base_version + 1
    client.servers = ["s0", "s1"]
    assert sync_with_master(demb, client) is True
    assert demb.version == base_version + 1
    assert demb.server_names == ["s0", "s1"]
    # rows redistributed across both processes, none lost
    stats = demb.stats()
    assert sum(s["emb"] for s in stats.values()) == 30

    # unknown address defers adoption instead of half-routing
    client.version += 1
    client.servers = ["s0", "s1", "ghost"]
    assert sync_with_master(demb, client) is False
    assert demb.server_names == ["s0", "s1"]
    demb.close()


# ---------------------------------------------------------------------------
# Elastic PS resharding: migration_plan property + mid-traffic drill
# ---------------------------------------------------------------------------


class _MasterPsClient:
    """Master-side surface the trainer/server polls, backed by the REAL
    ElasticPsService: kv-store for addresses (register_server /
    resolve_ring) and get_ps_version for the versioned server set."""

    def __init__(self, svc):
        self.svc = svc
        self.kv = {}

    def kv_store_set(self, k, v):
        self.kv[k] = v
        return True

    def kv_store_get(self, k):
        return self.kv.get(k, "")

    def get_ps_version(self, version_type="global"):
        from dlrover_tpu.common import messages as msgs

        return msgs.PsVersionResponse(
            version=self.svc.get_global_version(),
            servers=self.svc.get_servers(),
        )


def test_migration_plan_elastic_ps_property():
    """Property test over random key sets: for every ElasticPsService
    membership step (the 2→3 scale-out among them), applying
    ``migration_plan`` two-phase (copy all, then delete sources) leaves
    every key routable before AND after with no row lost or duplicated,
    values intact, and unchanged owners untouched."""
    from dlrover_tpu.master.elastic_ps import ElasticPsService
    from dlrover_tpu.sparse.partition import migration_plan, partition_keys

    rng = np.random.default_rng(123)
    svc = ElasticPsService()
    svc.set_servers(["s0", "s1"])
    memberships = [
        ["s0", "s1", "s2"],        # the drill's 2→3 scale-out
        ["s1", "s2"],              # scale-in
        ["s1", "s2", "s3", "s4"],  # double join
        ["s0", "s4"],              # churn: one back, most gone
    ]
    for new_set in memberships:
        keys = np.unique(
            rng.integers(0, 2**62, size=int(rng.integers(50, 400)))
        )
        old_set = svc.get_servers()
        before = partition_keys(keys, old_set)
        # routable BEFORE: the old partition covers every key once
        assert sum(v.size for v in before.values()) == keys.size
        stores = {
            s: {int(k): float(int(k) % 97) for k in ks}
            for s, ks in before.items()
        }

        v0 = svc.get_global_version()
        assert svc.set_servers(new_set) > v0  # membership change bumps
        assert svc.set_servers(new_set) == v0 + 1  # idempotent re-set

        plan = migration_plan(keys, old_set, new_set)
        # two-phase: every copy lands before any source delete (the
        # torn-transfer-atomic shape sparse/server.py migrates with)
        for key, src, dst in plan:
            stores.setdefault(dst, {})[key] = stores[src][key]
        for key, src, dst in plan:
            del stores[src][key]

        after = partition_keys(keys, new_set)
        for s, ks in after.items():
            held = stores.get(s, {})
            # routable AFTER, nothing lost, nothing duplicated
            assert set(held) == {int(k) for k in ks}
            # migrated values rode along exactly
            assert all(held[k] == float(k % 97) for k in held)
        assert (
            sum(len(stores.get(s, {})) for s in new_set) == keys.size
        )
        # servers that left the ring drained completely
        for s in set(old_set) - set(new_set):
            assert not stores[s]
        # bounded migration: HRW never reshuffles most of the keyspace
        # on a grow step (pure adds move ~added/total of the keys)
        if set(old_set) <= set(new_set):
            assert len(plan) < 0.7 * keys.size


@pytest.mark.slow  # serving loop + 3 KvServer processes: slow tier
def test_ps_reshard_drill_mid_traffic(two_servers):
    """Acceptance drill: scale the PS ring 2→3 WHILE a recommendation
    replica serves traffic against it. ``resync_ps`` adopts the
    master's bumped version at a step boundary; afterwards every
    submitted request resolved exactly once (futures), every row is
    still routable with per-table totals conserved (no loss, no
    duplication), and the reshard path + recovery seconds landed in
    the published SparseServingRecord."""
    from dlrover_tpu.master.elastic_ps import ElasticPsService
    from dlrover_tpu.serving.sparse_engine import SparseServingServer
    from dlrover_tpu.sparse.server import register_server

    ctx, procs, addrs = two_servers
    cfg = DeepFMConfig(n_fields=6, n_dense=4, emb_dim=8, mlp_dims=(32,))
    rng = np.random.default_rng(7)
    cat, dense, labels = _synthetic_ctr(rng, 256, cfg)

    model = DeepFM(cfg, optimizer=GroupAdam(lr=5e-3), dense_lr=5e-3)
    model.coll.close()
    demb = DistributedEmbedding(_specs(cfg.emb_dim), addrs)
    model.coll = demb
    for _ in range(3):  # warm rows onto the 2-server ring
        model.train_step(cat, dense, labels)
    totals_before = {}
    for tname in ("emb", "wide"):
        totals_before[tname] = sum(
            s[tname] for s in demb.stats().values()
        )
    assert totals_before["emb"] > 0

    svc = ElasticPsService()
    client = _MasterPsClient(svc)
    for name, addr in addrs.items():
        register_server(client, name, addr)
    svc.set_servers(sorted(addrs))

    srv = SparseServingServer(
        model, cfg, replica="rec-0", max_queue=4096
    ).start()
    futures = []
    stop_feed = threading.Event()

    def feed():
        frng = np.random.default_rng(11)
        while not stop_feed.is_set() and len(futures) < 400:
            i = int(frng.integers(0, cat.shape[0]))
            futures.append(srv.submit(cat[i], dense[i]).future)
            time.sleep(0.001)

    feeder = threading.Thread(target=feed, daemon=True)
    feeder.start()
    time.sleep(0.05)  # requests genuinely in flight before the reshard
    assert futures

    # ---- scale OUT mid-traffic: s2 joins, master bumps the version --
    p2, addr2 = _spawn_server(ctx)
    procs.append(p2)
    register_server(client, "s2", addr2)
    svc.add_server("s2")
    while svc.get_global_version() <= demb.version:
        svc.bump_global_version()
    assert srv.resync_ps(client) is True
    assert demb.server_names == ["s0", "s1", "s2"]

    stop_feed.set()
    feeder.join(timeout=30)
    n_submitted = len(futures)

    # zero lost/duplicated requests: every future resolves exactly once
    scores = [f.result(timeout=60)[0] for f in futures]
    assert len(scores) == n_submitted > 0
    assert all(np.isfinite(s) and 0.0 <= s <= 1.0 for s in scores)

    # zero lost/duplicated rows: per-table totals conserved across the
    # move and the new server owns its HRW share (serving traffic is
    # pull_frozen — it inserts nothing)
    stats = demb.stats()
    assert sorted(stats) == ["s0", "s1", "s2"]
    for tname in ("emb", "wide"):
        assert (
            sum(s[tname] for s in stats.values())
            == totals_before[tname]
        )
    assert stats["s2"]["emb"] > 0

    # reshard path + recovery seconds in telemetry
    rec = srv._publish()
    assert rec.ps_reshards == 1
    assert rec.last_reshard_s > 0.0
    assert rec.ps_version == demb.version
    assert rec.completed == n_submitted
    srv.stop()
    demb.close()
    model.dense_params = None  # model.close() would close demb twice


@pytest.mark.slow  # a serving loop (test_marker_lint's serving rule)
def test_tiered_serving_prefetch_moves_rows_not_values(tmp_path):
    """The recommendation replica over tiered tables whose rows all start
    in the cold tier, the same requests twice: lookahead prefetch off
    (every row faults on the request path), then on (the prefetcher
    promotes the queue's head while the loop is parked). The scores are
    bit-equal: tiers move rows, never values."""
    from dlrover_tpu.serving.sparse_engine import (
        SparseServingServer,
        merged_tier_snapshot,
        tier_model_tables,
    )
    from dlrover_tpu.sparse.tiered import TierStats

    cfg = DeepFMConfig(n_fields=4, n_dense=3, emb_dim=8, mlp_dims=(16,))
    rng = np.random.default_rng(5)
    n = 24
    cat = rng.integers(0, 500, size=(n, cfg.n_fields)).astype(np.int64)
    dense = rng.normal(size=(n, cfg.n_dense)).astype(np.float32)
    labels = (rng.random(n) < 0.3).astype(np.float32)
    model = DeepFM(cfg, optimizer=GroupAdam(lr=5e-3), dense_lr=5e-3)
    try:
        tiered = tier_model_tables(model, str(tmp_path))
        model.train_step(cat, dense, labels)  # creates every row served

        def serve(prefetch):
            for t in tiered:
                t.demote_before_timestamp(2**60)
                t.stats = TierStats()
            assert all(t.hot_size == 0 for t in tiered)
            srv = SparseServingServer(
                model, cfg, replica=f"rec-pf{int(prefetch)}",
                prefetch=prefetch, prefetch_lookahead=n, max_batch=1,
            ).start()
            try:
                with srv.paused():
                    reqs = [srv.submit(cat[i], dense[i]) for i in range(n)]
                    if prefetch:
                        pf = srv.prefetcher
                        deadline = time.monotonic() + 60.0
                        while (
                            pf.keys_promoted == 0
                            and time.monotonic() < deadline
                        ):
                            pf.notify()
                            time.sleep(0.001)
                        assert pf.drain(timeout=60.0)
                scores = [r.future.result(timeout=60)[0] for r in reqs]
            finally:
                srv.stop()
            return np.array(scores, np.float32), merged_tier_snapshot(tiered)

        scores_off, off = serve(False)
        scores_on, on = serve(True)
    finally:
        model.close()
    np.testing.assert_array_equal(scores_on, scores_off)
    assert off["cold_faults"] > 0 and off["prefetched"] == 0
    assert off["prefetch_coverage"] == 0.0
    # the whole queue fitted the lookahead window and was promoted before
    # the loop served its first request
    assert on["prefetched"] > 0 and on["cold_faults"] == 0
    assert on["hot_rows"] == off["hot_rows"] > 0
