"""Continuous-batching recommendation serving over the sparse tier.

The recommender scenario from the reference system's original
production domain: DeepFM predictions (models/deepfm.py) served from
the tiered embedding stack (sparse/tiered.py) behind the SAME
scheduler/server loop the LLM path uses. A request is one example —
``n_fields`` categorical ids (the scheduler ``prompt``) plus a dense
feature vector — and the engine drains the queue in batches, runs one
jitted forward, and resolves each future with the predicted CTR.

The async lookup pipeline: a ``LookaheadPrefetcher``
(sparse/prefetch.py) peeks the scheduler queue (``Scheduler.peek``),
extracts the keyed embedding ids of the next requests, and promotes
cold rows hot off-thread — so the step-time ``pull_frozen`` gather is
an in-RAM hit instead of a synchronous cold-store fault in the request
path. ``SparseServingRecord`` telemetry carries the tier hit-rate,
prefetch-coverage and promotion-latency gauges next to the usual
scheduler latency histograms.

Elastic PS resharding: when the model's collection is a
``DistributedEmbedding``, ``SparseServingServer.resync_ps`` adopts the
master's versioned server set at a step boundary (``paused()``), so
the two-phase checksummed-wire key migration runs with no step in
flight and queued requests keep their original admission tickets —
a PS scale-out mid-traffic loses zero rows and zero requests.
"""

import json
import time
from typing import List, Optional

import numpy as np

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.models.deepfm import _field_key
from dlrover_tpu.serving.scheduler import LATENCY_PHASES, Request
from dlrover_tpu.serving.server import GenerationServer
from dlrover_tpu.sparse.prefetch import LookaheadPrefetcher

logger = get_logger(__name__)


def extract_request_keys(req: Request) -> np.ndarray:
    """Keyed embedding ids one queued request will gather: the
    (field, id) keying of models/deepfm.py, over the request's prompt
    (its categorical ids). Both DeepFM tables share the keying, so one
    extraction feeds every table's prefetch."""
    ids = np.asarray(req.prompt, np.int64)
    return np.stack(
        [_field_key(i, ids[i]) for i in range(ids.size)]
    ).reshape(-1)


class _FanoutPrefetchTarget:
    """One prefetch surface over the model's tiered tables (DeepFM has
    two — ``emb`` and ``wide`` — keyed identically)."""

    def __init__(self, tables):
        self.tables = list(tables)

    def prefetch(self, keys, now_ts=None) -> int:
        return sum(t.prefetch(keys, now_ts) for t in self.tables)


def tier_model_tables(model, cold_dir: str, *, flush_every: int = 256,
                      codec: str = "f32") -> List:
    """Wrap every KvTable in ``model.coll`` with a TieredTable over a
    FileColdStore under ``cold_dir/<table>`` — the one-call setup for
    tiered serving (tests and drills). Returns the TieredTables."""
    import os

    from dlrover_tpu.sparse.tiered import FileColdStore, TieredTable

    out = []
    for name, table in list(model.coll.tables.items()):
        cold = FileColdStore(
            os.path.join(cold_dir, name), width=table.width,
            flush_every=flush_every, codec=codec,
        )
        tiered = TieredTable(table, cold)
        model.coll.tables[name] = tiered
        out.append(tiered)
    return out


def _tiered_tables(model) -> List:
    """The model collection's TieredTable values (empty when the
    collection is flat KvTables or a DistributedEmbedding ring)."""
    tables = getattr(getattr(model, "coll", None), "tables", None)
    if not isinstance(tables, dict):
        return []
    return [t for t in tables.values() if hasattr(t, "prefetch")]


def merged_tier_snapshot(tables) -> dict:
    """Sum TierStats across tables and recompute the derived rates."""
    snap = {
        "gathered": 0, "hot_hits": 0, "cold_faults": 0, "prefetched": 0,
        "inserted": 0, "demoted": 0, "hot_rows": 0, "cold_rows": 0,
        "promote_latency_avg_ms": 0.0,
    }
    lat_num = lat_den = 0.0
    for t in tables:
        s = t.stats.snapshot()
        for k in ("gathered", "hot_hits", "cold_faults", "prefetched",
                  "inserted", "demoted"):
            snap[k] += int(s[k])
        snap["hot_rows"] += t.hot_size
        snap["cold_rows"] += t.cold_size
        lat_num += s["promote_time_s"]
        lat_den += s["promote_batches"]
    looked_up = max(1, snap["gathered"])
    promoted = snap["cold_faults"] + snap["prefetched"]
    snap["hot_hit_rate"] = snap["hot_hits"] / looked_up
    snap["prefetch_coverage"] = (
        snap["prefetched"] / promoted if promoted else 1.0
    )
    snap["promote_latency_avg_ms"] = (
        1e3 * lat_num / lat_den if lat_den else 0.0
    )
    return snap


class SparseServingEngine:
    """DeepFM inference engine satisfying the GenerationServer engine
    contract (step/stats/max_len/role/draining/observability_snapshot)."""

    def __init__(self, model, cfg, scheduler, *, max_batch: int = 32,
                 lookahead: int = 4):
        self.model = model
        self.cfg = cfg
        self.scheduler = scheduler
        self.max_batch = max(1, int(max_batch))
        self.lookahead = int(lookahead)
        # admission bound the base server checks: a prompt is exactly
        # n_fields ids and every request asks for one "token" (score)
        self.max_len = int(cfg.n_fields) + 1
        self.role = "recommend"
        self.draining = False
        self.tiered = _tiered_tables(model)
        self._completed = 0
        self._t0 = 0.0

    @staticmethod
    def _can_admit(req: Request) -> bool:
        # producers attach dense_x right after scheduler.submit returns;
        # a request popped in that microsecond window would have no
        # features, so the head waits (lookahead lets others run)
        return getattr(req, "dense_x", None) is not None

    def step(self) -> bool:
        if self.draining:
            return False
        batch: List[Request] = []
        while len(batch) < self.max_batch:
            req = self.scheduler.pop_next(
                can_admit=self._can_admit, lookahead=self.lookahead
            )
            if req is None:
                break
            self.scheduler.record_admitted(req)
            batch.append(req)
        if not batch:
            return False
        if not self._t0:
            self._t0 = time.monotonic()
        cat = np.stack(
            [np.asarray(r.prompt, np.int64) for r in batch]
        )
        dense = np.stack(
            [np.asarray(r.dense_x, np.float32) for r in batch]
        )
        try:
            scores = self.model.predict(cat, dense)
        except Exception as exc:  # fail the batch, keep the loop alive
            logger.exception("sparse predict batch of %d failed",
                             len(batch))
            for r in batch:
                self.scheduler.fail(r, exc)
            return True
        for r, s in zip(batch, scores):
            self.scheduler.record_first_token(r)
            self.scheduler.complete(r, [float(s)])
        self._completed += len(batch)
        return True

    def stats(self) -> dict:
        dt = (time.monotonic() - self._t0) if self._t0 else 0.0
        qps = self._completed / dt if dt > 0 else 0.0
        out = {
            "active_slots": 0,
            "free_pages": 0,
            "tokens_per_s": qps,
            "qps": qps,
            "completed": self._completed,
            "role": self.role,
        }
        out.update(merged_tier_snapshot(self.tiered))
        return out

    def observability_snapshot(self) -> dict:
        return self.stats()


class SparseServingServer(GenerationServer):
    """Recommendation replica front end: the GenerationServer loop
    (pause protocol, drain, pacing) around a ``SparseServingEngine``,
    publishing ``SparseServingRecord`` and owning the lookahead
    prefetcher and the PS-resync path."""

    def __init__(self, model, cfg, *, prefetch: bool = True,
                 prefetch_lookahead: int = 8, **kw):
        super().__init__(model, cfg, **kw)
        self.ps_reshards = 0
        self.last_reshard_s = 0.0
        self.prefetcher: Optional[LookaheadPrefetcher] = None
        if prefetch and self.engine.tiered:
            self.prefetcher = LookaheadPrefetcher(
                _FanoutPrefetchTarget(self.engine.tiered),
                self.scheduler.peek,
                extract_request_keys,
                lookahead=prefetch_lookahead,
            )

    def _build_engine(self, params, cfg, scheduler, **engine_kw):
        return SparseServingEngine(params, cfg, scheduler, **engine_kw)

    # ---- lifecycle -------------------------------------------------------

    def start(self) -> "SparseServingServer":
        super().start()
        if self.prefetcher is not None:
            self.prefetcher.start()
        return self

    def stop(self, timeout: float = 30.0) -> None:
        if self.prefetcher is not None:
            self.prefetcher.stop()
        super().stop(timeout)

    # ---- intake ----------------------------------------------------------

    def submit(self, cat_ids, dense_x, *, priority: int = 0,
               deadline_s: Optional[float] = None) -> Request:
        """One example in: ``cat_ids`` [n_fields] int64 categorical
        ids, ``dense_x`` [n_dense] float features. The future resolves
        with ``[score]``."""
        cat = np.asarray(cat_ids, np.int64).reshape(-1)
        if cat.size != self.engine.cfg.n_fields:
            raise ValueError(
                f"expected {self.engine.cfg.n_fields} categorical ids, "
                f"got {cat.size}"
            )
        dense = np.asarray(dense_x, np.float32).reshape(-1)
        if dense.size != self.engine.cfg.n_dense:
            raise ValueError(
                f"expected {self.engine.cfg.n_dense} dense features, "
                f"got {dense.size}"
            )
        req = self.scheduler.submit(
            cat.tolist(), 1, priority=priority, deadline_s=deadline_s
        )
        req.dense_x = dense
        if self.prefetcher is not None:
            self.prefetcher.notify()
        return req

    def predict(self, cat_ids, dense_x, timeout: float = 30.0) -> float:
        """Blocking convenience: submit one example, wait for its score."""
        return self.submit(cat_ids, dense_x).future.result(timeout)[0]

    # ---- elastic PS ------------------------------------------------------

    def resync_ps(self, client) -> bool:
        """Adopt the master's current PS server set at a step boundary.

        Runs the versioned reroute (sparse/server.py sync_with_master →
        two-phase migration over the checksummed wire) under
        ``paused()``: no step is mid-gather while owners change, queued
        requests keep their original tickets, and new submissions keep
        landing in the scheduler throughout — the engine just resumes
        against the wider ring. Returns True when the routing changed."""
        from dlrover_tpu.sparse.server import sync_with_master

        demb = self.engine.model.coll
        if not hasattr(demb, "set_servers"):
            raise ValueError(
                "resync_ps needs a DistributedEmbedding-backed model"
            )
        t0 = time.monotonic()
        with self.paused():
            changed = sync_with_master(demb, client)
        if changed:
            self.ps_reshards += 1
            self.last_reshard_s = time.monotonic() - t0
            logger.info(
                "PS reshard %d adopted version %d in %.3fs",
                self.ps_reshards, demb.version, self.last_reshard_s,
            )
        return changed

    # ---- telemetry -------------------------------------------------------

    def _publish(self):
        from dlrover_tpu.observability.telemetry import SparseServingRecord

        stats = self.engine.stats()
        sched = self.scheduler
        hists = sched.histograms()
        lat = hists["e2e"].summary()
        demb = getattr(self.engine.model, "coll", None)
        rec = SparseServingRecord(
            replica=self.replica,
            queue_depth=sched.queue_depth(),
            admitted=sched.admitted,
            completed=sched.completed,
            re_admitted=sched.re_admitted,
            shed=sched.shed,
            rejected=sched.rejected,
            timed_out=sched.timed_out,
            qps=round(float(stats["qps"]), 3),
            p50_ms=round(lat["p50"], 3),
            p99_ms=round(lat["p99"], 3),
            queue_wait_p99_ms=round(
                hists["queue_wait"].percentile(99.0), 3
            ),
            hot_hit_rate=round(float(stats["hot_hit_rate"]), 6),
            prefetch_coverage=round(
                float(stats["prefetch_coverage"]), 6
            ),
            promote_latency_avg_ms=round(
                float(stats["promote_latency_avg_ms"]), 3
            ),
            cold_faults=int(stats["cold_faults"]),
            prefetched=int(stats["prefetched"]),
            demoted=int(stats["demoted"]),
            hot_rows=int(stats["hot_rows"]),
            cold_rows=int(stats["cold_rows"]),
            ps_version=int(getattr(demb, "version", 0) or 0),
            ps_reshards=self.ps_reshards,
            last_reshard_s=round(self.last_reshard_s, 3),
            hists=json.dumps(
                {k: hists[k].to_dict() for k in LATENCY_PHASES},
                sort_keys=True,
            ),
        )
        if sched.hub is not None:
            sched.hub.publish(rec)
        return rec
