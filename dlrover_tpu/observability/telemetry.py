"""Unified telemetry bus: typed records, pluggable sinks, one stream.

The observability layer grew as disconnected point tools (a step timer,
the runtime sampler, loss-spike/numeric checks, GoodputTracker) with nothing consuming them at runtime.  This module
is the substrate that joins them: producers publish small, typed,
JSON-serializable records into a :class:`TelemetryHub`; consumers
(JSONL flight-recorder files, the Prometheus surface in
``master/job_metrics.py``, master reporting over the wire, the
diagnosis manager) attach as sinks.

Contracts:

* **Lossless wire format.**  ``record.to_json()`` /
  ``from_json(line)`` round-trip every registered record exactly
  (pinned by the tier-1 schema lint) — the same envelope discipline as
  ``common/messages.py``, so master-side code can rehydrate a record a
  worker serialized.
* **Zero-cost when off.**  ``get_hub()`` returns a module-pinned
  ``_NullHub`` unless telemetry is configured; producers guard with
  ``if hub.enabled:`` so on the hot path a disabled hub costs one
  attribute load — no record construction, no publish, no allocation
  (pinned by the tier-1 overhead guard).
* **Sinks never break training.**  A sink raising is logged once and
  detached; the publisher never sees the exception.
"""

import dataclasses
import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple, Type

from dlrover_tpu.common.constants import GraftEnv
from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

# ---- record registry ------------------------------------------------------

_RECORD_TYPES: Dict[str, type] = {}


def _to_json(self) -> str:
    return json.dumps(
        {"r": type(self).__name__, "d": dataclasses.asdict(self)},
        sort_keys=True,
    )


def telemetry_record(cls):
    """Class decorator: dataclass + registry entry + ``to_json``."""
    cls = dataclasses.dataclass(cls)
    cls.to_json = _to_json
    _RECORD_TYPES[cls.__name__] = cls
    return cls


def from_json(line: str):
    """Rehydrate any registered record from its ``to_json`` line."""
    obj = json.loads(line)
    cls = _RECORD_TYPES[obj["r"]]
    return cls(**obj["d"])


def record_types() -> Dict[str, type]:
    """Registered name → class map (schema lint iterates this)."""
    return dict(_RECORD_TYPES)


# ---- record types ---------------------------------------------------------
# All fields are JSON scalars (str/int/float/bool) or plain dicts so
# asdict → json round-trips losslessly.  ``ts`` is seconds since epoch,
# stamped by the hub at publish when left 0.


@telemetry_record
class StepRecord:
    """One optimizer step as seen by the trainer."""

    step: int = 0
    loss: float = 0.0
    step_time_s: float = 0.0
    tokens_per_s: float = 0.0
    accum: int = 1
    ts: float = 0.0


@telemetry_record
class CheckpointRecord:
    """One save/restore action at any tier of the checkpoint stack."""

    kind: str = ""  # save_memory | persist | emergency | restore_* ...
    step: int = -1
    seconds: float = 0.0
    nbytes: int = 0
    ok: bool = True
    tier: str = ""  # memory | replica | storage
    # seconds by phase of this action, "plan=0.012,d2h_wait=31.4,..."
    # (``format_phases`` / ``parse_phases``): plan, lock_wait,
    # shm_alloc, d2h_wait, shm_copy for a memory save; restore_map,
    # read, h2d, device_wait for a restore; "" where there is one phase
    phases: str = ""
    ts: float = 0.0


@telemetry_record
class ElasticEvent:
    """A failover / membership phase transition."""

    kind: str = ""  # detect | rendezvous | mesh_replan | restore |
    #                 first_step | node_down | worker_exit ...
    node_id: int = -1
    rdzv_round: int = -1
    restart: int = -1
    seconds: float = 0.0
    detail: str = ""
    ts: float = 0.0


@telemetry_record
class NumericEvent:
    """A numeric-health incident (loss spike, non-finite grads, ...)."""

    kind: str = ""
    step: int = -1
    value: float = 0.0
    detail: str = ""
    ts: float = 0.0


@telemetry_record
class KernelSample:
    """One op from a sampled runtime-profiler step breakdown.

    ``block`` is the number of train steps the trace covered: 1 for the
    classic per-step loop, K when the profiled dispatch was a fused
    K-step block (the µs then span the whole block, not one step)."""

    step: int = -1
    op: str = ""
    us: float = 0.0
    share: float = 0.0
    block: int = 1
    ts: float = 0.0


@telemetry_record
class PlanRecord:
    """Planning numbers for a run, published before it starts so that
    tuners can compare plan with reality. Nothing in the repository
    produces one at present (ROADMAP D16)."""

    config: str = ""
    suggested_bucket_mb: float = 0.0
    planned_exposed_us: float = 0.0
    planned_hidden_us: float = 0.0
    assumed_ici_gbps: float = 0.0
    update_sharding_reason: str = ""
    # mean step wall time expected at this shape — the watchdog's
    # baseline for step_time_regression (0 = no plan available)
    planned_step_time_s: float = 0.0
    ts: float = 0.0


@telemetry_record
class OverlapDriftRecord:
    """Planned exposed-collective µs vs measured (from the sampled
    device trace) — the signal ``config_tuner``/``brain`` consume."""

    step: int = -1
    planned_exposed_us: float = 0.0
    measured_collective_us: float = 0.0
    drift_us: float = 0.0
    drift_frac: float = 0.0
    ts: float = 0.0


@telemetry_record
class StragglerRecord:
    """A worker lagging the per-worker step watermark front."""

    node_id: int = -1
    step: int = 0
    max_step: int = 0
    lag_steps: int = 0
    ratio: float = 0.0
    ts: float = 0.0


@telemetry_record
class ResourceRecord:
    """Per-node host usage, plus the device half where the reporter
    holds the chips: a worker's report names them (``tpu_type``,
    ``local_chips``) and its HBM figures are readings; the agent's
    host-only report leaves all four empty, which says nothing about
    HBM — least of all that it is free."""

    node_id: int = -1
    cpu_percent: float = 0.0
    mem_mb: float = 0.0
    hbm_mb: float = 0.0
    hbm_peak_mb: float = 0.0
    tpu_type: str = ""
    local_chips: int = 0
    ts: float = 0.0


@telemetry_record
class AnomalyRecord:
    """One classified training anomaly from the host-side watchdog.

    ``kind`` is one of observability.watchdog.ANOMALY_KINDS
    (nan_grads | loss_spike | fp8_saturation | step_time_regression |
    straggler) or watchdog.SERVING_ANOMALY_KINDS (slo_breach |
    ttft_regression | spec_accept_collapse | shed_storm |
    migration_fallback).  ``capture`` is the path of the
    triggered-capture artifact when the rate limiter granted one, else
    "".  ``replica`` names the serving replica for serving kinds
    ("" for training anomalies)."""

    kind: str = ""
    step: int = -1
    node_id: int = -1
    value: float = 0.0
    detail: str = ""
    capture: str = ""
    replica: str = ""
    ts: float = 0.0


@telemetry_record
class HealthSummary:
    """Master-side cross-host correlation of worker AnomalyRecords.

    ``verdict`` encodes the attribution rule: one rank reporting →
    suspect data/hardware on that host; every rank reporting → suspect
    model/config.  ``ranks`` is a comma-joined sorted rank list."""

    kind: str = ""
    first_step: int = -1
    ranks: str = ""
    n_ranks: int = 0
    world: int = 0
    verdict: str = ""
    detail: str = ""
    ts: float = 0.0


@telemetry_record
class ServingRecord:
    """Periodic serving-replica snapshot (serving/scheduler.py publish).

    Latencies are end-to-end request milliseconds (submit → complete)
    over the scheduler's sliding window; ``tokens_per_s`` is the
    engine's decode throughput since its first step. ``re_admitted``
    counts failover re-admissions this replica ABSORBED from dead
    peers (serving/replica.py ReplicaRouter).

    Speculative decoding (engine ``spec_k > 0``): ``draft_tokens`` /
    ``accepted_tokens`` are lifetime counts of drafts proposed to and
    accepted by the verify step; ``spec_accept_rate`` is their ratio
    (0 with speculation off). Recordings from builds that predate
    these fields replay fine — ``from_json`` fills missing fields from
    the dataclass defaults.

    Migration robustness (serving/migration.py): ``migrated_in`` /
    ``migrated_out`` are lifetime counts of requests this engine
    imported/exported as live KV pages; ``shed`` counts queued new
    admissions failed with a retry-after hint to protect a migration
    under page pressure.

    Phase latencies (observability/histogram.py): ``ttft_*`` is
    time-to-first-token (submit → first emitted token), ``tpot_*`` is
    time-per-output-token (mean inter-token ms within a request),
    ``queue_wait_p99_ms`` is enqueue → engine admission.  ``hists`` is
    the JSON-encoded envelope of all the per-phase histograms
    (``scheduler.LATENCY_PHASES`` → LatencyHistogram.to_dict()) —
    a *string* field so the record stays scalar-only on the wire; the
    router/master parse it to merge fleet percentiles from counts
    rather than averaging per-replica percentiles.

    Disaggregated serving (serving/disagg.py): ``role`` is this
    replica's pool ("prefill" | "decode" | "unified");
    ``handoffs_in`` / ``handoffs_out`` are lifetime counts of
    prefill→decode streaming handoffs this engine received/shipped,
    ``handoff_bytes`` the wire bytes they moved, ``handoff_ms_p99``
    the receiving-side first-fragment→commit latency. Recordings from
    builds predating the split replay with the defaults (unified, 0).

    Drop accounting (goodput vs offered load): ``rejected`` counts
    admission failures (queue at capacity + oversize requests),
    ``timed_out`` counts per-request deadline expiries, ``poisoned``
    counts requests failed for invalid sampling parameters; together
    with ``shed`` every dropped request is in exactly one counter."""

    replica: str = ""
    active_slots: int = 0
    queue_depth: int = 0
    admitted: int = 0
    completed: int = 0
    re_admitted: int = 0
    tokens_per_s: float = 0.0
    p50_ms: float = 0.0
    p99_ms: float = 0.0
    draft_tokens: int = 0
    accepted_tokens: int = 0
    spec_accept_rate: float = 0.0
    shed: int = 0
    migrated_in: int = 0
    migrated_out: int = 0
    ttft_p50_ms: float = 0.0
    ttft_p99_ms: float = 0.0
    tpot_p50_ms: float = 0.0
    tpot_p99_ms: float = 0.0
    queue_wait_p99_ms: float = 0.0
    rejected: int = 0
    timed_out: int = 0
    poisoned: int = 0
    # prefix sharing (serving/prefix.py): hit rate over sharing-on
    # admissions, prompt tokens whose prefill was skipped, live radix
    # index size in pages, and resident-bytes dedup (slot cells per
    # unique physical page). Defaults replay pre-sharing recordings.
    prefix_hit_rate: float = 0.0
    prefill_tokens_saved: int = 0
    trie_pages: int = 0
    dedup_ratio: float = 1.0
    role: str = "unified"
    handoffs_in: int = 0
    handoffs_out: int = 0
    handoff_bytes: int = 0
    handoff_ms_p99: float = 0.0
    hists: str = ""
    ts: float = 0.0


@telemetry_record
class ScaleDecisionRecord:
    """One serving-autoscaler decision (master/serving_autoscaler.py).

    ``direction`` is "out" (a warm replica joined ``role``'s pool) or
    "in" (the least-loaded member drained via live migration and
    detached); ``signal`` names the gate that drove it (slo_breach |
    ttft_regression | out_of_pages | queue_depth | shed_storm | clear |
    planned), with ``value`` the measured reading against ``target``.
    ``reaction_s`` is the breach-edge → decision-applied latency;
    ``version`` is the master's serving-scale directive version (0 when
    the scaler versioned locally). ``replica`` names the joiner
    (scale-out) or the drained victim (scale-in). Recordings that
    predate autoscaling simply contain no lines of this type — the
    healthcheck replay treats absence as "no decisions"."""

    role: str = "unified"
    direction: str = ""
    signal: str = ""
    value: float = 0.0
    target: float = 0.0
    n_before: int = 0
    n_after: int = 0
    version: int = 0
    reaction_s: float = 0.0
    replica: str = ""
    reason: str = ""
    ts: float = 0.0


@telemetry_record
class SparseServingRecord:
    """Periodic sparse-serving snapshot (serving/sparse_engine.py).

    The recommendation analog of ``ServingRecord``: one line per
    publish interval from a replica serving DeepFM predictions over the
    tiered embedding tier. ``qps`` is completed requests per second
    since the engine's first step; latency percentiles are the same
    scheduler histograms the LLM path uses (``hists`` carries the full
    per-phase envelope for fleet merges).

    Tier gauges (sparse/tiered.py TierStats): ``hot_hit_rate`` is the
    fraction of gathered keys already resident in the hot KvTable,
    ``prefetch_coverage`` the fraction of cold promotions done by the
    lookahead prefetcher instead of synchronously in the request path
    (1.0 when nothing was cold), ``promote_latency_avg_ms`` the mean
    cold→hot batch promotion latency, ``cold_faults`` / ``prefetched``
    / ``demoted`` lifetime key counts, ``hot_rows`` / ``cold_rows``
    current tier occupancy.

    PS resharding (sparse/server.py + master/elastic_ps.py):
    ``ps_version`` is the last master server-set version this replica
    adopted, ``ps_reshards`` how many reshard migrations it executed,
    ``last_reshard_s`` the most recent pause→resync→resume wall time
    (the recovery-seconds half of the reshard drill's acceptance bar).
    Recordings from builds that predate this type simply contain no
    lines of it — healthcheck replay treats absence as "no sparse
    serving"."""

    replica: str = ""
    queue_depth: int = 0
    admitted: int = 0
    completed: int = 0
    re_admitted: int = 0
    shed: int = 0
    rejected: int = 0
    timed_out: int = 0
    qps: float = 0.0
    p50_ms: float = 0.0
    p99_ms: float = 0.0
    queue_wait_p99_ms: float = 0.0
    hot_hit_rate: float = 0.0
    prefetch_coverage: float = 0.0
    promote_latency_avg_ms: float = 0.0
    cold_faults: int = 0
    prefetched: int = 0
    demoted: int = 0
    hot_rows: int = 0
    cold_rows: int = 0
    ps_version: int = 0
    ps_reshards: int = 0
    last_reshard_s: float = 0.0
    hists: str = ""
    ts: float = 0.0


# ---- sinks ----------------------------------------------------------------


class JsonlSink:
    """Append one ``to_json`` line per record (line-buffered, so records
    survive the process dying mid-failover)."""

    def __init__(self, path: str):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._f = open(path, "a", buffering=1)
        self._lock = threading.Lock()

    def emit(self, record) -> None:
        with self._lock:
            self._f.write(record.to_json() + "\n")

    def close(self) -> None:
        with self._lock:
            self._f.close()


# gauge/counter mappings per record type for any collector duck-typing
# inc(name)/set_gauge(name, value) — JobMetricCollector on the master.
_GAUGE_MAP: Dict[str, List[Tuple[str, str]]] = {
    "StepRecord": [
        ("telemetry_step_time_s", "step_time_s"),
        ("telemetry_loss", "loss"),
        ("telemetry_tokens_per_s", "tokens_per_s"),
    ],
    "PlanRecord": [
        ("plan_suggested_bucket_mb", "suggested_bucket_mb"),
        ("plan_exposed_collective_us", "planned_exposed_us"),
        ("plan_hidden_collective_us", "planned_hidden_us"),
    ],
    "OverlapDriftRecord": [
        ("overlap_planned_exposed_us", "planned_exposed_us"),
        ("overlap_measured_collective_us", "measured_collective_us"),
        ("overlap_drift_us", "drift_us"),
        ("overlap_drift_frac", "drift_frac"),
    ],
    "CheckpointRecord": [("ckpt_last_seconds", "seconds")],
    "ResourceRecord": [
        ("hbm_used_mb", "hbm_mb"),
        ("hbm_peak_mb", "hbm_peak_mb"),
    ],
    "StragglerRecord": [("straggler_lag_steps", "lag_steps")],
    "AnomalyRecord": [("anomaly_last_step", "step")],
    "ServingRecord": [
        ("serving_tokens_per_s", "tokens_per_s"),
        ("serving_p50_ms", "p50_ms"),
        ("serving_p99_ms", "p99_ms"),
        ("serving_queue_depth", "queue_depth"),
        ("serving_draft_tokens", "draft_tokens"),
        ("serving_accepted_tokens", "accepted_tokens"),
        ("serving_spec_accept_rate", "spec_accept_rate"),
        ("serving_shed", "shed"),
        ("serving_migrated_in", "migrated_in"),
        ("serving_migrated_out", "migrated_out"),
        ("serving_ttft_p50_ms", "ttft_p50_ms"),
        ("serving_ttft_p99_ms", "ttft_p99_ms"),
        ("serving_tpot_p50_ms", "tpot_p50_ms"),
        ("serving_tpot_p99_ms", "tpot_p99_ms"),
        ("serving_queue_wait_p99_ms", "queue_wait_p99_ms"),
        ("serving_rejected", "rejected"),
        ("serving_timed_out", "timed_out"),
        ("serving_poisoned", "poisoned"),
        ("serving_prefix_hit_rate", "prefix_hit_rate"),
        ("serving_prefill_tokens_saved", "prefill_tokens_saved"),
        ("serving_trie_pages", "trie_pages"),
        ("serving_dedup_ratio", "dedup_ratio"),
        ("serving_handoffs_in", "handoffs_in"),
        ("serving_handoffs_out", "handoffs_out"),
        ("serving_handoff_bytes", "handoff_bytes"),
        ("serving_handoff_ms_p99", "handoff_ms_p99"),
    ],
    "ScaleDecisionRecord": [
        ("autoscale_pool_size", "n_after"),
        ("autoscale_reaction_s", "reaction_s"),
    ],
    "SparseServingRecord": [
        ("sparse_serving_qps", "qps"),
        ("sparse_serving_p50_ms", "p50_ms"),
        ("sparse_serving_p99_ms", "p99_ms"),
        ("sparse_serving_queue_depth", "queue_depth"),
        ("sparse_serving_queue_wait_p99_ms", "queue_wait_p99_ms"),
        ("sparse_hot_hit_rate", "hot_hit_rate"),
        ("sparse_prefetch_coverage", "prefetch_coverage"),
        ("sparse_promote_latency_avg_ms", "promote_latency_avg_ms"),
        ("sparse_cold_faults", "cold_faults"),
        ("sparse_prefetched", "prefetched"),
        ("sparse_demoted", "demoted"),
        ("sparse_hot_rows", "hot_rows"),
        ("sparse_cold_rows", "cold_rows"),
        ("sparse_ps_version", "ps_version"),
        ("sparse_ps_reshards", "ps_reshards"),
        ("sparse_last_reshard_s", "last_reshard_s"),
    ],
    # cluster/brain.py records (registered on brain import)
    "TuningPlan": [
        ("tuning_version", "version"),
        ("tuning_comm_bucket_mb", "comm_bucket_mb"),
        ("tuning_spec_k", "spec_k"),
        ("tuning_prefill_chunk", "prefill_chunk"),
    ],
    "JobMetrics": [
        ("brain_steps_per_sec", "steps_per_sec"),
        ("brain_samples_per_sec", "samples_per_sec"),
        ("brain_hbm_used_bytes", "hbm_used_bytes"),
    ],
}
_COUNTER_MAP: Dict[str, str] = {
    "ElasticEvent": "elastic_events_total",
    "NumericEvent": "numeric_events_total",
    "CheckpointRecord": "ckpt_records_total",
    "StragglerRecord": "straggler_flags_total",
    "AnomalyRecord": "anomaly_records_total",
    "HealthSummary": "health_summaries_total",
    "ServingRecord": "serving_records_total",
    "SparseServingRecord": "sparse_serving_records_total",
    "ScaleDecisionRecord": "scale_decisions_total",
    "TuningPlan": "tuning_plans_total",
    "JobMetrics": "brain_job_metrics_total",
}


class MetricsSink:
    """Project records onto a Prometheus-style collector.

    ``collector`` is duck-typed: anything with ``inc(name)`` and
    ``set_gauge(name, value)``
    (``master.job_metrics.JobMetricCollector``).
    """

    def __init__(self, collector):
        self._c = collector

    def emit(self, record) -> None:
        tname = type(record).__name__
        gauges = _GAUGE_MAP.get(tname, ())
        if tname == "ResourceRecord" and not record.local_chips:
            gauges = ()  # host-only report: no HBM reading to project
        for gauge, attr in gauges:
            self._c.set_gauge(gauge, float(getattr(record, attr)))
        counter = _COUNTER_MAP.get(tname)
        if counter:
            self._c.inc(counter)
        if tname == "ElasticEvent" and record.seconds > 0 and record.kind:
            self._c.set_gauge(f"failover_{record.kind}_s", record.seconds)


class MasterSink:
    """Forward selected record types to the master over the existing
    agent↔master wire (``MasterClient.report_telemetry``).

    Per-step records are excluded by default: the bus must not turn the
    hot path into an RPC-per-step — the speed monitor already gets step
    reports through ``report_global_step``.
    """

    DEFAULT_TYPES = (
        "AnomalyRecord",
        "CheckpointRecord",
        "ElasticEvent",
        "NumericEvent",
        "OverlapDriftRecord",
        "PlanRecord",
        "TuningPlan",
    )

    def __init__(self, client, types: Optional[Tuple[str, ...]] = None):
        self._client = client
        self._types = frozenset(
            types if types is not None else self.DEFAULT_TYPES
        )

    def emit(self, record) -> None:
        if type(record).__name__ in self._types:
            self._client.report_telemetry(record.to_json())


class CallbackSink:
    """Deliver records to a plain callable (diagnosis subscription)."""

    def __init__(self, fn: Callable, types: Optional[Tuple[str, ...]] = None):
        self._fn = fn
        self._types = frozenset(types) if types is not None else None

    def emit(self, record) -> None:
        if self._types is None or type(record).__name__ in self._types:
            self._fn(record)


# ---- hub ------------------------------------------------------------------


class TelemetryHub:
    """Fan records out to attached sinks; a failing sink is detached
    after logging once, never propagated to the producer."""

    enabled = True

    def __init__(self):
        self._sinks: List = []
        self._lock = threading.Lock()

    def add_sink(self, sink) -> None:
        with self._lock:
            self._sinks.append(sink)

    def remove_sink(self, sink) -> None:
        with self._lock:
            if sink in self._sinks:
                self._sinks.remove(sink)

    def subscribe(
        self, fn: Callable, types: Optional[Tuple[str, ...]] = None
    ) -> CallbackSink:
        sink = CallbackSink(fn, types)
        self.add_sink(sink)
        return sink

    def publish(self, record) -> None:
        if not record.ts:
            record.ts = time.time()
        # snapshot under the lock; emit outside it so a slow sink
        # (file write, RPC) never serializes other publishers
        with self._lock:
            sinks = tuple(self._sinks)
        for sink in sinks:
            try:
                sink.emit(record)
            except Exception as e:
                logger.warning(
                    "telemetry sink %s failed (%s); detaching",
                    type(sink).__name__,
                    e,
                )
                self.remove_sink(sink)


def _noop(record) -> None:
    pass


class _NullHub:
    """Disabled hub: ``enabled`` is False and every method is a pinned
    no-op.  Producers guard ``if hub.enabled:`` so records are never
    even constructed on the disabled path."""

    __slots__ = ()
    enabled = False
    publish = staticmethod(_noop)

    def add_sink(self, sink) -> None:
        pass

    def remove_sink(self, sink) -> None:
        pass

    def subscribe(self, fn, types=None):
        return None


_NULL_HUB = _NullHub()
_hub = None
_hub_lock = threading.Lock()


def configure_hub(
    sinks: Optional[List] = None, jsonl_path: Optional[str] = None
):
    """Install the process hub (idempotent: reconfiguring adds sinks)."""
    global _hub
    with _hub_lock:
        if _hub is None or _hub is _NULL_HUB:
            _hub = TelemetryHub()
        for s in sinks or ():
            _hub.add_sink(s)
        if jsonl_path:
            _hub.add_sink(JsonlSink(jsonl_path))
        return _hub


def get_hub():
    """The process hub, or the pinned ``_NullHub`` when telemetry is
    off.  Auto-enables with a JSONL sink when
    ``DLROVER_TPU_TELEMETRY_DIR`` is set (one file per process, role
    from ``DLROVER_TPU_TRACE_ROLE``)."""
    if _hub is not None:
        return _hub
    tdir = os.getenv(GraftEnv.TELEMETRY_DIR)
    if tdir:
        role = os.getenv(GraftEnv.TRACE_ROLE, "proc")
        return configure_hub(
            jsonl_path=os.path.join(
                tdir, f"telemetry-{role}-{os.getpid()}.jsonl"
            )
        )
    return _NULL_HUB


def reset_hub() -> None:
    """Drop the installed hub (tests)."""
    global _hub
    with _hub_lock:
        _hub = None


# ---- producers' helpers ---------------------------------------------------


def format_phases(phases: Dict[str, float]) -> str:
    """Seconds by phase as a record's scalar field."""
    return ",".join(f"{k}={v:.6f}" for k, v in phases.items())


def parse_phases(text: str) -> Dict[str, float]:
    return {
        k: float(v)
        for k, _, v in (part.partition("=") for part in text.split(",") if part)
    }


# HLO opcodes that are collectives (their async ``-start``/``-done``
# halves begin the same way); runtime_timer reads the same tuple
COLLECTIVE_MARKERS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
    "collective-broadcast",
)


def measured_collective_us(breakdown: List) -> float:
    """Sum the measured µs of collective ops in a runtime-timer
    breakdown (list of objects with ``.name`` and ``.total_us``)."""
    total = 0.0
    for op in breakdown:
        name = op.name.lower()
        if any(m in name for m in COLLECTIVE_MARKERS):
            total += op.total_us
    return total


def overlap_drift(
    step: int, planned_exposed_us: float, breakdown: List
) -> OverlapDriftRecord:
    """Planned exposed-collective time vs measured collective time from
    one sampled step.  ``drift_frac`` is relative to the plan (0 when
    nothing was planned — pure-measurement mode)."""
    measured = measured_collective_us(breakdown)
    drift = measured - planned_exposed_us
    frac = drift / planned_exposed_us if planned_exposed_us > 0 else 0.0
    return OverlapDriftRecord(
        step=step,
        planned_exposed_us=float(planned_exposed_us),
        measured_collective_us=float(measured),
        drift_us=float(drift),
        drift_frac=float(frac),
    )
