"""Worker-side observability: step timer, sampled device profile, loss-spike,
numerics, and the unified telemetry bus + trace spans joining them.

TPU-native analog of the reference's xpu_timer (atorch/dev/xpu_timer —
LD_PRELOAD CUDA hook timing GEMMs clustered by B/M/N/K and NCCL collectives,
exported via Prometheus) and of atorch/atorch/utils/{prof.py AProfiler,
loss_spike_utils.py, numberic_checker.py}.

On TPU there is nothing to LD_PRELOAD: every kernel is compiled by XLA from
a traced program, so step timing comes from host wall-clock around the
dispatched step, and what the device did inside it from a sampled XLA
profiler trace that the program reduces itself (``runtime_timer``), by
kernel name and by phase of the step.

The point tools publish into one stream: producers emit typed records
onto the :class:`~dlrover_tpu.observability.telemetry.TelemetryHub` and
trace spans through :mod:`~dlrover_tpu.observability.tracing`, so one
merged timeline covers train step → checkpoint → failover across the
worker, agent and master processes.
"""

from dlrover_tpu.observability.histogram import (
    LatencyHistogram,
    merge_histograms,
)
from dlrover_tpu.observability.loss_spike import LossSpikeDetector
from dlrover_tpu.observability.numeric import (
    GradSanitizer,
    NumericChecker,
    check_finite,
    sanitize_grads,
)
from dlrover_tpu.observability.profiler import (
    StepClock,
    reset_step_clock,
    step_clock,
)
from dlrover_tpu.observability.telemetry import (
    CheckpointRecord,
    ElasticEvent,
    JsonlSink,
    KernelSample,
    MasterSink,
    MetricsSink,
    NumericEvent,
    OverlapDriftRecord,
    PlanRecord,
    ResourceRecord,
    StepRecord,
    StragglerRecord,
    TelemetryHub,
    configure_hub,
    from_json,
    get_hub,
    record_types,
    reset_hub,
)
from dlrover_tpu.observability.tracing import (
    NullTracer,
    Span,
    Tracer,
    configure_tracer,
    get_tracer,
    counters,
    merge_trace_dir,
    reset_tracer,
    self_seconds,
    set_counter,
    span_intervals,
)

__all__ = [
    "StepClock",
    "step_clock",
    "reset_step_clock",
    "LossSpikeDetector",
    "NumericChecker",
    "GradSanitizer",
    "check_finite",
    "sanitize_grads",
    # telemetry bus
    "TelemetryHub",
    "configure_hub",
    "get_hub",
    "reset_hub",
    "from_json",
    "record_types",
    "JsonlSink",
    "MetricsSink",
    "MasterSink",
    "StepRecord",
    "CheckpointRecord",
    "ElasticEvent",
    "NumericEvent",
    "KernelSample",
    "PlanRecord",
    "OverlapDriftRecord",
    "StragglerRecord",
    "ResourceRecord",
    # latency histograms
    "LatencyHistogram",
    "merge_histograms",
    # tracing
    "Tracer",
    "NullTracer",
    "Span",
    "configure_tracer",
    "get_tracer",
    "reset_tracer",
    "merge_trace_dir",
    "span_intervals",
    "self_seconds",
    # counter table
    "set_counter",
    "counters",
]
