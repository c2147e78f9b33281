"""Unified telemetry: lifecycle tracing + metric registry + exposition,
cross-host trace stitching, live perf attribution, flight recorder.

Dependency-free (stdlib + numpy).  See docs/OBSERVABILITY.md for the
metric catalog, the stitching/skew-alignment method, and how to open an
exported trace in Perfetto.
"""

from lmrs_tpu.obs.anatomy import (
    CLASSES,
    NULL_ANATOMY,
    SEGMENTS,
    NullAnatomy,
    StepAnatomy,
    anatomy_enabled,
    maybe_anatomy,
    merge_anatomy,
    rtt_resample_s,
    slow_step_ms,
)
from lmrs_tpu.obs.flight import (
    POSTMORTEM_SCHEMA,
    dump_postmortem,
    postmortem_dir,
    validate_postmortem_file,
)
from lmrs_tpu.obs.ledger import DEFAULT_TENANT, CostLedger, merge_usage
from lmrs_tpu.obs.metrics import (
    DEFAULT_LATENCY_BUCKETS_S,
    MS_LATENCY_BUCKETS,
    POW2_TOKEN_BUCKETS,
    RATIO_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    add_label_to_exposition,
    log_buckets,
    merge_expositions,
)
from lmrs_tpu.obs.perf import (
    DispatchAttribution,
    profile_capture_active,
    start_profile_capture,
)
from lmrs_tpu.obs.slo import (
    DEFAULT_SPECS,
    SLOEngine,
    SLOSpec,
    specs_from_env,
    state_rank,
    worst_state,
)
from lmrs_tpu.obs.trace import (
    PID_ENGINE,
    PID_PIPELINE,
    PID_STITCH,
    TID_SCHED,
    TRACE_TRACK_PREFIX,
    Tracer,
    disable_tracing,
    enable_tracing,
    export_current,
    get_tracer,
    new_trace_id,
    req_tid,
    span,
    stitch_traces,
    stitched_chains,
    validate_trace_events,
    validate_trace_file,
)

__all__ = [
    "CLASSES", "NULL_ANATOMY", "SEGMENTS", "NullAnatomy", "StepAnatomy",
    "anatomy_enabled", "maybe_anatomy", "merge_anatomy", "rtt_resample_s",
    "slow_step_ms",
    "DEFAULT_LATENCY_BUCKETS_S", "MS_LATENCY_BUCKETS", "POW2_TOKEN_BUCKETS",
    "RATIO_BUCKETS",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "add_label_to_exposition", "log_buckets", "merge_expositions",
    "DispatchAttribution", "profile_capture_active", "start_profile_capture",
    "POSTMORTEM_SCHEMA", "dump_postmortem", "postmortem_dir",
    "validate_postmortem_file",
    "DEFAULT_TENANT", "CostLedger", "merge_usage",
    "DEFAULT_SPECS", "SLOEngine", "SLOSpec", "specs_from_env",
    "state_rank", "worst_state",
    "PID_ENGINE", "PID_PIPELINE", "PID_STITCH", "TID_SCHED",
    "TRACE_TRACK_PREFIX", "Tracer",
    "disable_tracing", "enable_tracing", "export_current", "get_tracer",
    "new_trace_id", "req_tid", "span", "stitch_traces", "stitched_chains",
    "validate_trace_events", "validate_trace_file",
]
