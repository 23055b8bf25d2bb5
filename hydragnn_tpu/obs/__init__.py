"""Unified telemetry: metrics registry, flight recorder, span tracing,
compile monitoring — the one observability layer train, serve, the
loader, and the benches all emit into (docs/OBSERVABILITY.md).

Pieces:
  - :mod:`~hydragnn_tpu.obs.registry` — counters / gauges / windowed
    histograms in a rank-aware store; null-object disabled path.
  - :mod:`~hydragnn_tpu.obs.flight` — crash-safe append-only JSONL
    event log per run (manifest, epochs, compiles, errors, summary).
  - :mod:`~hydragnn_tpu.obs.spans` — data-wait / host-dispatch /
    device-execute step-time decomposition with a sampled sync window.
  - :mod:`~hydragnn_tpu.obs.compile_monitor` — ``jax.monitoring``-based
    compile counting ("no recompile after step 1", now assertable).
  - :mod:`~hydragnn_tpu.obs.export` — tensorboard / JSONL / Prometheus
    textfile exporters over the registry.
  - :mod:`~hydragnn_tpu.obs.trace` — per-request / per-step distributed
    traces (trace IDs, spans, Chrome/Perfetto export).
  - :mod:`~hydragnn_tpu.obs.triggers` — declarative SLO rules over the
    live registry; firing captures a bounded profiler trace into a
    self-contained incident bundle.
  - :mod:`~hydragnn_tpu.obs.podview` — pod-visibility plane: per-host
    flight shards, cross-host merge/stitching, and the rank-0
    SkewMonitor behind the ``step_skew`` / ``host_stall`` triggers.

Global gate: ``HYDRAGNN_TELEMETRY=0`` disables the process-global
registry and everything the train loop wires up; each piece is also
individually constructible as enabled/disabled.
"""

from hydragnn_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    reset_registry,
    telemetry_enabled,
)
from hydragnn_tpu.obs.flight import (
    FAULT_KINDS,
    SCHEMA_VERSION,
    SUPPORTED_SCHEMA_VERSIONS,
    FlightRecorder,
    epoch_phases,
    flight_record_warnings,
    read_flight_record,
    validate_flight_record,
)
from hydragnn_tpu.obs.introspect import (
    HardwareLedger,
    HeadDiagnostics,
    collect_head_series,
    cost_analysis,
    device_memory_stats,
    flag_anomalies,
    make_diagnostics_step,
    peak_flops,
    per_head_error_metrics,
)
from hydragnn_tpu.obs.podview import (
    MergedFlights,
    SkewMonitor,
    collective_attribution,
    host_artifact_path,
    host_epoch_table,
    host_flight_path,
    host_identity,
    list_host_shards,
    load_skew_tolerance,
    merge_host_flights,
    podview_enabled,
    resolve_run_id,
    straggler_spec,
    validate_podview_report,
)
from hydragnn_tpu.obs.spans import StepSpans, span
from hydragnn_tpu.obs.trace import (
    RequestTrace,
    Tracer,
    export_flight_chrome,
    flight_to_chrome,
    new_trace_id,
    trace_enabled,
)
from hydragnn_tpu.obs.triggers import (
    RULE_KINDS,
    IncidentRecorder,
    TriggerEngine,
    TriggerRule,
    TriggerVerdict,
    list_incidents,
    validate_incident_bundle,
    validate_incident_manifest,
)
from hydragnn_tpu.obs.compile_monitor import (
    BACKEND_COMPILE_EVENT,
    CompileMonitor,
)
from hydragnn_tpu.obs.drift import (
    DriftMonitor,
    P2Quantile,
    RunningMoments,
    build_reference,
    load_reference,
    psi,
    validate_drift_report,
)
from hydragnn_tpu.obs.spool import (
    RequestSpool,
    list_shards,
    read_spool,
    validate_spool_manifest,
)
from hydragnn_tpu.obs.export import (
    prometheus_name,
    registry_to_jsonl,
    registry_to_prometheus,
    registry_to_prometheus_text,
    registry_to_tensorboard,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "get_registry",
    "reset_registry",
    "telemetry_enabled",
    "FAULT_KINDS",
    "SCHEMA_VERSION",
    "SUPPORTED_SCHEMA_VERSIONS",
    "FlightRecorder",
    "epoch_phases",
    "flight_record_warnings",
    "read_flight_record",
    "validate_flight_record",
    "HardwareLedger",
    "HeadDiagnostics",
    "collect_head_series",
    "cost_analysis",
    "device_memory_stats",
    "flag_anomalies",
    "make_diagnostics_step",
    "peak_flops",
    "per_head_error_metrics",
    "MergedFlights",
    "SkewMonitor",
    "collective_attribution",
    "host_artifact_path",
    "host_epoch_table",
    "host_flight_path",
    "host_identity",
    "list_host_shards",
    "load_skew_tolerance",
    "merge_host_flights",
    "podview_enabled",
    "resolve_run_id",
    "straggler_spec",
    "validate_podview_report",
    "StepSpans",
    "span",
    "RequestTrace",
    "Tracer",
    "export_flight_chrome",
    "flight_to_chrome",
    "new_trace_id",
    "trace_enabled",
    "RULE_KINDS",
    "IncidentRecorder",
    "TriggerEngine",
    "TriggerRule",
    "TriggerVerdict",
    "list_incidents",
    "validate_incident_bundle",
    "validate_incident_manifest",
    "BACKEND_COMPILE_EVENT",
    "CompileMonitor",
    "DriftMonitor",
    "P2Quantile",
    "RunningMoments",
    "build_reference",
    "load_reference",
    "psi",
    "validate_drift_report",
    "RequestSpool",
    "list_shards",
    "read_spool",
    "validate_spool_manifest",
    "prometheus_name",
    "registry_to_jsonl",
    "registry_to_prometheus",
    "registry_to_prometheus_text",
    "registry_to_tensorboard",
]
