"""End-to-end request tracing + per-stage latency telemetry.

One request through the stack yields a span tree — HTTP ingress →
preprocess → KV-router decision → (queue wait → prefill | remote
prefill → KV transfer) → decode — correlated by a contextvar-carried
``trace_id`` that also lands in JSONL log lines and rides the wire
across the request plane and the disagg protocol. See
``docs/observability.md``.
"""

from .anatomy import (
    COMPONENTS,
    AnatomyRing,
    RequestAnatomy,
    anatomy_from_flight,
    anatomy_from_spans,
    anatomy_from_timing,
    render_anatomy,
    render_slow,
)
from .context import (
    TraceContext,
    attach,
    current_span_id,
    current_trace,
    current_trace_id,
    detach,
    new_trace,
    wire_headers,
)
from .dispatch import DISPATCH_KINDS, DispatchProfiler
from .fingerprint import (
    FingerprintBuilder,
    WorkloadDriftWatch,
    WorkloadFingerprint,
    drift_score,
    fingerprint_from_spans,
    fingerprint_from_trace,
    load_fingerprint,
    render_fingerprint,
    replay_workload,
)
from .fleet import (
    FleetAggregator,
    FleetView,
    InstanceView,
    TransferLedger,
    get_transfer_ledger,
    parse_prometheus_text,
    render_top,
)
from .flight import (
    FlightRecorder,
    Watchdog,
    dump_all,
    load_dumps,
    render_flight,
)
from .slo import BURN_WINDOWS, SloAttribution, SloConfig, percentile
from .spans import Span, Telemetry, adopt, get_telemetry, span
from .timeline import (
    find_trace,
    list_traces,
    load_spans,
    render_timeline,
    transfer_hops,
)

__all__ = [
    "BURN_WINDOWS",
    "COMPONENTS",
    "DISPATCH_KINDS",
    "AnatomyRing",
    "DispatchProfiler",
    "FingerprintBuilder",
    "FleetAggregator",
    "FleetView",
    "FlightRecorder",
    "InstanceView",
    "RequestAnatomy",
    "SloAttribution",
    "SloConfig",
    "Span",
    "Telemetry",
    "TraceContext",
    "TransferLedger",
    "Watchdog",
    "WorkloadDriftWatch",
    "WorkloadFingerprint",
    "adopt",
    "anatomy_from_flight",
    "anatomy_from_spans",
    "anatomy_from_timing",
    "attach",
    "current_span_id",
    "current_trace",
    "current_trace_id",
    "detach",
    "drift_score",
    "dump_all",
    "find_trace",
    "fingerprint_from_spans",
    "fingerprint_from_trace",
    "get_telemetry",
    "get_transfer_ledger",
    "list_traces",
    "load_dumps",
    "load_fingerprint",
    "load_spans",
    "new_trace",
    "parse_prometheus_text",
    "percentile",
    "render_anatomy",
    "render_fingerprint",
    "render_flight",
    "render_slow",
    "render_timeline",
    "render_top",
    "replay_workload",
    "span",
    "transfer_hops",
    "wire_headers",
]
