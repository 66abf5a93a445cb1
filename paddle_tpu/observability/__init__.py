"""paddle_tpu.observability — framework-wide telemetry.

Three process-local pillars, wired through every hot subsystem (ISSUE 3
tentpole):

- ``MetricsRegistry`` (metrics.py): process-global labelled counters /
  gauges / histograms with snapshot(), reset(), Prometheus text exposition
  and JSONL export. Fed by framework/autograd (dispatch + trace-cache
  counters), distributed/grad_comm + collective (collectives issued, wire
  bytes per codec, bucket fill ratios), robustness/checkpoint (save/load
  duration histograms, retry counts) and robustness/watchdog (NaN-guard
  trips, heartbeats).
- ``EventLog`` (events.py): append-only structured JSONL with severity,
  monotonic + wall timestamps and rank tagging. The global log collects
  checkpoint commits, NaN trips and watchdog stalls;
  ``FLAGS_enable_rpc_profiler`` additionally streams per-collective events
  into it (the reference's RPC profiler, reinterpreted).
- ``host_spans`` (host_spans.py): the program's span stream in the
  registry. The always-installed span sink — every profiler.RecordEvent
  end adds to ``host_span_seconds_total{span}`` and
  ``host_span_calls_total{span}``, so set-up phases and step spans can be
  read after the fact (the flight recorder's ring evicts); the caller's
  time between top-level spans, ``host_outside_seconds_total{before}``;
  and JAX's compile events booked to the span that caused them,
  ``jit_compile_seconds_total{phase, span}``.
- ``StepTimer`` (step_timer.py): per-step data / forward / backward /
  optimizer / comm / checkpoint breakdown assembled from nested
  RecordEvent spans.

And the distributed plane on top (ISSUE 6 tentpole):

- ``MetricsAggregator`` (aggregate.py): cross-rank merge of per-rank
  snapshots under per-kind reduction rules (counters sum, gauges
  min/max/mean, histogram buckets add), exchanged through the guarded
  collective layer so PR-4 timeouts/retries/chaos apply; surfaces the
  per-rank step-time spread as the ``step_time_skew`` straggler gauge.
- ``FlightRecorder`` (flight_recorder.py): always-on lock-light bounded
  ring of recent spans, events, and collective-lane launches; dumped to a
  postmortem JSON from every escalation path (HangDetector, NanGuard,
  CollectiveTimeoutError exhaustion, ReplicaGuard).
- ``memory`` (memory.py): live-tensor bytes on the eager path, XLA
  ``memory_analysis`` peaks keyed by trace-cache entry on the compiled
  path.
- ``TelemetryServer`` (exposition.py): stdlib HTTP endpoint per rank —
  /metrics (Prometheus text), /snapshot (rank-0 aggregate), /events,
  /flightrecorder; ``FLAGS_telemetry_http_port`` turns it on job-wide.
- ``Tracer`` / ``TraceStore`` (tracing.py, ISSUE 18): request-scoped
  tracing — a TraceContext minted per ServeRequest (and per train step)
  whose lifecycle spans land in a bounded store served at /traces and in
  the flight-recorder ring; latency histograms carry the trace id as an
  OpenMetrics exemplar, linking a scraped p99 bucket to a concrete trace.

Reference anchor: platform/profiler/'s HostTracer event tree gives the span
stream; this layer adds the aggregated, exportable telemetry the reference
kept in ad-hoc VLOG lines.
"""
from __future__ import annotations

from .aggregate import (  # noqa: F401
    MetricsAggregator, merge_payloads, merge_typed_snapshots, note_step_time,
)
from .events import (  # noqa: F401
    SEVERITIES, EventLog, add_event_sink, get_event_log, remove_event_sink,
    set_event_log,
)
from .exposition import (  # noqa: F401
    TelemetryServer, get_telemetry_server, parse_prometheus_text,
    start_exposition, stop_exposition,
)
from .flight_recorder import (  # noqa: F401
    FlightRecorder, configure_flight_recorder, dump_flight_recorder,
    get_flight_recorder,
)
from .metrics import (  # noqa: F401
    DEFAULT_BUCKETS, Counter, Gauge, Histogram, MetricsRegistry, get_registry,
)
from .step_timer import (  # noqa: F401
    PHASES, StepTimer, format_breakdown, phase_of,
)
from .tracing import (  # noqa: F401
    Span, TraceContext, TraceStore, Tracer, get_tracer, tracing_enabled,
)

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "get_registry",
    "DEFAULT_BUCKETS",
    "EventLog", "SEVERITIES", "get_event_log", "set_event_log",
    "add_event_sink", "remove_event_sink",
    "StepTimer", "PHASES", "phase_of", "format_breakdown",
    "rpc_profiler_enabled", "enable_rpc_event_log",
    "MetricsAggregator", "merge_payloads", "merge_typed_snapshots",
    "note_step_time",
    "FlightRecorder", "get_flight_recorder", "dump_flight_recorder",
    "configure_flight_recorder",
    "TelemetryServer", "start_exposition", "stop_exposition",
    "get_telemetry_server", "parse_prometheus_text",
    "TraceContext", "Span", "TraceStore", "Tracer", "get_tracer",
    "tracing_enabled",
]

# ---------------------------------------------------------------------------
# FLAGS_enable_rpc_profiler compat wiring (framework/flags.py): the reference
# flag turned on per-RPC span collection in the fluid PS path. Here the
# distributed/ps layers have no RPC layer of their own (XLA/PJRT own the
# wire), so the flag is reinterpreted: when on, distributed + ps paths emit
# per-collective / per-push events into the global EventLog.
# ---------------------------------------------------------------------------

_rpc_profiler = {"enabled": False}


def rpc_profiler_enabled() -> bool:
    return _rpc_profiler["enabled"]


def enable_rpc_event_log(enabled: bool = True):
    """Toggle per-collective event logging (FLAGS_enable_rpc_profiler)."""
    _rpc_profiler["enabled"] = bool(enabled)
    return get_event_log()
