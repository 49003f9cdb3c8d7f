"""Unified observability plane (docs/observability.md).

``repro.obs.trace`` — structured tracing: spans / instants / counters
recorded in Chrome trace-event JSON (loadable in Perfetto or
``chrome://tracing``) on *dual clocks*: a deterministic virtual tick
timeline plus wall-clock annotations, so traces from seeded runs are
reproducible byte-for-byte once the wall fields are stripped.  The
default recorder is a no-op — instrumented hot paths cost nothing when
tracing is off.

``repro.obs.metrics`` — a counter / gauge / histogram registry with
JSONL export, and the nearest-rank ``percentile`` helper every latency
aggregation in the repo shares.

``repro.obs.analyze`` / ``repro.obs.report`` — the analysis layer over
recorded traces: step-time attribution (compute / comm / snapshot /
stall), comm overlap efficiency vs the modeled bounds, pipeline-bubble
accounting, serve latency extraction, and the
``python -m repro.obs.report trace.json`` CLI.

``repro.obs.slo`` — declarative serve objectives (``ttft_p99<8``) with
multi-window burn-rate alerting, wired into the serve engine and
autoscaler.
"""
from repro.obs.analyze import (analyze, overlap_efficiency,
                               pipeline_accounting, request_latencies,
                               serve_summary, step_attribution)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               percentile)
from repro.obs.slo import Objective, SLOMonitor, evaluate_trace
from repro.obs.trace import (NullRecorder, TraceRecorder, emit_sched_trace,
                             get_recorder, load_trace, set_recorder,
                             strip_wall, tracing, validate_trace)

__all__ = [
    "TraceRecorder", "NullRecorder", "get_recorder", "set_recorder",
    "tracing", "load_trace", "strip_wall", "validate_trace",
    "emit_sched_trace",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "percentile",
    "analyze", "step_attribution", "overlap_efficiency",
    "pipeline_accounting", "request_latencies", "serve_summary",
    "Objective", "SLOMonitor", "evaluate_trace",
]
