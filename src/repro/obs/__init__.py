"""Unified telemetry layer: metrics registry, phase spans, JSONL events.

    from repro.obs import Registry

    reg = Registry()
    with reg.span("search"):
        with reg.span("dispatch"):
            ...                       # -> histogram "search/dispatch"
    reg.counter("search.queries").inc(64)
    reg.gauge("serve.queue_depth").set(3)
    reg.snapshot()                    # one nested, JSON-serializable dict

Consumed by ``repro.api.OverlapIndex`` (per-phase search/ingest/maintain
spans + per-island node-access counters, exposed via ``.metrics()``) and
``repro.serve.ServeEngine`` (latency histograms + queue/slot gauges).
See README.md in this directory for metric names and overhead notes.

Adjacent modules: ``repro.obs.trace`` (per-request trace propagation +
``Trace.reconstruct`` over the JSONL events), ``repro.obs.attribution``
(contributing/wasted visit classification behind ``OverlapIndex.explain``),
``repro.obs.export`` (Prometheus text rendering + the
``python -m repro.obs.export`` CLI).
"""
from repro.obs.events import EventLog, events_path_from_env
from repro.obs.metrics import Counter, Gauge, Histogram, Registry
from repro.obs.trace import (
    SpanNode,
    Trace,
    TraceContext,
    TraceSampler,
    current_trace,
    new_trace,
    use_trace,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "EventLog",
    "events_path_from_env",
    "SpanNode",
    "Trace",
    "TraceContext",
    "TraceSampler",
    "current_trace",
    "new_trace",
    "use_trace",
]
