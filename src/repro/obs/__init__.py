"""repro.obs — tracing + metrics for the tune/decompose/serve stack.

Three pieces (see docs/observability.md for the span catalog, metric
inventory, and the Perfetto how-to):

- `tracing` — a process-global, thread-aware span tracer that is a true
  no-op when disabled (one attribute check on the hot path).  Enable with
  `enable_tracing()`, the `capture()` scope, or ``REPRO_TRACE=1`` /
  ``REPRO_TRACE_PATH=trace.jsonl`` in the environment.
- `metrics` — counters and histograms; histograms use fixed log-spaced
  buckets so p50/p95/p99 come without storing samples, and registry
  snapshots are consistent cuts.
- `export` — trace JSONL read/write, Chrome trace-event JSON for Perfetto,
  and the tables behind ``python -m repro.obs summarize``.

The instrumented surface: `autotune_engine` emits per-candidate probe
spans and a decision span, `cp_als`/`cp_als_batched` emit per-iteration
and per-mode spans (the same measurement `CPResult.iter_times` reports),
`DecomposeService` records queue-wait/dispatch/request-latency histograms
(p50/p99 surfaced in `ServeStats`), and `sweep.runner` wraps each cell in
a fingerprint-tagged span.

Never emit spans or metrics inside jitted code — the `trace-in-jit`
analysis rule (docs/static-analysis.md#trace-in-jit) enforces it.
"""
from __future__ import annotations

from .export import (
    read_jsonl,
    span_kind_summary,
    summarize_text,
    to_chrome_trace,
    tune_decision_summary,
    validate_spans,
    write_chrome_trace,
    write_jsonl,
)
from .metrics import (
    Counter,
    Histogram,
    MetricsRegistry,
    default_histogram_bounds,
    default_registry,
)
from .tracing import (
    TRACE_ENV,
    TRACE_PATH_ENV,
    SpanRecord,
    Tracer,
    capture,
    disable_tracing,
    enable_tracing,
    get_tracer,
    record_span,
    span,
    traced,
    tracing_enabled,
)

__all__ = [
    "TRACE_ENV",
    "TRACE_PATH_ENV",
    "Counter",
    "Histogram",
    "MetricsRegistry",
    "SpanRecord",
    "Tracer",
    "capture",
    "default_histogram_bounds",
    "default_registry",
    "disable_tracing",
    "enable_tracing",
    "get_tracer",
    "read_jsonl",
    "record_span",
    "span",
    "span_kind_summary",
    "summarize_text",
    "to_chrome_trace",
    "traced",
    "tracing_enabled",
    "tune_decision_summary",
    "validate_spans",
    "write_chrome_trace",
    "write_jsonl",
]
