"""The program's own records of a run, as the per-layer readers read them.

Two records live in the run's process:

- the spans of `repro.obs.tracing`, which the traced pass collects inside
  `capture()` around one decomposition: `cp_als.decompose` and everything
  under it (`cp_als.init`, `cp_als.fit`, `cp_als.readback`, ...);
- `repro.obs.default_registry`, into which the layout build counts whether
  or not tracing is on: the histogram `layout.chunk_seconds` (each
  `chunk_tensor` call) and the counters `layout.kernel_nonzeros` and
  `layout.kernel_slots` (the Pallas layout's T·P slots).

Both describe a run on the chip: a traced decomposition in which no
operation ran on a device (a rehearsal on the CPU) reads as no record, as
the device trace's own metrics do.  A program that lacks a span or a
counter reads as none, and its reader prints nothing.
"""
from __future__ import annotations

__all__ = ["decomposition_spans", "on_chip", "registry_metric", "seconds"]


def on_chip(obs) -> bool:
    """True when the run traced a decomposition that ran on a device."""
    return obs.trace is not None and obs.trace.busy_s > 0


def decomposition_spans(obs) -> list:
    """The `SpanRecord`s of the last `cp_als.decompose` the program
    recorded (the traced one) and of every span nested in it."""
    from repro.obs.tracing import get_tracer

    if not on_chip(obs):
        return []
    spans = get_tracer().spans()
    roots = [s for s in spans if s.name == "cp_als.decompose"]
    if not roots:
        return []
    kept, ids = [], {roots[-1].span_id}
    # A child closes before its parent, so it precedes it in the buffer.
    for s in reversed(spans):
        if s.span_id in ids or s.parent_id in ids:
            ids.add(s.span_id)
            kept.append(s)
    return kept[::-1]


def seconds(spans: list, name: str) -> list[float]:
    """The durations of the spans called `name`."""
    return [s.duration for s in spans if s.name == name]


def registry_metric(obs, name: str) -> dict | None:
    """`repro.obs.default_registry`'s rendering of metric `name`, or None."""
    from repro.obs.metrics import default_registry

    if not on_chip(obs):
        return None
    return default_registry.snapshot().get(name)
