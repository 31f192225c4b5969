"""Reduction of a JAX profiler trace to device busy time, top ops and idle gaps.

A trace is read into plain `Event`s (plane, line, name, start and end in ns)
so that the reduction can be checked on a small recorded trace without a
chip.  Device operations are the events of the "XLA Ops" line of the first
"/device:TPU:" plane.  Busy time is the union of their intervals inside the
traced window; an idle gap is a stretch of the window that no operation
covers, labelled by the innermost host span around its midpoint.  Host spans
are the benchmark's own `jax.profiler.TraceAnnotation`s and the program's
spans, given as `Event`s on the trace's clock.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path

__all__ = ["Event", "Summary", "busy_seconds", "device_ops", "load", "summarize"]

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: int
    end_ns: int

    def to_json(self) -> list:
        return [self.plane, self.line, self.name, self.start_ns, self.end_ns]

    @classmethod
    def from_json(cls, row) -> Event:
        return cls(*row)


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    device_ops: list[list]          # [[op name, self seconds], ...], longest first
    idle_gaps: list[list]           # [[host span, seconds], ...], longest first
    op_seconds: dict[str, float]    # every op name's self seconds on the device


def load(log_dir: str | Path) -> list[Event]:
    """Every event of the `.xplane.pb` trace that `jax.profiler` wrote under `log_dir`."""
    from jax.profiler import ProfileData

    paths = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb trace under {log_dir}")
    out = []
    for path in paths:
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                out.extend(Event(plane.name, line.name, ev.name, int(ev.start_ns),
                                 int(ev.start_ns + ev.duration_ns))
                           for ev in line.events)
    return out


def device_ops(events: list[Event]) -> list[Event]:
    """The op events of the first TPU plane, in start order."""
    planes = sorted({e.plane for e in events if e.plane.startswith(DEVICE_PLANE)})
    if not planes:
        return []
    return sorted((e for e in events if e.plane == planes[0] and e.line == OPS_LINE),
                  key=lambda e: e.start_ns)


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _op_name(name: str) -> str:
    """An op's HLO instruction name without its numeric suffix:
    `%mttkrp_mode0.6 = f32[...] custom-call(...)` -> `mttkrp_mode0`."""
    return re.sub(r"(\.\d+)+$", "", name.split(" = ", 1)[0].lstrip("%"))


def _self_seconds(ops) -> dict[str, float]:
    """Seconds per op name, each op less the ops nested in it (a `while`
    holds its body's ops on the same line)."""
    out = defaultdict(float)
    stack = []  # (end, name) of the ops that enclose the current one
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= e - s
        out[name] += e - s
        stack.append((e, name))
    return {k: v * 1e-9 for k, v in out.items()}


def _clipped(events: list[Event], window: tuple[int, int]) -> list[tuple[int, int, str]]:
    """(start, end, op name) of the device ops, clipped to `window` (ns)."""
    lo, hi = window
    return [(max(e.start_ns, lo), min(e.end_ns, hi), _op_name(e.name))
            for e in device_ops(events) if e.end_ns > lo and e.start_ns < hi]


def busy_seconds(events: list[Event], window: tuple[int, int]) -> float:
    """Seconds inside `window` (ns) in which some device op ran."""
    return sum(e - s for s, e in _merged((s, e) for s, e, _ in _clipped(events, window))) * 1e-9


def summarize(events: list[Event], window: tuple[int, int],
              spans: list[Event]) -> Summary:
    """Busy time, top ops and labelled idle gaps inside `window` (ns)."""
    lo, hi = window
    ops = _clipped(events, window)
    busy = _merged((s, e) for s, e, _ in ops if e > s)
    per_op = _self_seconds(ops)

    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    per_label = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) // 2
        around = [sp for sp in spans if sp.start_ns <= mid < sp.end_ns]
        label = min(around, key=lambda sp: sp.end_ns - sp.start_ns).name if around else "outside any span"
        per_label[label] += (e - s) * 1e-9

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP] if v > 0]

    return Summary(
        busy_s=sum(e - s for s, e in busy) * 1e-9,
        window_s=(hi - lo) * 1e-9,
        device_ops=top(per_op),
        idle_gaps=top(per_label),
        op_seconds=dict(per_op),
    )
