"""Executables in a profiler trace: how many ran, and the device time of
those that compute the MTTKRP.

On a TPU the first device plane's "XLA Modules" line holds one event per
run of a compiled executable, named after the jitted function that made it
(`jit__mttkrp_pallas(...)`, `jit_multiply(...)`), spanning that run's ops
on the "XLA Ops" line (`trace.device_ops`).  An eager CP-ALS sweep runs one
executable per `jnp` call, so the count inside a window is what the host
dispatched there.  The MTTKRP's executables are those whose module name
holds `mttkrp`: `jit__mttkrp_pallas` (`kernels/ops.py`) and
`jit_mttkrp_coo_blocked` (`core/blocked.py`); `tests/test_cp_als_spans.py`
pins both names.
"""
from __future__ import annotations

from .trace import DEVICE_PLANE, Event, busy_seconds

__all__ = ["MODULES_LINE", "launches", "module_busy_seconds", "modules"]

MODULES_LINE = "XLA Modules"


def modules(events: list[Event]) -> list[Event]:
    """The executable runs on the first TPU plane, in start order."""
    planes = sorted({e.plane for e in events if e.plane.startswith(DEVICE_PLANE)})
    if not planes:
        return []
    return sorted((e for e in events if e.plane == planes[0] and e.line == MODULES_LINE),
                  key=lambda e: e.start_ns)


def launches(events: list[Event], window: tuple[int, int]) -> int:
    """Executables that started inside `window` (ns)."""
    lo, hi = window
    return sum(lo <= e.start_ns < hi for e in modules(events))


def module_busy_seconds(events: list[Event], window: tuple[int, int], part: str) -> float:
    """Device busy seconds inside `window` (ns) of the executables whose
    module name holds `part`: the union of the ops inside each one's run."""
    lo, hi = window
    return sum(busy_seconds(events, (max(e.start_ns, lo), min(e.end_ns, hi)))
               for e in modules(events)
               if part in e.name and e.end_ns > lo and e.start_ns < hi)
