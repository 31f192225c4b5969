"""Executables in a device trace: launches and the MTTKRP's device time."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from bench.executables import launches, module_busy_seconds, modules
from bench.trace import Event

DEV, MODS, OPS = "/device:TPU:0", "XLA Modules", "XLA Ops"
MS = 1_000_000


def module(name, s, e, plane=DEV):
    return Event(plane, MODS, name, s * MS, e * MS)


def op(name, s, e):
    return Event(DEV, OPS, f"%{name} = f32[8] op()", s * MS, e * MS)


EVENTS = [
    module("jit__mttkrp_pallas(11)", 0, 50),                   # ops 0-40 and 45-50
    op("mttkrp_mode0.6", 0, 40), op("fusion.3", 45, 50),
    module("jit_multiply(12)", 55, 58), op("multiply.1", 55, 58),
    module("jit_mttkrp_coo_blocked(13)", 60, 90), op("fusion.7", 60, 90),
    module("jit__pinv(14)", 95, 99), op("custom-call.2", 95, 99),
    module("jit_mttkrp_coo_blocked(15)", 0, 100, plane="/device:TPU:1"),  # another chip
    Event("/host:CPU", "python", "cp_als.iter", 0, 100 * MS),
]


def test_modules_are_the_first_chips_module_line():
    assert [e.name for e in modules(EVENTS)] == [
        "jit__mttkrp_pallas(11)", "jit_multiply(12)", "jit_mttkrp_coo_blocked(13)",
        "jit__pinv(14)"]


@pytest.mark.parametrize("window, count", [((0, 100), 4), ((1, 100), 3), ((55, 95), 2),
                                           ((99, 200), 0)])
def test_launches_count_the_executables_that_start_inside(window, count):
    assert launches(EVENTS, (window[0] * MS, window[1] * MS)) == count


@pytest.mark.parametrize("window, seconds", [((0, 100), 0.075), ((20, 70), 0.035),
                                             ((50, 60), 0.0)])
def test_mttkrp_time_is_the_busy_time_inside_its_executables(window, seconds):
    # The gap 40-45 inside jit__mttkrp_pallas is idle; jit_multiply is not an MTTKRP.
    assert module_busy_seconds(EVENTS, (window[0] * MS, window[1] * MS), "mttkrp") == \
        pytest.approx(seconds)


RECORDED = json.loads((Path(__file__).parent / "data" / "iteration_modules.json").read_text())


@pytest.mark.parametrize("cell, n, jit", [("nell2.r16.pallas", 3, "jit__mttkrp_pallas"),
                                          ("lbnl.r16.ref", 5, "jit_mttkrp_coo_blocked")])
def test_recorded_iteration(cell, n, jit):
    """One CP-ALS iteration of a traced decomposition on a TPU v5e, from one
    `cp_als.iter` span's start to the next, with the device plane's module
    and op lines (ops cut to their instruction names) and the program's
    spans as the profiler's host plane recorded them: 3N² + 10N + 11
    executables, N of them the MTTKRP, whose device time is the per-mode
    MTTKRP time that the same run measured on isolated calls."""
    rec = RECORDED[cell]
    events = [Event.from_json(r) for r in rec["events"]]
    window = tuple(rec["window"])
    assert launches(events, window) == 3 * n * n + 10 * n + 11
    names = [re.sub(r"\(\d+\)$", "", e.name) for e in modules(events)
             if window[0] <= e.start_ns < window[1]]
    assert [x for x in names if "mttkrp" in x] == [jit] * n
    assert module_busy_seconds(events, window, "mttkrp") == pytest.approx(
        sum(rec["mode_s"]), rel=2e-3)
    host = [e.name for e in events if e.plane.startswith("/host")]
    assert host.count("cp_als.mttkrp") == host.count("cp_als.solve") == n
    assert host.count("cp_als.fit_readback") == 1
