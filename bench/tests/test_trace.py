"""The trace reduction: busy union, idle share, top ops, labelled gaps."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench.trace import Event, busy_seconds, device_ops, summarize

DEV, OPS = "/device:TPU:0", "XLA Ops"
MS = 1_000_000


def op(name, s, e, plane=DEV, line=OPS):
    return Event(plane, line, f"%{name} = f32[8] op()", s * MS, e * MS)


def span(name, s, e):
    return Event("program", "spans", name, s * MS, e * MS)


EVENTS = [
    op("while.2", 0, 50),                                    # a loop and its body:
    op("mttkrp_mode0.6", 0, 40), op("fusion.3", 40, 50),     # busy 0-50
    op("mttkrp_mode1.6", 60, 90),                            # gap 50-60
    op("fusion.17", 95, 100),                                # gap 90-95
    op("other_chip", 0, 100, plane="/device:TPU:1"),         # only the first chip counts
    op("module", 0, 100, line="XLA Modules"),                # not an op line
    Event("/host:CPU", "python", "bench.traced_window", 0, 120 * MS),
]
SPANS = [span("cp_als.iter", 0, 100), span("cp_als.fit", 48, 62),
         span("cp_als.mode", 88, 97)]


def test_device_ops_are_the_first_chips_op_line():
    assert [e.name.split(" ")[0] for e in device_ops(EVENTS)] == [
        "%while.2", "%mttkrp_mode0.6", "%fusion.3", "%mttkrp_mode1.6", "%fusion.17"]


def test_busy_is_the_union_and_gaps_carry_their_span():
    s = summarize(EVENTS, (0, 120 * MS), SPANS)
    assert s.busy_s == pytest.approx(0.085)
    assert s.window_s == pytest.approx(0.120)
    # Gaps: 50-60 inside the fit, 90-95 inside a mode, 100-120 in no span.
    assert dict(s.idle_gaps) == pytest.approx(
        {"outside any span": 0.020, "cp_als.fit": 0.010, "cp_als.mode": 0.005})
    assert [k for k, _ in s.idle_gaps] == ["outside any span", "cp_als.fit", "cp_als.mode"]


def test_ops_are_self_times_summed_by_name_without_numeric_suffix():
    s = summarize(EVENTS, (0, 120 * MS), SPANS)
    assert dict(s.device_ops) == pytest.approx(
        {"mttkrp_mode0": 0.040, "mttkrp_mode1": 0.030, "fusion": 0.015})
    assert s.op_seconds["while"] == pytest.approx(0.0)


def test_window_clips_ops():
    s = summarize(EVENTS, (20 * MS, 70 * MS), SPANS)
    assert s.busy_s == pytest.approx(0.040)
    assert dict(s.device_ops) == pytest.approx(
        {"mttkrp_mode0": 0.020, "fusion": 0.010, "mttkrp_mode1": 0.010})


def test_busy_seconds_is_the_union_inside_a_span():
    # A mode's annotation from 30 to 97 ms: 30-50, 60-90 and 95-97 are busy.
    assert busy_seconds(EVENTS, (30 * MS, 97 * MS)) == pytest.approx(0.052)
    assert busy_seconds(EVENTS, (50 * MS, 60 * MS)) == 0


def test_no_device_ops_reads_no_busy_time():
    s = summarize([e for e in EVENTS if not e.plane.startswith("/device")], (0, 120 * MS), SPANS)
    assert s.busy_s == 0 and s.device_ops == []


def test_recorded_nell2_iteration():
    """One CP-ALS iteration of nell2.r16.pallas and its fit, recorded on a
    TPU v5e: three kernel calls of ~265 ms, then the fit's host work with
    the device idle."""
    rec = json.loads((Path(__file__).parent / "data" / "nell2_iteration.json").read_text())
    events = [Event.from_json(r) for r in rec["events"]]
    spans = [e for e in events if not e.plane.startswith("/device")]
    s = summarize(events, tuple(rec["window"]), spans)
    assert s.window_s == pytest.approx(1.521717017)
    assert s.busy_s == pytest.approx(0.846467885)
    ops = dict(s.device_ops)
    assert [k for k, _ in s.device_ops[:4]] == [
        "mttkrp_mode0", "mttkrp_mode1", "mttkrp_mode2", "fusion"]
    for m in range(3):
        assert ops[f"mttkrp_mode{m}"] == pytest.approx(0.2655, rel=2e-3)
    assert sum(s.op_seconds.values()) == pytest.approx(s.busy_s)
    assert s.idle_gaps[0][0] == "cp_als.fit"
    assert s.idle_gaps[0][1] == pytest.approx(0.6584474)
