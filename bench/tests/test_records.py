"""The readers of the program's own records (`records.py`): the spans of the
traced decomposition and the layout counters, on the CPU at a tiny size."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench.run import read_metric
from bench.trace import Summary

SPAN_READERS = ("fit_host_ms", "decompose_fixed_ms")
COUNTER_READERS = ("layout_chunk_s", "layout_fill_pct")


def _obs(busy_s: float = 1.0):
    """What the readers see of a traced run: a decomposition that kept the
    device busy for `busy_s` (0: a rehearsal on the CPU)."""
    return SimpleNamespace(trace=Summary(busy_s, 2.0, [], [], {}))


@pytest.fixture(autouse=True)
def _tracer():
    from repro.obs.tracing import disable_tracing, get_tracer

    disable_tracing()
    get_tracer().clear()
    yield get_tracer()
    disable_tracing()
    get_tracer().clear()


@pytest.fixture
def registry(monkeypatch):
    import repro.obs.metrics
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    monkeypatch.setattr(repro.obs.metrics, "default_registry", reg)
    return reg


def _decompose(seed: int):
    from repro.core import cp_als, random_tensor
    from repro.obs.tracing import capture

    with capture() as spans:
        cp_als(random_tensor((12, 10, 8), 60, seed=seed), rank=3, n_iters=2,
               engine="ref", track_diff=False)
    return spans


def test_span_readers_read_the_last_decomposition():
    _decompose(1)
    spans = _decompose(2)
    total = {name: sum(s.duration for s in spans if s.name == name)
             for name in ("cp_als.fit", "cp_als.init", "cp_als.readback")}
    assert read_metric("fit_host_ms", _obs()) == pytest.approx(1000 * total["cp_als.fit"] / 2)
    assert read_metric("decompose_fixed_ms", _obs()) == pytest.approx(
        1000 * (total["cp_als.init"] + total["cp_als.readback"]))


def test_a_program_without_the_init_and_readback_spans_reads_no_fixed_cost():
    from repro.obs.tracing import capture, span

    with capture(), span("cp_als.decompose"):
        with span("cp_als.fit"):
            pass
    assert read_metric("fit_host_ms", _obs()) > 0
    assert read_metric("decompose_fixed_ms", _obs()) is None


def test_counter_readers_read_the_layout_counters(registry):
    registry.histogram("layout.chunk_seconds").observe(1.5)
    registry.histogram("layout.chunk_seconds").observe(2.5)
    registry.counter("layout.kernel_nonzeros").inc(3)
    registry.counter("layout.kernel_slots").inc(4)
    assert read_metric("layout_chunk_s", _obs()) == pytest.approx(4.0)
    assert read_metric("layout_fill_pct", _obs()) == pytest.approx(75.0)


@pytest.mark.parametrize("name", SPAN_READERS + COUNTER_READERS)
def test_no_record_reads_none(name, registry):
    assert read_metric(name, _obs()) is None


@pytest.mark.parametrize("name", SPAN_READERS + COUNTER_READERS)
def test_a_run_with_no_device_op_reads_none(name, registry):
    _decompose(3)
    registry.histogram("layout.chunk_seconds").observe(1.0)
    registry.counter("layout.kernel_nonzeros").inc(1)
    registry.counter("layout.kernel_slots").inc(2)
    assert read_metric(name, _obs(busy_s=0.0)) is None
    assert read_metric(name, SimpleNamespace(trace=None)) is None
    assert read_metric(name, _obs()) is not None
