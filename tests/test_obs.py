"""repro.obs: tracer contract (zero emissions + bounded overhead when
disabled, thread-aware nesting when enabled), histogram percentile
accuracy against the log-bucket error bound, trace JSONL schema
round-trip, Chrome export validity, and the summarize CLI exit codes."""
import json
import threading
import time

import pytest

from repro.obs import (
    MetricsRegistry,
    SpanRecord,
    capture,
    default_histogram_bounds,
    disable_tracing,
    enable_tracing,
    get_tracer,
    read_jsonl,
    record_span,
    span,
    span_kind_summary,
    to_chrome_trace,
    traced,
    tracing_enabled,
    tune_decision_summary,
    validate_spans,
    write_chrome_trace,
    write_jsonl,
)
from repro.obs.__main__ import main as obs_cli


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with the global tracer off and empty."""
    disable_tracing()
    get_tracer().clear()
    yield
    disable_tracing()
    get_tracer().clear()


# ---------------------------------------------------------------------------
# tracer: disabled fast path
# ---------------------------------------------------------------------------

def test_disabled_tracer_emits_zero_spans():
    assert not tracing_enabled()
    with span("work.outer", a=1):
        with span("work.inner"):
            pass
    record_span("work.record", 0.01)
    assert len(get_tracer()) == 0


def test_disabled_span_is_shared_noop_singleton():
    s1 = span("a")
    s2 = span("b", attr=1)
    assert s1 is s2  # no per-call allocation when disabled
    assert s1.set(x=1) is s1
    assert s1.duration is None


def test_disabled_overhead_budget():
    """The disabled path must stay within a generous constant factor of an
    uninstrumented loop — it is one attribute check, but CI machines are
    noisy, so the gate is deliberately loose (and the zero-span assertion
    above is the real contract)."""
    n = 20_000

    def plain():
        acc = 0
        for i in range(n):
            acc += i
        return acc

    def instrumented():
        acc = 0
        for i in range(n):
            with span("hot.iter"):
                acc += i
        return acc

    plain()
    instrumented()  # warm both paths before timing
    t0 = time.perf_counter()
    plain()
    t_plain = time.perf_counter() - t0
    t0 = time.perf_counter()
    instrumented()
    t_inst = time.perf_counter() - t0
    assert len(get_tracer()) == 0
    # Context-manager entry alone costs a few x of a bare add; 50x of the
    # plain loop is far above anything but a broken (allocating/locking)
    # disabled path.
    assert t_inst < max(50 * t_plain, 0.25), \
        f"disabled tracing overhead too high: {t_inst:.4f}s vs {t_plain:.4f}s"


def test_traced_decorator_disabled_passthrough():
    calls = []

    @traced("unit.fn", static=True)
    def fn(x):
        calls.append(x)
        return x + 1

    assert fn(1) == 2
    assert len(get_tracer()) == 0
    enable_tracing()
    assert fn(2) == 3
    spans = get_tracer().spans()
    assert [s.name for s in spans] == ["unit.fn"]
    assert spans[0].attrs["static"] is True
    assert calls == [1, 2]


# ---------------------------------------------------------------------------
# tracer: enabled semantics
# ---------------------------------------------------------------------------

def test_span_nesting_and_attrs():
    enable_tracing()
    with span("outer", kind="o") as so:
        with span("inner") as si:
            si.set(found=3)
    spans = get_tracer().spans()
    assert [s.name for s in spans] == ["inner", "outer"]  # close order
    inner, outer = spans
    assert inner.parent_id == outer.span_id
    assert outer.parent_id == 0
    assert inner.attrs == {"found": 3}
    assert outer.attrs == {"kind": "o"}
    assert so.duration >= inner.duration >= 0


def test_span_records_exception_and_reraises():
    enable_tracing()
    with pytest.raises(ValueError), span("boom"):
        raise ValueError("x")
    (rec,) = get_tracer().spans()
    assert rec.attrs["error"] == "ValueError"


def test_record_span_explicit_start_and_parent():
    enable_tracing()
    t0 = time.perf_counter()
    rid = record_span("req", 0.5, t_start=t0, parent_id=0, index=1)
    record_span("req.child", 0.2, t_start=t0, parent_id=rid)
    parent, child = get_tracer().spans()
    assert rid == parent.span_id and child.parent_id == rid
    assert child.t_start == pytest.approx(parent.t_start)
    assert parent.duration == 0.5


def test_threads_nest_independently():
    enable_tracing()
    ready = threading.Barrier(2)

    def work(tag):
        ready.wait()
        with span(f"{tag}.outer"):
            with span(f"{tag}.inner"):
                pass

    threads = [threading.Thread(target=work, args=(t,), name=t)
               for t in ("a", "b")]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    spans = {s.name: s for s in get_tracer().spans()}
    assert len(spans) == 4
    for tag in ("a", "b"):
        assert spans[f"{tag}.inner"].parent_id == spans[f"{tag}.outer"].span_id
        assert spans[f"{tag}.inner"].thread_name == tag
    # Cross-thread spans never parent each other implicitly.
    assert spans["a.outer"].parent_id == spans["b.outer"].parent_id == 0


def test_capture_scope_restores_disabled_state():
    assert not tracing_enabled()
    with capture() as spans:
        assert tracing_enabled()
        with span("scoped"):
            pass
    assert not tracing_enabled()
    assert [s.name for s in spans] == ["scoped"]


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs")
    c.inc()
    c.inc(4)
    snap = reg.snapshot()
    assert snap["reqs"] == {"type": "counter", "value": 5}
    with pytest.raises(TypeError):
        reg.histogram("reqs")  # kind mismatch on an existing name


def test_histogram_percentile_accuracy():
    """Log-bucketed percentiles must land within one bucket width — a
    factor of 10^(1/8) for the default 8-per-decade geometry — of the
    exact sample percentile."""
    import numpy as np
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-5.0, sigma=1.5, size=5000)
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for v in samples:
        h.observe(float(v))
    width = 10 ** (1 / 8)
    for q in (50, 95, 99):
        exact = float(np.percentile(samples, q))
        est = h.percentile(q)
        assert exact / width <= est <= exact * width, \
            f"p{q}: est {est:.3g} vs exact {exact:.3g}"
    assert h.count == len(samples)
    assert h.total == pytest.approx(float(samples.sum()))


def test_histogram_percentile_clamps_to_observed_range():
    reg = MetricsRegistry()
    h = reg.histogram("one")
    h.observe(0.0123)
    for q in (0, 50, 100):
        assert h.percentile(q) == pytest.approx(0.0123)
    assert reg.histogram("empty").percentile(99) == 0.0


def test_histogram_overflow_bucket_returns_max():
    reg = MetricsRegistry()
    h = reg.histogram("big", bounds=(1.0, 10.0))
    h.observe(5000.0)
    assert h.percentile(99) == 5000.0


def test_default_bounds_geometry():
    b = default_histogram_bounds()
    assert b[0] == pytest.approx(1e-6) and b[-1] == pytest.approx(1e3)
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
    assert all(r == pytest.approx(10 ** (1 / 8)) for r in ratios)


def test_metrics_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("n")
    h = reg.histogram("v")

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.001)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert c.value == 4000
    assert h.count == 4000


# ---------------------------------------------------------------------------
# export: JSONL round-trip, Chrome trace, summaries
# ---------------------------------------------------------------------------

def _sample_spans():
    enable_tracing()
    with span("cp_als.iter", iter=0):
        with span("cp_als.mode", mode=1):
            pass
    record_span("autotune.probe", 0.002, candidate="ref", mode=0,
                seconds=0.001, provenance="measured")
    record_span("autotune.probe", 0.0, candidate="ref", mode=1,
                provenance="elided")
    record_span("autotune.decision", 0.0, source="measured", probes=1)
    return get_tracer().spans()


def test_jsonl_round_trip(tmp_path):
    spans = _sample_spans()
    path = write_jsonl(spans, tmp_path / "t.jsonl")
    meta, back = read_jsonl(path)
    assert meta["version"] == 1 and meta["pid"] > 0
    assert back == spans  # SpanRecord is frozen+eq: exact round-trip
    validate_spans(back)
    # Every line is JSON with an explicit type tag.
    lines = (tmp_path / "t.jsonl").read_text().splitlines()
    assert json.loads(lines[0])["type"] == "meta"
    assert all(json.loads(ln)["type"] == "span" for ln in lines[1:])


def test_read_jsonl_rejects_bad_traces(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"type": "span", "name": "x"}\n')
    with pytest.raises(ValueError, match="no meta|missing"):
        read_jsonl(p)
    p.write_text('{"type": "meta", "version": 999}\n')
    with pytest.raises(ValueError, match="version"):
        read_jsonl(p)
    p.write_text("not json\n")
    with pytest.raises(ValueError, match="not JSON"):
        read_jsonl(p)


def test_validate_spans_catches_violations():
    rec = SpanRecord(name="a", t_start=0.0, duration=0.1, span_id=1,
                     parent_id=0, thread_id=1, thread_name="t", attrs={})
    import dataclasses
    dup = dataclasses.replace(rec)
    with pytest.raises(ValueError, match="duplicate"):
        validate_spans([rec, dup])
    orphan = dataclasses.replace(rec, span_id=2, parent_id=99)
    with pytest.raises(ValueError, match="unknown parent"):
        validate_spans([rec, orphan])
    neg = dataclasses.replace(rec, span_id=3, duration=-1.0)
    with pytest.raises(ValueError, match="negative"):
        validate_spans([rec, neg])


def test_chrome_trace_export(tmp_path):
    spans = _sample_spans()
    doc = to_chrome_trace(spans)
    events = doc["traceEvents"]
    meta_ev = [e for e in events if e["ph"] == "M"]
    x_ev = [e for e in events if e["ph"] == "X"]
    assert meta_ev and meta_ev[0]["name"] == "thread_name"
    assert len(x_ev) == len(spans)
    by_name = {e["name"]: e for e in x_ev}
    assert by_name["cp_als.iter"]["cat"] == "cp_als"
    assert by_name["cp_als.mode"]["args"]["mode"] == 1
    # Durations are microseconds: the probe's 2ms becomes 2000.
    assert by_name["autotune.probe"]["dur"] in (2000.0, 0.0)
    path = write_chrome_trace(spans, tmp_path / "t.json")
    json.loads(open(path).read())  # valid JSON document


def test_summaries():
    spans = _sample_spans()
    rows = {r["span"]: r for r in span_kind_summary(spans)}
    assert rows["cp_als.iter"]["count"] == 1
    assert rows["autotune.probe"]["count"] == 2
    tune = tune_decision_summary(spans)
    assert tune["decisions"] == {"measured": 1}
    assert tune["probes"] == {"measured": 1, "elided": 1}
    assert tune["probe_seconds"] == pytest.approx(0.002)


def test_summarize_cli(tmp_path, capsys):
    spans = _sample_spans()
    trace = str(tmp_path / "t.jsonl")
    write_jsonl(spans, trace)
    assert obs_cli(["summarize", trace]) == 0
    out = capsys.readouterr().out
    assert "cp_als.iter" in out and "probes:" in out
    # export subcommand produces a Perfetto-loadable JSON
    out_json = str(tmp_path / "t.json")
    assert obs_cli(["export", trace, "-o", out_json]) == 0
    assert json.loads(open(out_json).read())["traceEvents"]
    # invalid trace → exit 1
    (tmp_path / "bad.jsonl").write_text("nope\n")
    assert obs_cli(["summarize", str(tmp_path / "bad.jsonl")]) == 1
    assert obs_cli(["summarize", str(tmp_path / "missing.jsonl")]) == 1


# ---------------------------------------------------------------------------
# stack integration: the wired spans actually appear
# ---------------------------------------------------------------------------

def test_cp_als_iter_times_match_trace():
    from repro.core import cp_als, random_tensor
    st = random_tensor((5, 4, 3), 20, seed=0)
    with capture() as spans:
        res = cp_als(st, rank=2, n_iters=2)
    iters = [s for s in spans if s.name == "cp_als.iter"]
    assert [s.attrs["seconds"] for s in iters] == res.iter_times
    modes = [s for s in spans if s.name == "cp_als.mode"]
    assert len(modes) == 2 * st.ndim
    iter_ids = {s.span_id for s in iters}
    assert all(m.parent_id in iter_ids for m in modes)
    root = [s for s in spans if s.name == "cp_als.decompose"]
    assert len(root) == 1 and root[0].attrs["nnz"] == 20


def test_autotune_emits_probe_and_decision_spans(tmp_path):
    from repro.core import random_tensor
    from repro.engine import autotune_engine, TunePolicy
    from repro.engine.registry import EngineContext
    st = random_tensor((6, 5, 4), 30, seed=1)
    policy = TunePolicy(candidates=("ref", "chunked"), warmup=0, reps=1,
                        store=str(tmp_path / "store.json"))
    with capture() as spans:
        _eng, rep = autotune_engine(EngineContext(st=st, rank=2), tune=policy)
    probes = [s for s in spans if s.name == "autotune.probe"]
    assert len(probes) == rep.n_probes + rep.n_elided
    assert all(s.attrs["provenance"] == "measured" for s in probes
               if s.attrs.get("seconds") is not None)
    (decision,) = [s for s in spans if s.name == "autotune.decision"]
    assert decision.attrs["source"] == "measured"
    # Warm second call: zero probes, a persisted decision record.
    with capture() as spans2:
        _eng2, rep2 = autotune_engine(EngineContext(st=st, rank=2),
                                      tune=policy)
    assert rep2.source == "persisted"
    assert [s.name for s in spans2] == ["autotune.decision"]
    assert spans2[0].attrs["source"] == "persisted"


def test_report_to_dict_and_breakdown(tmp_path):
    from repro.core import random_tensor
    from repro.engine import autotune_engine, TunePolicy
    from repro.engine.registry import EngineContext
    st = random_tensor((5, 4, 3), 25, seed=2)
    policy = TunePolicy(candidates=("ref", "chunked"), warmup=0, reps=1,
                        store=str(tmp_path / "s.json"))
    _eng, rep = autotune_engine(EngineContext(st=st, rank=2), tune=policy)
    d = rep.to_dict()
    json.dumps(d)  # JSON-safe end to end
    assert d["source"] == "measured"
    assert d["probes"] == {"measured": rep.n_probes, "elided": rep.n_elided,
                           "persisted": 0}
    assert set(d["winners"]) == set(range(st.ndim))
    assert "probes: measured=" in rep.summary()
    _eng2, rep2 = autotune_engine(EngineContext(st=st, rank=2), tune=policy)
    assert rep2.to_dict()["probes"]["persisted"] == st.ndim
    assert "persisted=3" in rep2.summary()


def test_sweep_cell_spans_carry_fingerprint(tmp_path):
    from repro.sweep import run_sweep
    from repro.sweep.config import SweepConfig, TensorBand
    from repro.sweep.runner import cell_key
    cfg = SweepConfig(
        name="obs-smoke",
        tensors=(TensorBand(name="b0", shape=(5, 4, 3), nnz=(16,),
                            distribution="uniform", seed=0),),
        ranks=(2,), candidates=("ref",), warmup=0, reps=1)
    with capture() as spans:
        result = run_sweep(cfg, str(tmp_path / "store.json"))
    cells = [s for s in spans if s.name == "sweep.cell"]
    assert len(cells) == 1
    keys = {cell_key(c, cfg).fingerprint() for c in cfg.cells()}
    assert cells[0].attrs["fingerprint"] in keys
    # Probe/decision spans nest under the cell span.
    children = [s for s in spans if s.parent_id == cells[0].span_id]
    assert any(s.name == "autotune.decision" for s in children) or \
        any(s.name == "autotune.probe" for s in spans)
    assert result.count("measured") == 1
