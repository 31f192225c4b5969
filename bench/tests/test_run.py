"""End-to-end rehearsals of `bench/run.py` on the CPU, at a tiny size."""
from __future__ import annotations

import json

import pytest

from bench import run


def _run(bench_dir, capsys, *argv, **kw):
    rc = run.main(["--seed", "12345678901", "--seconds", "0.2", *argv],
                  benchmark=bench_dir / "BENCHMARK.json", data=bench_dir,
                  require_tpu=False, compile_cache=None, **kw)
    out, err = capsys.readouterr()
    return rc, out, err


@pytest.mark.parametrize("engine", ["ref", "pallas"])
def test_run_reports_end_to_end_metrics(tiny_bench, capsys, engine):
    rc, out, err = _run(tiny_bench, capsys, "--workload", f"tiny.r4.{engine}")
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 0, err
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"als_iter_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check fit ")


def test_traced_run_reports_per_layer_metrics(tiny_bench, capsys):
    rc, out, err = _run(tiny_bench, capsys, "--workload", "tiny.r4.ref", "--trace", "1")
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 0, err
    # No device ops and no peaks on the CPU: those readers find nothing.
    assert set(result["metrics"]) == {"window_compiles", "layout_build_s"}
    assert result["metrics"]["window_compiles"]["value"] == 0
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_tpu_prints_no_result(tiny_bench, capsys):
    rc = run.main(["--workload", "tiny.r4.ref", "--seed", "1", "--seconds", "1"],
                  benchmark=tiny_bench / "BENCHMARK.json", data=tiny_bench,
                  compile_cache=None)
    out, err = capsys.readouterr()
    assert rc == 2
    assert out == ""
    assert "no TPU" in err


def test_device_kind_without_peaks_prints_no_result(tiny_bench, capsys, monkeypatch):
    import jax

    monkeypatch.setattr(run, "device_check", lambda *a: jax.devices())
    rc = run.main(["--workload", "tiny.r4.ref", "--seed", "1", "--seconds", "1"],
                  benchmark=tiny_bench / "BENCHMARK.json", data=tiny_bench,
                  compile_cache=None)
    out, err = capsys.readouterr()
    assert rc == 2
    assert out == ""
    assert "no peaks for device kind 'cpu'" in err


def test_checkout_without_the_system_prints_no_result(tmp_path, capsys, monkeypatch):
    monkeypatch.setattr(run, "ROOT", tmp_path)
    rc = run.main(["--workload", "nell2.r16.pallas", "--seed", "1", "--seconds", "1"],
                  compile_cache=None)
    out, err = capsys.readouterr()
    assert rc == 2
    assert out == ""
    assert "no system under test" in err


def test_control_comes_out_not_correct(tiny_bench, capsys):
    rc, out, err = _run(tiny_bench, capsys, "--workload", "tiny.r4.ref", "--control")
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 1
    assert result["correct"] is False
    over = {k: c["value"] > c["limit"] for k, c in result["checks"].items() if k != "fit"}
    assert over == {"mttkrp": True, "factors": True, "lambda": True}


def _no_steps(monkeypatch):
    """Each decomposition returns its initial state: no ALS step runs."""
    import repro.core

    real = repro.core.cp_als
    monkeypatch.setattr(repro.core, "cp_als",
                        lambda *a, **kw: real(*a, **{**kw, "n_iters": 0}))


def _half_the_nonzeros(monkeypatch):
    """The engine sums every other nonzero, scaled by 2 for the mean."""
    import repro.engine
    from repro.core import SparseTensor

    real = repro.engine.build_engine

    def build(st, method, rank, **kw):
        half = SparseTensor(st.coords[::2], st.values[::2] * 2, st.shape)
        return real(half, method, rank, **kw)
    monkeypatch.setattr(repro.engine, "build_engine", build)


def _altered_answer(monkeypatch):
    """The engine's MTTKRP comes back with one row changed."""
    import repro.engine
    from repro.engine import Engine

    real = repro.engine.build_engine

    def build(st, method, rank, **kw):
        eng = real(st, method, rank, **kw)
        return Engine(eng.name, lambda f, m: eng(f, m).at[0].add(1.0),
                      spec=eng.spec, context=eng.context)
    monkeypatch.setattr(repro.engine, "build_engine", build)


def _fit_left_at_zero(monkeypatch):
    """Each decomposition reports a fit of 0 after every iteration."""
    import dataclasses

    import repro.core

    real = repro.core.cp_als

    def cp_als(*a, **kw):
        r = real(*a, **kw)
        return dataclasses.replace(r, fit_history=[0.0] * len(r.fit_history))
    monkeypatch.setattr(repro.core, "cp_als", cp_als)


def _stale_fit(monkeypatch):
    """Each iteration reports the fit of the iteration before it."""
    import dataclasses

    import repro.core

    real = repro.core.cp_als

    def cp_als(*a, **kw):
        r = real(*a, **kw)
        return dataclasses.replace(r, fit_history=[0.0] + r.fit_history[:-1])
    monkeypatch.setattr(repro.core, "cp_als", cp_als)


@pytest.mark.parametrize("fault", [_no_steps, _half_the_nonzeros, _altered_answer,
                                   _fit_left_at_zero, _stale_fit])
@pytest.mark.parametrize("engine", ["ref", "pallas"])
def test_faults_come_out_not_correct(tiny_bench, capsys, monkeypatch, fault, engine):
    fault(monkeypatch)
    rc, out, err = _run(tiny_bench, capsys, "--workload", f"tiny.r4.{engine}")
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 1, err
    assert result["correct"] is False
    if fault in (_fit_left_at_zero, _stale_fit):
        assert result["checks"]["fit"]["value"] > result["checks"]["fit"]["limit"]
