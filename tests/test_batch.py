"""repro.batch: bucketing edge cases, batched-vs-sequential parity, and the
one-decision-per-bucket tuning contract (zero probes for the 2nd..Nth
members and for a fresh process against a warm store)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.batch import (
    BucketPlanCache,
    bucket_tensors,
    cp_als_batched,
    nnz_band,
    pad_bucket,
    shape_class,
)
from repro.core import SparseTensor, cp_als, random_tensor
from repro.engine import TunePolicy

RANK = 4
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def small(shape, nnz, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    coords = np.stack([rng.integers(0, d, size=nnz) for d in shape],
                      axis=1).astype(np.int32)
    values = rng.uniform(-1, 1, size=nnz).astype(dtype)
    return SparseTensor(coords, values, tuple(shape))


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------

def test_shape_class_rounds_to_pow2():
    assert shape_class((12, 10, 8)) == (16, 16, 8)
    assert shape_class((1, 2, 3)) == (1, 2, 4)


def test_nnz_band_boundary_is_exact():
    # 2^k is band k; 2^k - 1 is band k-1 — the boundary itself never
    # wobbles (integer bit_length, no float log).
    for k in (3, 5, 10, 20):
        assert nnz_band(2 ** k) == k
        assert nnz_band(2 ** k - 1) == k - 1
        assert nnz_band(2 ** k + 1) == k
    assert nnz_band(1) == 0
    assert nnz_band(0) == -1
    with pytest.raises(ValueError, match="nnz must be >= 0"):
        nnz_band(-1)


def test_empty_input_is_empty():
    assert bucket_tensors([]) == {}
    assert cp_als_batched([], RANK) == []


def test_single_tensor_bucket_round_trips():
    t = small((9, 7, 5), 33, seed=1)
    buckets = bucket_tensors([t])
    assert len(buckets) == 1
    ((dims, band), bucket), = buckets.items()
    assert dims == (16, 8, 8) and band == 5 and bucket.size == 1
    res = cp_als_batched([t], RANK, n_iters=2)
    assert len(res) == 1
    assert [f.shape for f in res[0].factors] == [(9, RANK), (7, RANK),
                                                 (5, RANK)]


def test_band_boundary_splits_buckets():
    lo = small((8, 8, 8), 63, seed=2)   # band 5
    hi = small((8, 8, 8), 64, seed=3)   # band 6 — exactly on the boundary
    buckets = bucket_tensors([lo, hi])
    assert len(buckets) == 2
    assert sorted(b for (_, b) in buckets) == [5, 6]


def test_mixed_value_dtypes_rejected():
    a = small((8, 8), 10, seed=4, dtype=np.float32)
    b = small((8, 8), 10, seed=5, dtype=np.float64)
    with pytest.raises(TypeError, match="mixed value dtypes"):
        cp_als_batched([a, b], RANK)


def test_non_tensor_input_rejected():
    with pytest.raises(TypeError, match="input 1"):
        bucket_tensors([small((4, 4), 5), "nope"])


def test_padding_is_zero_and_masked():
    # nnz 17 and 30 are both band 4 → one bucket, padded to 30
    a, b = small((6, 6), 17, seed=6), small((6, 6), 30, seed=7)
    bucket, = bucket_tensors([a, b]).values()
    pb = pad_bucket(bucket)
    assert pb.pad_nnz == 30
    assert pb.values.shape == (2, 30)
    assert np.all(pb.values[0, 17:] == 0.0)
    assert np.all(pb.coords[0, 17:] == 0)
    assert pb.mask[0].sum() == 17 and pb.mask[1].sum() == 30


# ---------------------------------------------------------------------------
# batched ALS correctness
# ---------------------------------------------------------------------------

#: Batched vs sequential CP-ALS.  Not bit-exact on a CPU: a gram over a
#: member's zero-padded rows reduces in a different order than over its true
#: rows, and ALS carries the rounding forward.  Factors are L-inf
#: normalised, so an absolute bound fits them; the largest gap seen on a CPU
#: was 2.7e-6 (ref) and 4.0e-6 (alto).
PARITY_ATOL = 2e-5


def test_batched_matches_sequential_ref_bitexact():
    tensors = [small((12, 10, 8), 40 + i, seed=10 + i) for i in range(4)]
    res = cp_als_batched(tensors, RANK, n_iters=3,
                         tune=TunePolicy(candidates=("ref",)))
    for t, rb in zip(tensors, res, strict=True):
        rs = cp_als(t, RANK, n_iters=3, engine="ref", track_diff=False)
        for fb, fs in zip(rb.factors, rs.factors, strict=True):
            np.testing.assert_allclose(fb, np.asarray(fs), rtol=0,
                                       atol=PARITY_ATOL)
        np.testing.assert_allclose(rb.lam, np.asarray(rs.lam),
                                   rtol=PARITY_ATOL, atol=0)
        assert rb.fit_history[-1] == pytest.approx(rs.fit_history[-1],
                                                   abs=1e-5)


def test_batched_alto_matches_sequential_alto():
    tensors = [small((12, 10, 8), 40 + i, seed=20 + i) for i in range(3)]
    res = cp_als_batched(tensors, RANK, n_iters=2,
                         tune=TunePolicy(candidates=("alto",)))
    for t, rb in zip(tensors, res, strict=True):
        rs = cp_als(t, RANK, n_iters=2, engine="alto", track_diff=False)
        for fb, fs in zip(rb.factors, rs.factors, strict=True):
            np.testing.assert_allclose(fb, np.asarray(fs), rtol=0,
                                       atol=PARITY_ATOL)


def test_mixed_buckets_preserve_input_order():
    tensors = [small((12, 10, 8), 40, seed=30), small((24, 24), 50, seed=31),
               small((12, 10, 8), 45, seed=32)]
    res = cp_als_batched(tensors, RANK, n_iters=1)
    for t, r in zip(tensors, res, strict=True):
        assert [f.shape[0] for f in r.factors] == list(t.shape)


def test_random_tensor_inputs_work_end_to_end():
    tensors = [random_tensor((10, 9, 8), nnz=70, seed=s) for s in range(3)]
    res = cp_als_batched(tensors, RANK, n_iters=2, track_diff=True)
    for r in res:
        assert len(r.fit_history) == 2
        assert len(r.diff_history) == 2
        assert r.engine.startswith("batched:")


# ---------------------------------------------------------------------------
# one autotune decision per bucket
# ---------------------------------------------------------------------------

def test_second_member_and_second_call_are_probe_free(tmp_path):
    store = str(tmp_path / "bucket-store.json")
    tensors = [small((12, 10, 8), 40 + i, seed=40 + i) for i in range(4)]
    plans = BucketPlanCache()
    pol = TunePolicy(store=store)
    res = cp_als_batched(tensors, RANK, n_iters=1, tune=pol, plans=plans)
    # one bucket => every member shares literally the same report object
    reports = {id(r.tune_report) for r in res}
    assert len(reports) == 1
    assert res[0].tune_report.source == "measured"
    assert res[0].tune_report.n_probes > 0

    # same process, warm plan cache: zero probes, no store read
    res2 = cp_als_batched(tensors, RANK, n_iters=1, tune=pol, plans=plans)
    assert res2[0].tune_report.n_probes == 0
    assert res2[0].tune_report.source == "cached"

    # no plan cache, warm store: still zero probes
    res3 = cp_als_batched(tensors, RANK, n_iters=1, tune=pol)
    assert res3[0].tune_report.n_probes == 0
    assert res3[0].tune_report.source == "persisted"


def test_fresh_process_reports_zero_probes(tmp_path):
    store = str(tmp_path / "bucket-store.json")
    code = textwrap.dedent(f"""
        import numpy as np
        from repro.batch import cp_als_batched
        from repro.core import SparseTensor
        from repro.engine import TunePolicy
        rng = np.random.default_rng(0)
        ts = []
        for s in range(3):
            coords = np.stack([rng.integers(0, d, size=40)
                               for d in (12, 10, 8)], axis=1).astype(np.int32)
            vals = rng.uniform(-1, 1, size=40).astype(np.float32)
            ts.append(SparseTensor(coords, vals, (12, 10, 8)))
        res = cp_als_batched(ts, {RANK}, n_iters=1,
                             tune=TunePolicy(store={store!r}))
        print("PROBES", res[0].tune_report.n_probes,
              res[0].tune_report.source)
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    out1 = subprocess.run([sys.executable, "-c", code], check=True, env=env,
                          capture_output=True, text=True, timeout=600).stdout
    assert "PROBES" in out1 and "measured" in out1
    out2 = subprocess.run([sys.executable, "-c", code], check=True, env=env,
                          capture_output=True, text=True, timeout=600).stdout
    assert "PROBES 0 persisted" in out2


def test_accuracy_budget_rejected_on_batched_path():
    t = small((8, 8), 20, seed=50)
    with pytest.raises(ValueError, match="accuracy_budget does not apply"):
        cp_als_batched([t], RANK, tune=TunePolicy(accuracy_budget=0.1))
