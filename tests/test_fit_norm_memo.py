"""‖X‖ for the fit is computed once per tensor: the first fit of a
`SparseTensor` computes it inside a `cp_als.fit_norm` span, and every later
fit of that tensor, in the same or a later `cp_als` call, reads the float
the tensor kept.  The fits stay the same floats, bit for bit."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.core import SparseTensor, cp_als, random_tensor
from repro.core import cpals
from repro.obs import capture, default_registry, disable_tracing

SHAPE, NNZ, RANK, ITERS = (12, 10, 8), 60, 3, 3


@pytest.fixture(autouse=True)
def _tracer_off():
    disable_tracing()
    yield
    disable_tracing()


def _counts():
    snap = default_registry.snapshot()
    return tuple(snap.get(f"cp_als.fit_norm_{k}", {}).get("value", 0)
                 for k in ("computed", "reused"))


def _run(st, engine, seed=0):
    return cp_als(st, rank=RANK, n_iters=ITERS, engine=engine, track_diff=False, seed=seed)


def _fresh(st):
    return SparseTensor(st.coords, st.values, st.shape)


@pytest.mark.parametrize("engine", ["ref", "pallas"])
def test_two_decompositions_of_one_tensor_compute_the_norm_once(engine):
    st = random_tensor(SHAPE, NNZ, seed=0)
    before = _counts()
    with capture() as spans:
        first = _run(st, engine)
        second = _run(st, engine)
    after = _counts()
    norms = [s for s in spans if s.name == "cp_als.fit_norm"]
    assert len(norms) == 1 and norms[0].attrs == {"nnz": NNZ}
    (first_fit,) = [s for s in spans
                    if s.name == "cp_als.fit" and s.span_id == norms[0].parent_id]
    assert first_fit.attrs["iter"] == 0
    assert [a - b for a, b in zip(after, before)] == [1, 2 * ITERS - 1]
    assert second.fit_history == first.fit_history


@pytest.mark.parametrize("engine", ["ref", "pallas"])
def test_fits_equal_a_recomputation_on_every_iteration(engine, monkeypatch):
    """Each fit `cp_als` reports is the float that `fit_value` gives on a
    fresh tensor with the same arrays, which computes ‖X‖ anew."""
    calls = []
    real = cpals.fit_value

    def spy(st, factors, lam, mlast=None, last_mode=None):
        calls.append((st, list(factors), lam, mlast, last_mode))
        return real(st, factors, lam, mlast, last_mode)

    monkeypatch.setattr(cpals, "fit_value", spy)
    st = random_tensor(SHAPE, NNZ, seed=1)
    fits = _run(st, engine).fit_history + _run(st, engine, seed=1).fit_history
    assert len(calls) == 2 * ITERS and all(c[0] is st for c in calls)
    recomputed = []
    for _, factors, lam, mlast, last_mode in calls:
        fresh = _fresh(st)
        assert not fresh.norm_known
        recomputed.append(real(fresh, factors, lam, mlast, last_mode))
    assert fits == recomputed


def test_the_kept_norm_is_the_float64_norm_of_the_values():
    st = random_tensor(SHAPE, NNZ, seed=2)
    assert not st.norm_known
    want = float(np.linalg.norm(st.values.astype(np.float64)))
    assert st.norm() == want and st.norm_known
    assert st.norm() == want


@pytest.mark.parametrize("other", ["permuted", "scaled"])
def test_another_tensor_computes_its_own_norm(other):
    st = random_tensor(SHAPE, NNZ, seed=3)
    _run(st, "ref")
    if other == "permuted":
        st2 = st.permuted(np.random.default_rng(0).permutation(st.nnz))
    else:
        st2 = SparseTensor(st.coords, st.values * np.float32(2), st.shape)
    assert st.norm_known and not st2.norm_known
    before = _counts()
    with capture() as spans:
        _run(st2, "ref")
    assert [a - b for a, b in zip(_counts(), before)] == [1, ITERS - 1]
    assert sum(s.name == "cp_als.fit_norm" for s in spans) == 1
    assert st2.norm() == float(np.linalg.norm(st2.values.astype(np.float64)))
    if other == "scaled":
        assert st2.norm() == 2 * st.norm()


def test_the_memo_leaves_equality_repr_and_fields_alone():
    st = random_tensor(SHAPE, NNZ, seed=4)
    twin = _fresh(st)
    text = repr(st)
    st.norm()
    assert st.norm_known and not twin.norm_known
    assert st == twin and twin == st
    assert repr(st) == text == repr(twin)
    assert [f.name for f in dataclasses.fields(st)] == ["coords", "values", "shape"]
    with pytest.raises(dataclasses.FrozenInstanceError):
        st.values = st.values
