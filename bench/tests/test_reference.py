"""The plain reference against the program's own `ref` engine, at a tiny size."""
from __future__ import annotations

import numpy as np
import pytest

from bench.check import rel_gap
from bench.generate import generate
from bench.reference import Reference, als, init_factors

from .conftest import TINY

RANK = 4


@pytest.fixture(scope="module")
def tiny():
    coords, values = generate(TINY, 99)
    # Blocks of 256 nonzeros: 1500 of them take six blocks, the last padded.
    return coords, values, Reference.put(coords, values, TINY["shape"], block=256)


def test_init_matches_cp_als_start():
    from repro.core import init_factors as program_init

    for ours, theirs in zip(init_factors((5, 7), RANK, 2**40 + 3),
                            program_init((5, 7), RANK, 2**40 + 3), strict=True):
        np.testing.assert_array_equal(ours, np.asarray(theirs))


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_mttkrp_matches_program_ref_engine(tiny, mode):
    from repro.core import SparseTensor
    from repro.engine import build_engine

    coords, values, ref = tiny
    st = SparseTensor(coords, values, tuple(TINY["shape"]))
    factors = init_factors(st.shape, RANK, 5)
    ours = ref.mttkrp(factors, mode)
    theirs = np.asarray(build_engine(st, "ref", RANK)(factors, mode))
    assert ours.shape == (TINY["shape"][mode], RANK)
    assert rel_gap(theirs, ours) < 1e-6


def test_als_matches_program_cp_als(tiny):
    from repro.core import SparseTensor, cp_als

    coords, values, ref = tiny
    st = SparseTensor(coords, values, tuple(TINY["shape"]))
    res = cp_als(st, RANK, n_iters=3, engine="ref", track_diff=False, seed=8)
    ours = als(ref, float(np.linalg.norm(values.astype(np.float64))), RANK, 3, 8)
    assert max(rel_gap(a, b) for a, b in zip(res.factors, ours.factors, strict=True)) < 1e-4
    assert rel_gap(res.lam, ours.lam) < 1e-4
    np.testing.assert_allclose(res.fit_history, ours.fits, atol=1e-5)


def test_bfloat16_moves_the_mttkrp(tiny):
    coords, values, ref = tiny
    factors = init_factors(TINY["shape"], RANK, 5)
    assert rel_gap(ref.mttkrp(factors, 0, "bfloat16"), ref.mttkrp(factors, 0)) > 1e-4
