"""The benchmark's tensor generator: exact distinct counts, determinism."""
from __future__ import annotations

import numpy as np
import pytest

from bench.generate import draw_coords, generate, mode_groups

UNIFORM = {"kind": "uniform", "seed": 4}
ZIPF = {"kind": "zipf", "a": 1.3, "seed": 4}
VALUES = {"kind": "uniform", "low": -1.0, "high": 1.0}
#: 20 + 20 + 20 + 10 = 70 key bits: no single int64 key holds a cell.
WIDE = (1 << 20, 1 << 20, 1 << 20, 1 << 10)


def _distinct_sorted(coords):
    rows = [tuple(r) for r in coords.tolist()]
    return len(set(rows)) == len(rows) and rows == sorted(rows)


@pytest.mark.parametrize("spec", [UNIFORM, ZIPF])
@pytest.mark.parametrize(("shape", "nnz"), [((300, 200, 400), 5000), (WIDE, 4000),
                                            ((7, 9, 5, 11, 13), 3000)])
def test_exact_distinct_count_in_lexicographic_order(spec, shape, nnz):
    coords = draw_coords(shape, nnz, spec)
    assert coords.shape == (nnz, len(shape))
    assert coords.dtype == np.int32
    assert (coords >= 0).all() and (coords < np.asarray(shape)).all()
    assert _distinct_sorted(coords)


def test_wide_shape_splits_its_key():
    assert mode_groups(WIDE) == [(0, 3), (3, 4)]
    assert mode_groups((12092, 9184, 28818)) == [(0, 3)]
    assert mode_groups((1605, 4198, 1631, 4209, 868131)) == [(0, 4), (4, 5)]


@pytest.mark.parametrize("spec", [UNIFORM, ZIPF])
def test_pattern_is_the_dataset_values_follow_the_seed(spec):
    cfg = {"shape": [50, 60, 70], "nnz": 4000, "coords": spec, "values": VALUES}
    c1, v1 = generate(cfg, 2**31 + 11)
    c2, v2 = generate(cfg, 2**31 + 11)
    c3, v3 = generate(cfg, 7)
    np.testing.assert_array_equal(c1, c2)
    np.testing.assert_array_equal(v1, v2)
    np.testing.assert_array_equal(c1, c3)
    assert not np.array_equal(v1, v3)
    assert v1.dtype == np.float32 and (v1 >= -1).all() and (v1 < 1).all()


def test_pattern_follows_the_configuration_seed():
    a = draw_coords((50, 60, 70), 4000, {"kind": "zipf", "a": 1.3, "seed": 1})
    b = draw_coords((50, 60, 70), 4000, {"kind": "zipf", "a": 1.3, "seed": 2})
    assert not np.array_equal(a, b)


def test_zipf_is_skewed():
    coords = draw_coords((1000, 1000, 1000), 20000, ZIPF)
    top = np.bincount(coords[:, 0]).max()
    assert top > 0.1 * 20000


def test_too_many_nonzeros_is_an_error():
    with pytest.raises(ValueError, match="do not fit"):
        draw_coords((3, 3), 10, UNIFORM)
