"""Sparse-tensor container bugfix batch: `random_tensor` tops up the
post-dedup collision shortfall so the requested nnz is met exactly, and
`SparseTensor.permuted` rejects anything that is not a permutation of
`arange(nnz)` instead of silently dropping/duplicating nonzeros."""
import numpy as np
import pytest

from repro.core import random_tensor, table1_tensor
from repro.core.sptensor import TABLE1, SparseTensor, _dedup


# ---------------------------------------------------------------------------
# random_tensor: exact nnz after dedup top-up
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(TABLE1))
def test_table1_tensor_has_exactly_requested_nnz(name):
    """Regression: powerlaw tensors used to lose up to ~10% of the requested
    nonzeros to duplicate-coordinate merging."""
    st = table1_tensor(name)
    assert st.nnz == TABLE1[name]["nnz"], (name, st.nnz)
    # coordinates stay canonical (unique) after the top-up
    assert np.unique(st.coords, axis=0).shape[0] == st.nnz


@pytest.mark.parametrize("dist", ["uniform", "powerlaw"])
def test_random_tensor_exact_nnz_small_dims(dist):
    # small dims force heavy collisions — the old behavior lost most of them
    st = random_tensor((8, 6, 10), 300, distribution=dist, seed=3)
    assert st.nnz == 300
    assert np.unique(st.coords, axis=0).shape[0] == 300


def test_random_tensor_nnz_caps_at_cell_count():
    st = random_tensor((3, 4), 1000, seed=0)
    assert st.nnz == 12            # the tensor is full, not overfull
    st0 = random_tensor((5, 5), 0, seed=0)
    assert st0.nnz == 0


def test_random_tensor_deterministic_per_seed():
    a = random_tensor((20, 16, 24), 500, seed=7, distribution="powerlaw")
    b = random_tensor((20, 16, 24), 500, seed=7, distribution="powerlaw")
    np.testing.assert_array_equal(a.coords, b.coords)
    np.testing.assert_array_equal(a.values, b.values)
    c = random_tensor((20, 16, 24), 500, seed=8, distribution="powerlaw")
    assert not np.array_equal(a.coords, c.coords)


def test_random_tensor_powerlaw_stays_imbalanced():
    """The top-up reuses the per-mode scatter permutations, so the hot rows
    of the first batch stay hot — the imbalanced character the partition
    decider is stress-tested with must survive."""
    st = random_tensor((2000, 1800, 2200), 30_000, distribution="powerlaw",
                       seed=1)
    assert st.nnz == 30_000
    counts = np.bincount(st.coords[:, 0], minlength=st.shape[0])
    top = np.sort(counts)[::-1][:20].sum()
    assert top > 0.2 * st.nnz      # a Zipf head, nothing like uniform


# ---------------------------------------------------------------------------
# SparseTensor.permuted: order validation
# ---------------------------------------------------------------------------

def _tensor():
    return random_tensor((10, 8, 12), 60, seed=5)


def test_permuted_accepts_real_permutation():
    st = _tensor()
    order = np.random.default_rng(0).permutation(st.nnz)
    pt = st.permuted(order)
    assert pt.nnz == st.nnz
    np.testing.assert_array_equal(pt.coords, st.coords[order])
    np.testing.assert_array_equal(pt.to_dense(), st.to_dense())


@pytest.mark.parametrize(("bad", "why"), [
    (np.arange(59), "wrong length (short)"),
    (np.arange(61), "wrong length (long)"),
    (np.zeros(60, dtype=np.int64), "repeated index"),
    (np.arange(60, dtype=np.float64), "float dtype"),
    (np.arange(1, 61), "out of range"),
    (np.concatenate([[-1], np.arange(1, 60)]), "negative index"),
    (np.ones(60, dtype=bool), "boolean mask"),
])
def test_permuted_rejects_non_permutations(bad, why):
    st = _tensor()
    assert st.nnz == 60
    with pytest.raises(ValueError, match="permutation"):
        st.permuted(bad)


def test_dedup_linear_key_path_is_byte_identical():
    # Power-law draws collide often, so the duplicate-summing path is hit.
    rng = np.random.default_rng(3)
    shape = (40, 7, 300)
    coords = np.stack([np.minimum(rng.zipf(1.3, size=5000) - 1, d - 1)
                       for d in shape], axis=1).astype(np.int32)
    values = rng.uniform(-1, 1, size=5000).astype(np.float32)
    slow_c, slow_v = _dedup(coords, values)
    fast_c, fast_v = _dedup(coords, values, shape)
    assert slow_c.shape[0] < coords.shape[0]
    assert fast_c.dtype == slow_c.dtype and fast_v.dtype == slow_v.dtype
    assert fast_c.tobytes() == slow_c.tobytes()
    assert fast_v.tobytes() == slow_v.tobytes()
