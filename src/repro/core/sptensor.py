"""Sparse tensor container + synthetic dataset generators.

The paper evaluates on FROSTT tensors (Table I). The offline container cannot
ship FROSTT, so `table1_tensor` generates synthetic tensors whose mode count,
relative dimension shape, and nonzero *distribution* (balanced vs imbalanced)
match each Table-I entry, scaled to CPU-runnable sizes.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

__all__ = [
    "SparseTensor",
    "random_tensor",
    "table1_tensor",
    "FROSTT",
    "TABLE1",
]


@dataclasses.dataclass(frozen=True)
class SparseTensor:
    """COO sparse tensor. Coordinates are (nnz, N) int32, values (nnz,) f32."""

    coords: np.ndarray
    values: np.ndarray
    shape: tuple[int, ...]

    def __post_init__(self):
        assert self.coords.ndim == 2 and self.coords.shape[1] == len(self.shape)
        assert self.values.shape == (self.coords.shape[0],)

    @property
    def nnz(self) -> int:
        return self.coords.shape[0]

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def density(self) -> float:
        return self.nnz / math.prod(self.shape)

    def to_dense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=np.float64)
        np.add.at(out, tuple(self.coords.T), self.values.astype(np.float64))
        return out.astype(np.float32)

    def norm(self) -> float:
        """‖X‖_F of the nonzeros, in float64.  The first call computes it and
        the tensor keeps it: the fields are frozen and no code writes into
        their arrays, so every later call returns the same float."""
        return self._norm

    @property
    def norm_known(self) -> bool:
        """True once `norm()` has run on this tensor, so that a call is free."""
        return "_norm" in self.__dict__

    @functools.cached_property
    def _norm(self) -> float:
        return float(np.linalg.norm(self.values.astype(np.float64)))

    def permuted(self, order: np.ndarray) -> SparseTensor:
        """Reorder the nonzeros by `order`, which must be a permutation of
        ``arange(nnz)`` — fancy indexing happily accepts short, repeated or
        boolean indexers and silently drops/duplicates nonzeros."""
        order = np.asarray(order)
        if (order.shape != (self.nnz,)
                or not np.issubdtype(order.dtype, np.integer)):
            raise ValueError(
                f"order must be an integer permutation of arange(nnz="
                f"{self.nnz}); got shape {order.shape} dtype {order.dtype}")
        seen = np.zeros(self.nnz, dtype=bool)
        in_range = (order >= 0) & (order < self.nnz)
        seen[order[in_range]] = True
        if not (in_range.all() and seen.all()):
            raise ValueError(
                f"order is not a permutation of arange(nnz={self.nnz}): "
                "every nonzero must appear exactly once")
        return SparseTensor(self.coords[order], self.values[order], self.shape)


#: Collision top-up policy (see `random_tensor`): after this many exact-
#: shortfall rejection rounds, small tensors switch to an exact fill from
#: the not-yet-used cells; tensors too large to enumerate raise after the
#: round cap instead of hanging (statistically unreachable for any sparse
#: request — stalls need density near 1, which implies an enumerable shape).
_TOPUP_EXACT_AFTER = 16
_TOPUP_EXACT_CELLS = 1 << 24
_TOPUP_MAX_ROUNDS = 1024


def _dedup(coords: np.ndarray, values: np.ndarray,
           shape: tuple[int, ...] | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Merge duplicate coordinates by summing values (keeps COO canonical).

    Rows come back in lexicographic order.  Given a `shape` whose cell count
    fits int64, rows are keyed by their row-major linear index and
    deduplicated with a 1-D sort: the same order, so the same bytes, as
    `np.unique(axis=0)`, which sorts row tuples and is far slower (30 s at
    8M nonzeros)."""
    if shape is not None and math.prod(shape) <= np.iinfo(np.int64).max:
        keys = np.ravel_multi_index(tuple(coords.T.astype(np.int64)), shape)
        ukeys, inv = np.unique(keys, return_inverse=True)
        uniq = np.stack(np.unravel_index(ukeys, shape), axis=1)
    else:
        uniq, inv = np.unique(coords, axis=0, return_inverse=True)
    out = np.zeros(uniq.shape[0], dtype=values.dtype)
    np.add.at(out, inv, values)
    return uniq.astype(np.int32), out


def random_tensor(
    shape: tuple[int, ...],
    nnz: int,
    *,
    distribution: str = "uniform",
    value_scale: float = 1.0,
    seed: int = 0,
    zipf_a: float = 1.3,
) -> SparseTensor:
    """Synthetic sparse tensor.

    distribution:
      "uniform"  — nonzeros spread evenly (the paper's "well-balanced",
                   like 5D_large).
      "powerlaw" — Zipf-distributed coordinates per mode (imbalanced, like
                   Delicious), which stresses the partition decider.

    The returned tensor has EXACTLY `nnz` nonzeros (capped at the number of
    cells): `_dedup` merges duplicate draws, so a single batch would come up
    short — powerlaw tensors by up to ~10% — and every consumer sized off
    the request (TABLE1 workload fingerprints, benchmark labels) would be
    silently wrong.  Collision shortfall is topped up with fresh draws until
    the target is met.
    """
    rng = np.random.default_rng(seed)
    shape = tuple(int(d) for d in shape)
    target = min(int(nnz), math.prod(shape))
    # Powerlaw scatter permutations are drawn once per mode and shared by
    # every draw batch, so top-ups hit the same hot rows as the first batch
    # (the imbalanced character must survive the top-up).
    perms = [rng.permutation(dim) if distribution == "powerlaw" else None
             for dim in shape]

    def draw(n: int) -> np.ndarray:
        cols = []
        for dim, perm in zip(shape, perms, strict=True):
            if distribution == "uniform":
                c = rng.integers(0, dim, size=n, dtype=np.int64)
            elif distribution == "powerlaw":
                # Zipf over the dimension, shuffled so hot rows are scattered.
                raw = rng.zipf(zipf_a, size=n) - 1
                c = perm[np.minimum(raw, dim - 1)]
            else:
                raise ValueError(f"unknown distribution {distribution!r}")
            cols.append(c)
        return np.stack(cols, axis=1).astype(np.int32)

    def values_for(n: int) -> np.ndarray:
        return rng.uniform(-value_scale, value_scale, size=n).astype(np.float32)

    coords, values = _dedup(draw(int(nnz)), values_for(int(nnz)), shape)
    for rounds in range(_TOPUP_MAX_ROUNDS):
        if coords.shape[0] >= target:
            break
        # Drawing exactly the shortfall adds at most that many new uniques,
        # so the loop converges to `target` from below and never overshoots.
        need = target - coords.shape[0]
        # Rejection sampling stalls when the request approaches the cell
        # count (a zipf tail makes the last unseen cells nearly
        # unreachable — a coupon-collector hang); such requests only arise
        # on small, enumerable tensors, so fill the shortfall exactly from
        # the missing cells instead.
        if rounds >= _TOPUP_EXACT_AFTER and math.prod(shape) <= _TOPUP_EXACT_CELLS:
            missing = np.setdiff1d(
                np.arange(math.prod(shape), dtype=np.int64),
                np.ravel_multi_index(tuple(coords.T), shape).astype(np.int64),
                assume_unique=True)
            pick = rng.choice(missing, size=need, replace=False)
            extra = np.stack(np.unravel_index(pick, shape), axis=1).astype(np.int32)
        else:
            extra = draw(need)
        coords, values = _dedup(
            np.concatenate([coords, extra]),
            np.concatenate([values, values_for(need)]), shape)
    else:
        raise ValueError(
            f"random_tensor could not reach nnz={target} on shape {shape} "
            f"({distribution!r}) within {_TOPUP_MAX_ROUNDS} top-up rounds — "
            "the request is too dense for rejection sampling on a tensor "
            "too large to fill exactly; lower nnz")
    return SparseTensor(coords, values, shape)


# Table I of the paper, scaled so the *relative* mode sizes and the balanced /
# imbalanced character survive while staying CPU-runnable.  `scale` divides
# each dimension; nnz is chosen to keep a few tens of thousands of nonzeros.
TABLE1: dict[str, dict] = {
    # name: (paper dims), scaled dims, nnz, distribution
    "nell2": dict(shape=(605, 460, 1440), nnz=50_000, distribution="uniform"),
    "nell1": dict(shape=(2900, 2100, 25500), nnz=60_000, distribution="powerlaw"),
    "amazon": dict(shape=(4800, 1800, 1800), nnz=60_000, distribution="uniform"),
    "delicious": dict(shape=(533, 17300, 2500, 140), nnz=40_000, distribution="powerlaw"),
    "lbnl": dict(shape=(160, 420, 160, 420, 868), nnz=30_000, distribution="powerlaw"),
    "5d_large": dict(shape=(10000, 1000, 3000, 4000, 500), nnz=80_000, distribution="uniform"),
}


#: Table I tensors at the dims and nnz FROSTT publishes (frostt.io), with
#: TABLE1's nonzero distribution.  Generated from a seed, not downloaded.
FROSTT: dict[str, dict] = {
    "nell2": dict(shape=(12092, 9184, 28818), nnz=76_879_419,
                  distribution="uniform"),
    "lbnl": dict(shape=(1605, 4198, 1631, 4209, 868131), nnz=1_698_825,
                 distribution="powerlaw"),
}


def table1_tensor(name: str, *, seed: int = 0, nnz: int | None = None) -> SparseTensor:
    spec = TABLE1[name]
    return random_tensor(
        tuple(spec["shape"]),
        nnz if nnz is not None else spec["nnz"],
        distribution=spec["distribution"],
        seed=seed,
    )
