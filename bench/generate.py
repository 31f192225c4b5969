"""The benchmark's sparse-tensor generator, driven by a configuration file.

A configuration names the published dims and count of distinct nonzeros of
a tensor and the distribution its coordinates are assumed to follow:

    "shape": [12092, 9184, 28818], "nnz": 76879419,
    "coords": {"kind": "uniform", "seed": 0}            # or
    "coords": {"kind": "zipf", "a": 1.3, "seed": 0}
    "values": {"kind": "uniform", "low": -1.0, "high": 1.0}

The nonzero pattern is the dataset: it is drawn from the configuration's own
`coords.seed`, so every run decomposes the same tensor, as a user of that
dataset does, and every compiled program keeps its shapes from run to run.
The run's `--seed` draws the values.

Coordinates come back distinct, in lexicographic order, exactly `nnz` of
them.  Duplicate draws are removed and the shortfall is drawn again until the
count is met.  A row's key is its row-major index where the cell count fits
int64; a shape of more than 62 key bits (FROSTT lbnl-network needs 68) is
keyed per group of modes that fits, with no linear key to overflow.
"""
from __future__ import annotations

import math

import numpy as np

__all__ = ["draw_coords", "draw_values", "generate", "mode_groups", "rng_for"]

_INT64_CELLS = 1 << 62
_MAX_ROUNDS = 64


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    """A generator from a whole-number seed of any size or sign."""
    return np.random.default_rng([int(seed) % (1 << 64), *salt])


def mode_groups(shape) -> list[tuple[int, int]]:
    """Contiguous [lo, hi) runs of modes whose cell count stays under 2**62."""
    groups, lo, cells = [], 0, 1
    for m, d in enumerate(shape):
        if cells * d >= _INT64_CELLS and m > lo:
            groups.append((lo, m))
            lo, cells = m, 1
        cells *= d
    groups.append((lo, len(shape)))
    return groups


def _keys(coords: np.ndarray, shape) -> list[np.ndarray]:
    """One row-major int64 key per mode group."""
    out = []
    for lo, hi in mode_groups(shape):
        k = coords[:, lo].astype(np.int64)
        for m in range(lo + 1, hi):
            k = k * shape[m] + coords[:, m]
        out.append(k)
    return out


def _unravel(key: np.ndarray, shape) -> np.ndarray:
    coords = np.empty((key.shape[0], len(shape)), dtype=np.int32)
    for m in reversed(range(1, len(shape))):
        key, coords[:, m] = np.divmod(key, shape[m])
    coords[:, 0] = key
    return coords


def _row_hash(keys: list[np.ndarray]) -> np.ndarray:
    """One int64 per row: the key itself for one group, else a mix of the
    group keys.  Two distinct rows may share a mix, with odds of about
    nnz / 2**64; the later one is then drawn again, so rows stay distinct."""
    if len(keys) == 1:
        return keys[0]
    h = np.zeros(keys[0].shape, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for k in keys:
            h = (h ^ k.astype(np.uint64)) * np.uint64(0x9E3779B97F4A7C15)
            h ^= h >> np.uint64(29)
    return h.view(np.int64)


def _draw(rng: np.random.Generator, spec: dict, shape, n: int,
          perms: list[np.ndarray] | None) -> np.ndarray:
    cols = np.empty((n, len(shape)), dtype=np.int32)
    for m, d in enumerate(shape):
        if spec["kind"] == "uniform":
            cols[:, m] = rng.integers(0, d, size=n, dtype=np.int32)
        elif spec["kind"] == "zipf":
            # Zipf ranks clipped to the mode, then scattered over the rows by
            # one fixed permutation per mode so hot rows are spread out.
            raw = np.minimum(rng.zipf(spec["a"], size=n) - 1, d - 1)
            cols[:, m] = perms[m][raw]
        else:
            raise ValueError(f"unknown coordinate distribution {spec['kind']!r}")
    return cols


def _round(rng, spec: dict, shape, n: int, perms, one_key: bool):
    """n fresh draws: their distinct keys, sorted, and the rows they stand
    for (None where the key is the row-major index and unravels to it)."""
    if one_key and spec["kind"] == "uniform":
        # Uniform over every cell: draw the row-major key itself.
        return np.unique(rng.integers(0, math.prod(shape), size=n)), None
    rows = _draw(rng, spec, shape, n, perms)
    if one_key:
        return np.unique(_keys(rows, shape)[0]), None
    h, first = np.unique(_row_hash(_keys(rows, shape)), return_index=True)
    return h, rows[first]


def draw_coords(shape, nnz: int, spec: dict) -> np.ndarray:
    """(nnz, N) int32 distinct coordinates in lexicographic order."""
    shape = tuple(int(d) for d in shape)
    if nnz > math.prod(shape):
        raise ValueError(f"{nnz} nonzeros do not fit shape {shape}")
    rng = rng_for(spec.get("seed", 0), 1)
    perms = ([rng.permutation(d).astype(np.int32) for d in shape]
             if spec["kind"] == "zipf" else None)
    one_key = len(mode_groups(shape)) == 1
    have = np.empty(0, dtype=np.int64)  # sorted keys of the rows kept
    kept = []
    for _ in range(_MAX_ROUNDS):
        if have.size == nnz:
            break
        h, rows = _round(rng, spec, shape, nnz - have.size, perms, one_key)
        if have.size:
            new = have[np.minimum(np.searchsorted(have, h), have.size - 1)] != h
            h = h[new]
            rows = None if rows is None else rows[new]
            have = np.insert(have, np.searchsorted(have, h), h)
        else:
            have = h
        if rows is not None:
            kept.append(rows)
    else:
        raise ValueError(
            f"{spec['kind']} coordinates on {shape} stayed short of {nnz} "
            f"distinct after {_MAX_ROUNDS} rounds")
    if one_key:
        return _unravel(have, shape)
    coords = np.concatenate(kept)
    return coords[np.lexsort(_keys(coords, shape)[::-1])]


def draw_values(nnz: int, spec: dict, seed: int) -> np.ndarray:
    if spec["kind"] != "uniform":
        raise ValueError(f"unknown value distribution {spec['kind']!r}")
    lo, hi = float(spec["low"]), float(spec["high"])
    v = rng_for(seed, 2).random(nnz, dtype=np.float32)
    return (v * np.float32(hi - lo) + np.float32(lo)).astype(np.float32)


def generate(config: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(coords, values) of the configuration's tensor for run seed `seed`."""
    coords = draw_coords(config["shape"], int(config["nnz"]), config["coords"])
    return coords, draw_values(coords.shape[0], config["values"], seed)
