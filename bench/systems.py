"""What a run drives: the system under test, or the control in its place.

Both offer the same three calls, so the window and the check cannot tell
them apart:

    decompose(seed) -> Decomposition    one whole CP-ALS from init seed `seed`
    mttkrp(factors, mode) -> array      one MTTKRP of the decomposed tensor
    close()                             drop what the system holds on the device
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from .reference import Reference, als

__all__ = ["Control", "Decomposition", "Program"]


@dataclasses.dataclass
class Decomposition:
    factors: list[np.ndarray]
    lam: np.ndarray
    fits: list[float]

    def finite(self) -> bool:
        return (all(np.isfinite(f).all() for f in self.factors)
                and bool(np.isfinite(self.lam).all())
                and all(np.isfinite(self.fits)))


class Program:
    """`repro.core.cp_als` on an engine that `repro.engine.build_engine`
    builds once, in set-up, for the traffic's backend and rank."""

    def __init__(self, coords, values, shape, traffic: dict):
        from repro.core import SparseTensor
        from repro.engine import build_engine

        self.traffic = traffic
        self.st = SparseTensor(coords, values, tuple(int(d) for d in shape))
        t0 = time.perf_counter()
        self.engine = build_engine(self.st, traffic["engine"], traffic["rank"])
        self.layout_build_s = time.perf_counter() - t0

    def decompose(self, seed: int) -> Decomposition:
        from repro.core import cp_als

        t = self.traffic
        r = cp_als(self.st, t["rank"], n_iters=t["n_iters"], engine=self.engine,
                   track_diff=t["track_diff"], seed=seed)
        return Decomposition(r.factors, r.lam, r.fit_history)

    def mttkrp(self, factors, mode: int):
        import jax.numpy as jnp

        return self.engine([jnp.asarray(f) for f in factors], mode)

    def close(self) -> None:
        from repro.engine import default_plan_cache
        from repro.formats.convert import default_format_cache

        self.engine = self.st = None
        default_plan_cache.clear()
        default_format_cache.clear()
        gc.collect()


class Control:
    """The reference computed at `precision`, put in the program's place."""

    def __init__(self, coords, values, shape, traffic: dict, precision: str):
        self.traffic, self.precision = traffic, precision
        self.ref = Reference.put(coords, values, shape)
        self.norm_x = float(np.linalg.norm(values.astype(np.float64)))
        self.layout_build_s = 0.0

    def decompose(self, seed: int) -> Decomposition:
        t = self.traffic
        r = als(self.ref, self.norm_x, t["rank"], t["n_iters"], seed, self.precision)
        return Decomposition(r.factors, r.lam, r.fits)

    def mttkrp(self, factors, mode: int):
        return self.ref.mttkrp(factors, mode, self.precision)

    def close(self) -> None:
        self.ref = None
        gc.collect()
