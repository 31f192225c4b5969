"""Plain reference of what a cell computes: COO MTTKRP and CP-ALS.

It follows the paper's definitions and imports nothing of the system under
test.  The MTTKRP is the element-wise COO definition,

    M[i_n, r] = Σ_{nonzeros x at (i_1..i_N)} x · Π_{k≠n} F_k[i_k, r],

in float32 on the device, over fixed-size blocks of nonzeros so that its
(block, R) temporaries fit the chip.  CP-ALS (Algorithm 1) does its small
dense algebra on the host in float64: grams, their Hadamard product, the
pseudo-inverse, L-infinity normalisation and the fit.

`precision` names the arithmetic of the MTTKRP's products:

    "highest"  float32 products, the reference itself;
    "bfloat16" every factor row and partial product rounded to bfloat16: the
               control, the reference one precision below the float32 that
               the configurations state.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Reference", "als", "init_factors"]

BLOCK = 1 << 20


def _round(x, precision: str):
    if precision == "highest":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    raise ValueError(f"unknown precision {precision!r}")


@partial(jax.jit, static_argnames=("mode", "block", "precision"), donate_argnums=0)
def _block(acc, factors, coords, values, start, *, mode: int, block: int, precision: str):
    coords = jax.lax.dynamic_slice_in_dim(coords, start, block)
    part = jax.lax.dynamic_slice_in_dim(values, start, block)[:, None]
    for k, f in enumerate(factors):
        if k != mode:
            part = part * _round(f[coords[:, k]], precision)
    return acc.at[coords[:, mode]].add(_round(part, precision))


@dataclasses.dataclass
class Reference:
    """A COO tensor on the device, padded to whole blocks of nonzeros."""

    coords: jax.Array
    values: jax.Array
    shape: tuple[int, ...]
    block: int

    @classmethod
    def put(cls, coords: np.ndarray, values: np.ndarray, shape,
            block: int = BLOCK) -> Reference:
        nnz = values.shape[0]
        block = min(block, max(nnz, 1))
        pad = (-nnz) % block
        # Padding slots hold value 0 at coordinate 0: they add nothing.
        coords = np.concatenate([coords, np.zeros((pad, coords.shape[1]), coords.dtype)])
        values = np.concatenate([values, np.zeros(pad, values.dtype)])
        return cls(jnp.asarray(coords), jnp.asarray(values),
                   tuple(int(d) for d in shape), block)

    def mttkrp(self, factors, mode: int, precision: str = "highest") -> np.ndarray:
        """(I_mode, R) float32, read back to the host."""
        factors = tuple(jnp.asarray(f, jnp.float32) for f in factors)
        acc = jnp.zeros((self.shape[mode], factors[0].shape[1]), jnp.float32)
        for s in range(0, self.values.shape[0], self.block):
            acc = _block(acc, factors, self.coords, self.values, s, mode=mode,
                         block=self.block, precision=precision)
        return np.asarray(acc)


def init_factors(shape, rank: int, seed: int) -> list[np.ndarray]:
    """CP-ALS's documented start: U[0, 1) float32 per mode, in mode order,
    from one `numpy.random.default_rng(seed)`."""
    rng = np.random.default_rng(seed)
    return [rng.uniform(0, 1, size=(d, rank)).astype(np.float32) for d in shape]


@dataclasses.dataclass
class ALSResult:
    factors: list[np.ndarray]
    lam: np.ndarray
    fits: list[float]


def als(ref: Reference, norm_x: float, rank: int, n_iters: int, seed: int,
        precision: str = "highest") -> ALSResult:
    """CP-ALS, Algorithm 1, from `init_factors(seed)`."""
    n = len(ref.shape)
    factors = [f.astype(np.float64) for f in init_factors(ref.shape, rank, seed)]
    lam = np.ones(rank)
    fits = []
    for _ in range(n_iters):
        for mode in range(n):
            m = ref.mttkrp([f.astype(np.float32) for f in factors], mode,
                           precision).astype(np.float64)
            v = np.ones((rank, rank))
            for k in range(n):
                if k != mode:
                    v *= factors[k].T @ factors[k]
            a = m @ np.linalg.pinv(v)
            lam = np.max(np.abs(a), axis=0)
            lam[lam == 0] = 1.0
            factors[mode] = a / lam
        # ||X - X̂||² = ||X||² - 2<X, X̂> + ||X̂||², with <X, X̂> from the
        # last mode's MTTKRP, which does not depend on the last factor.
        had = np.outer(lam, lam)
        for f in factors:
            had *= f.T @ f
        inner = float(np.sum(m * factors[-1] * lam[None, :]))
        resid = max(norm_x ** 2 - 2.0 * inner + float(had.sum()), 0.0)
        fits.append(1.0 - math.sqrt(resid) / max(norm_x, 1e-30))
    return ALSResult([f.astype(np.float32) for f in factors], lam, fits)
