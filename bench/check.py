"""The comparison that decides a run's `correct`.

A decomposition drawn from the run's seed is held against the plain
reference (`reference.py`) from the same start:

    mttkrp   the largest relative Frobenius gap, over the modes, between the
             engine's MTTKRP of the decomposition's final factors and the
             reference's MTTKRP of the same factors;
    factors  the largest relative Frobenius gap, over the modes, between the
             decomposition's final factors and those of the reference's
             CP-ALS from the same initial factors;
    lambda   the relative gap between the two final weight vectors;
    fit      the largest absolute gap, over the iterations, between the fit
             that the decomposition reports after each iteration and the
             reference's fit after the same iteration.

Each number has its own limit, set per cell in `workloads/<cell>.json` from
readings of sound runs and of the control (see PERF.md).  A run is correct
when every decomposition it attempted finished with finite results and each
number is at or under its limit.
"""
from __future__ import annotations

import math

import numpy as np

__all__ = ["NUMBERS", "gaps", "judge", "rel_gap"]

NUMBERS = ("mttkrp", "factors", "lambda", "fit")


def rel_gap(x, ref) -> float:
    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(x - ref) / max(np.linalg.norm(ref), 1e-30))


def gaps(mttkrp, ref_mttkrp, factors, ref_factors, lam, ref_lam,
         fits, ref_fits) -> dict[str, float]:
    if len(fits) != len(ref_fits):  # a fit missing or extra is no fit to compare
        fit = math.inf
    else:
        fit = max((abs(float(a) - float(b)) for a, b in zip(fits, ref_fits)), default=0.0)
    return {
        "mttkrp": max(rel_gap(a, b) for a, b in zip(mttkrp, ref_mttkrp, strict=True)),
        "factors": max(rel_gap(a, b) for a, b in zip(factors, ref_factors, strict=True)),
        "lambda": rel_gap(lam, ref_lam),
        "fit": fit,
    }


def judge(numbers: dict[str, float], limits: dict[str, float],
          attempted: int, failed: int) -> bool:
    if attempted == 0 or failed:
        return False
    return all(math.isfinite(numbers[k]) and numbers[k] <= limits[k] for k in NUMBERS)
