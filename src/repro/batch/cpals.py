"""Batched CP-ALS: one ALS loop decomposing a whole bucket at once.

The math is member-wise identical to the sequential `repro.core.cp_als`:
every step (MTTKRP, gram Hadamard, pinv solve, normalization, the sparse
fit identity) is the same computation with a leading batch axis, and each
member's factors are initialized from `init_factors(member.shape, rank,
seed)` — the sequential initializer on the member's TRUE shape, zero-padded
to the bucket dims.  Padded factor rows receive zero MTTKRP contributions,
solve to zero, and never disturb column norms or grams, so the per-member
results match the sequential path to float tolerance, not bit-exactly: a
gram over zero-padded rows reduces in a different order than over the true
rows.  `tests/test_batch.py` holds factors to an absolute 2e-5.

Where the sequential driver re-decides its engine per tensor, this one
makes ONE decision per bucket (`tune.autotune_bucket`): the first member
probes, everyone after dispatches warm with zero probes.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core.cpals import _HIGHEST, CPResult, init_factors
from ..engine.tunepolicy import TunePolicy
from ..obs.tracing import span
from .bucketing import Bucket, bucket_tensors, pad_bucket
from .tune import BucketPlanCache, autotune_bucket

__all__ = ["cp_als_batched"]


def _normalize_batched(f: jnp.ndarray, norm: str):
    """Batched `repro.core.cpals._normalize`: f (B, I, R) → (f/λ, λ (B, R))."""
    if norm == "linf":
        lam = jnp.max(jnp.abs(f), axis=1)
    elif norm == "l2":
        lam = jnp.linalg.norm(f, axis=1)
    else:
        raise ValueError(norm)
    lam = jnp.where(lam == 0, 1.0, lam)
    return f / lam[:, None, :], lam


def _gram(f: jnp.ndarray) -> jnp.ndarray:
    """(B, I, R) → (B, R, R) FᵀF per member: the sequential driver's
    `f.T @ f` with a batch axis, so a member whose dims need no padding gets
    the same bits (an einsum contracting axis 1 rounds differently)."""
    return jnp.matmul(jnp.swapaxes(f, 1, 2), f, precision=_HIGHEST)


@jax.jit
def _pinv_each(v: jnp.ndarray) -> jnp.ndarray:
    """(B, R, R) → per-member pseudo-inverses, one member at a time.  A
    TPU's batched SVD does not give a member the bits its unbatched SVD
    gives, so a vmapped pinv would make a served answer depend on the
    requests it was coalesced with."""
    return jax.lax.map(jnp.linalg.pinv, v)


def _fit_batched(norm_x2, factors, lam, mlast):
    """Batched sparse fit identity (see `repro.core.cpals.fit_value`):
    ||X - X̂||² = ||X||² - 2<X, X̂> + ||X̂||², with the <X, X̂> fast path from
    the last mode's MTTKRP output — every batched kernel is exact, so the
    fast path always qualifies.  Returns (B,) fits, on device."""
    had = lam[:, :, None] * lam[:, None, :]
    for f in factors:
        had = had * _gram(f)
    norm_approx2 = jnp.sum(had, axis=(1, 2))
    inner = jnp.sum(mlast * (factors[-1] * lam[:, None, :]), axis=(1, 2))
    resid = jnp.maximum(norm_x2 - 2.0 * inner + norm_approx2, 0.0)
    return 1.0 - jnp.sqrt(resid) / jnp.maximum(jnp.sqrt(norm_x2), 1e-30)


def _diff_batched(values, mask, nnz, coords, factors, lam):
    """Nonzero-only mean |X - X̂| per member, masking the padded slots (the
    reconstruction is NOT zero at a padded slot's (0,...,0) coordinate, so
    the mask — not the padded values — keeps padding out of the metric).
    Returns (B,) on device."""
    prod = lam[:, None, :]
    for m, f in enumerate(factors):
        prod = prod * jnp.take_along_axis(f, coords[:, :, m][..., None], axis=1)
    recon = jnp.sum(prod, axis=2)
    return jnp.sum(jnp.abs(values - recon) * mask, axis=1) / jnp.maximum(nnz, 1)


def _init_batched(bucket: Bucket, rank: int, seed: int) -> list[np.ndarray]:
    """Sequential-compatible init: each member draws
    `init_factors(member.shape, rank, seed)` — byte-identical to what
    `cp_als(member, rank, seed=seed)` starts from — zero-padded to the
    bucket dims and stacked over the batch axis."""
    stacked = []
    for m, dim in enumerate(bucket.dims):
        rows = np.zeros((bucket.size, dim, rank), dtype=np.float32)
        stacked.append(rows)
    for i, t in enumerate(bucket.tensors):
        for m, f in enumerate(init_factors(t.shape, rank, seed)):
            stacked[m][i, : f.shape[0]] = np.asarray(f)
    return stacked


def cp_als_batched(
    tensors,
    rank: int,
    n_iters: int = 5,
    *,
    tune: TunePolicy | None = None,
    norm: str = "linf",
    seed: int = 0,
    track_diff: bool = False,
    plans: BucketPlanCache | None = None,
) -> list[CPResult]:
    """Decompose many small tensors with one ALS loop per bucket.

    Tensors are grouped by (shape class, nnz band) — see
    `repro.batch.bucketing` — padded within each bucket, and driven through
    a `vmap`-batched MTTKRP kernel chosen by ONE autotune decision per
    bucket (`tune=` carries the `TunePolicy`; with a `store` in the policy,
    the bucket's first-ever member probes and every later member — in any
    process — dispatches with zero probes).

    Returns one `CPResult` per input, in input order.  Per-result notes:
    `engine` is the bucket's winning batched kernel (e.g. ``"batched:ref"``),
    `tune_report` is the BUCKET's report (shared by every member of the
    bucket — `n_probes` is the bucket's total, charged once, not per
    member), and `iter_times` are bucket-level wall-clock seconds (the
    whole batch's iteration, not a per-member share).  `diff_history` is
    tracked only when `track_diff=True` (off by default — it is a
    diagnostic pass over every nonzero per iteration) and uses the
    nonzero-only metric for every member.  Convergence `tol` is not
    supported: members of one batch would converge at different iterations.

    `plans` is an optional in-process `BucketPlanCache` so repeat
    dispatches of a decided bucket skip even the store read (the serving
    loop passes a per-service cache).
    """
    policy = tune if tune is not None else TunePolicy()
    buckets = bucket_tensors(tensors)
    results: list[CPResult | None] = [None] * sum(
        b.size for b in buckets.values())
    for bucket in buckets.values():
        for idx, res in zip(bucket.indices,
                            _decompose_bucket(bucket, rank, n_iters,
                                              policy=policy, norm=norm,
                                              seed=seed,
                                              track_diff=track_diff,
                                              plans=plans), strict=True):
            results[idx] = res
    return results


def _decompose_bucket(
    bucket: Bucket,
    rank: int,
    n_iters: int,
    *,
    policy: TunePolicy,
    norm: str,
    seed: int,
    track_diff: bool,
    plans: BucketPlanCache | None,
) -> list[CPResult]:
    pb = pad_bucket(bucket)
    bucket_sp = span("cp_als_batched.bucket", dims=list(pb.dims),
                     band=pb.band, size=pb.size, rank=rank, n_iters=n_iters)
    with bucket_sp:
        engine, report = autotune_bucket(pb, rank, policy, seed=seed,
                                         plans=plans)
        bucket_sp.set(engine=report.chosen, tune_source=report.source)
        n = len(pb.dims)

        factors = [jnp.asarray(f) for f in _init_batched(bucket, rank, seed)]
        lam = jnp.ones((pb.size, rank), jnp.float32)
        values = jnp.asarray(pb.values)
        norm_x2 = jnp.sum(values * values, axis=1)
        mask = jnp.asarray(pb.mask)
        coords = jnp.asarray(pb.coords)
        nnz = jnp.asarray(pb.nnz, jnp.float32)

        fit_rows: list[np.ndarray] = []
        diff_rows: list[np.ndarray] = []
        iter_times: list[float] = []
        for it in range(n_iters):
            iter_sp = span("cp_als_batched.iter", iter=it)
            with iter_sp:
                t0 = time.perf_counter()
                mlast = None
                for mode in range(n):
                    m = engine(factors, mode)
                    v = jnp.ones((pb.size, rank, rank), jnp.float32)
                    for k in range(n):
                        if k == mode:
                            continue
                        v = v * _gram(factors[k])
                    a = jnp.matmul(m, _pinv_each(v), precision=_HIGHEST)
                    a, lam = _normalize_batched(a, norm)
                    factors[mode] = a
                    mlast = m
                # repro-lint: disable=host-sync -- timing barrier: iter_times must measure completed device work, not dispatch
                jax.block_until_ready(factors[-1])
                dt = time.perf_counter() - t0
                # Same measurement the CPResults report as iter_times.
                iter_times.append(dt)
                iter_sp.set(seconds=dt)
            fits = _fit_batched(norm_x2, factors, lam, mlast)
            fit_rows.append(np.asarray(fits))
            if track_diff:
                diffs = _diff_batched(values, mask, nnz, coords, factors,
                                      lam)
                diff_rows.append(np.asarray(diffs))

    host_factors = [np.asarray(f) for f in factors]
    host_lam = np.asarray(lam)
    out: list[CPResult] = []
    for i, t in enumerate(bucket.tensors):
        out.append(CPResult(
            factors=[host_factors[m][i, : t.shape[m]] for m in range(n)],
            lam=host_lam[i],
            fit_history=[float(row[i]) for row in fit_rows],
            diff_history=[float(row[i]) for row in diff_rows],
            iter_times=list(iter_times),
            engine=report.chosen,
            quant_error=None,
            tune_report=report,
        ))
    return out
