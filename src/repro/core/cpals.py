"""Full CP-ALS (paper Algorithm 1) with pluggable MTTKRP engines.

Everything except MTTKRP — gram matrices, Hadamard products, the pseudo-
inverse solve, normalization, convergence — runs in float on the host side,
exactly as the paper leaves them on the CPU.  The MTTKRP engine is swappable
— any name registered in `repro.engine` (see its backend registry):

  engine="ref"         plain COO (paper Fig. 1 definition)
  engine="alto"        ALTO linearized format (repro.formats.alto): one
                       bit-interleaved index serving every mode
  engine="csf"         CSF fiber trees (repro.formats.csf): interior factor
                       rows fetched once per fiber
  engine="chunked"     PRISM chunked format (float)
  engine="fixed"       PRISM chunked + paper Alg. 2 fixed point ("int7"/"int15-12")
  engine="hetero"      dense(MXU)/sparse split (paper §IV-D analogue)
  engine="pallas"      Pallas TPU kernel (kernels/ops.py), interpret on CPU
  engine="distributed" shard_map over a (data, model) mesh (paper §IV-B)
  engine="auto"        empirical autotuner: measures the eligible backends
                       per (tensor, rank, mode) and dispatches to the winner;
                       pass store=True/path/TuningStore (forwarded via
                       **engine_kwargs) to persist winners across processes,
                       max_probes=k to cap cold-start probing to the
                       cost-model prior's top-k, and prior="calibrated" to
                       fit the prior to the store's measurements (which also
                       turns on cross-mode probe elision)
  engine=callable      custom: f(factors, mode) -> (I_mode, R)

Normalization is L-infinity by default (paper §IV-C: uses the full [-1, 1]
range, which fixed point needs); L2 is available for comparison.
"""
from __future__ import annotations

import dataclasses
import math
import time
import warnings
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.metrics import default_registry
from ..obs.tracing import span
from .sptensor import SparseTensor

#: The ALS solve's matmuls run at full f32 precision: at the default, a TPU
#: rounds f32 operands to bf16 on the MXU.
_HIGHEST = jax.lax.Precision.HIGHEST

__all__ = [
    "CPResult",
    "cp_als",
    "make_engine",
    "init_factors",
    "avg_abs_diff",
    "fit_value",
    "reconstruct_nnz",
]


@dataclasses.dataclass
class CPResult:
    factors: list[np.ndarray]
    lam: np.ndarray
    fit_history: list[float]
    diff_history: list[float]
    iter_times: list[float]
    engine: str
    #: Measured MTTKRP relative error of the quantized (lossy) engine that
    #: produced the factors — the autotuner's per-mode error measurements
    #: when available, else one direct comparison against the float COO
    #: reference on the final factors.  None for exact engines.
    quant_error: float | None = None
    #: The autotuner's report (winners, timings, errors) when engine="auto"
    #: built the engine in this call; None otherwise.
    tune_report: object | None = None


def init_factors(shape, rank: int, seed: int = 0) -> list[jnp.ndarray]:
    """Random init in [0, 1) — respects the [-1, 1] fixed-point range."""
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.uniform(0, 1, size=(d, rank)).astype(np.float32)) for d in shape]


def _normalize(f: jnp.ndarray, norm: str):
    if norm == "linf":
        lam = jnp.max(jnp.abs(f), axis=0)
    elif norm == "l2":
        lam = jnp.linalg.norm(f, axis=0)
    else:
        raise ValueError(norm)
    lam = jnp.where(lam == 0, 1.0, lam)
    return f / lam, lam


def reconstruct_nnz(factors, lam, coords) -> jnp.ndarray:
    """x̂ at the given coordinates: Σ_r λ_r ∏_m F_m[c_m, r]."""
    prod = jnp.asarray(lam)[None, :]
    for m, f in enumerate(factors):
        prod = prod * jnp.asarray(f)[coords[:, m]]
    return prod.sum(axis=1)


def avg_abs_diff(st: SparseTensor, factors, lam, *, dense_limit: int = 1 << 22) -> float:
    """Paper Fig. 6 metric: mean |X - X̂| over all elements when the tensor is
    small enough, else over the nonzeros only (as done for Delicious/Lbnl).

    The dense path builds einsum subscripts from "abcdefg", so it only
    serves tensors up to 7 modes; higher orders take the nonzero-only path
    regardless of size (a small 8-D tensor must not crash on a subscript
    overrun)."""
    if math.prod(st.shape) <= dense_limit and st.ndim <= 7:
        dense = jnp.asarray(st.to_dense())
        letters = "abcdefg"[: st.ndim]
        sub = ",".join(f"{c}r" for c in letters)
        approx = jnp.einsum(f"r,{sub}->{''.join(letters)}", jnp.asarray(lam),
                            *[jnp.asarray(f) for f in factors])
        # repro-lint: disable=host-sync -- diagnostic API returning a host scalar; called once per decomposition, not per iteration
        return float(jnp.mean(jnp.abs(dense - approx)))
    approx = reconstruct_nnz(factors, lam, jnp.asarray(st.coords))
    # repro-lint: disable=host-sync -- diagnostic API returning a host scalar; called once per decomposition, not per iteration
    return float(jnp.mean(jnp.abs(jnp.asarray(st.values) - approx)))


def fit_value(st: SparseTensor, factors, lam, mlast=None, last_mode=None) -> float:
    """fit = 1 - ||X - X̂||_F / ||X||_F, using the standard sparse identity
    ||X - X̂||² = ||X||² - 2<X, X̂> + ||X̂||².

    ||X|| is computed on the tensor's first fit and kept on the tensor
    (`SparseTensor.norm`); every later fit of it reads the same float."""
    if st.norm_known:
        default_registry.counter("cp_als.fit_norm_reused").inc()
    else:
        with span("cp_als.fit_norm", nnz=int(st.nnz)):
            st.norm()
        default_registry.counter("cp_als.fit_norm_computed").inc()
    norm_x2 = st.norm() ** 2
    grams = [jnp.matmul(jnp.asarray(f).T, jnp.asarray(f), precision=_HIGHEST)
             for f in factors]
    had = jnp.asarray(lam)[:, None] * jnp.asarray(lam)[None, :]
    for g in grams:
        had = had * g
    norm_approx2 = jnp.sum(had)
    inner = (
        jnp.sum(mlast * (jnp.asarray(factors[last_mode])
                         * jnp.asarray(lam)[None, :]))
        if mlast is not None and last_mode is not None
        else jnp.dot(reconstruct_nnz(factors, lam, jnp.asarray(st.coords)),
                     jnp.asarray(st.values), precision=_HIGHEST))
    # Both reductions stay on device and fuse into ONE residual readout —
    # fit is a host scalar by contract, so exactly one sync is the floor
    # (this used to read norm_approx2 and inner back separately).
    with span("cp_als.fit_readback"):
        resid = max(float(norm_x2 - 2.0 * inner + norm_approx2), 0.0)
    return 1.0 - math.sqrt(resid) / max(math.sqrt(norm_x2), 1e-30)


# ---------------------------------------------------------------------------
# Engines — the implementations live in repro.engine (backend registry);
# make_engine survives as a thin deprecated shim over build_engine.
# ---------------------------------------------------------------------------

def make_engine(
    st: SparseTensor,
    method: str,
    rank: int,
    **options,
) -> Callable:
    """DEPRECATED: use `repro.engine.build_engine` instead.

    Builds an MTTKRP engine closure `f(factors, mode) -> (I_mode, R) f32`
    through the backend registry (same semantics as the old if/elif ladder,
    plus `"auto"` and `"distributed"`)."""
    warnings.warn(
        "make_engine is deprecated; use repro.engine.build_engine",
        DeprecationWarning, stacklevel=2)
    from ..engine import build_engine
    return build_engine(st, method, rank, **options)


# ---------------------------------------------------------------------------
# CP-ALS driver (Algorithm 1)
# ---------------------------------------------------------------------------

def _exact_mttkrp(eng) -> bool:
    """True when the engine's MTTKRP output is the exact float operand, so
    the fit fast path (inner product from `mlast`) matches the slow path.
    Lossy backends (fixed point — whether named "fixed" or as a preset
    candidate id like "fixed:int7") and lock-free collision dropping produce
    approximate MTTKRPs — their noise must not bias the reported fit, so
    they keep the factors-only slow path."""
    ctx = getattr(eng, "context", None)
    if ctx is not None and ctx.lockfree_mode:
        return False
    spec = getattr(eng, "spec", None)
    if spec is not None:
        return spec.lossless
    report = getattr(eng, "report", None)
    if report is not None:  # autotuned: every dispatched winner must be exact
        from ..engine import candidate_lossless
        return all(candidate_lossless(n) for n in set(report.winners.values()))
    return False  # bare callable: nothing is known about its output


def _lossy_winners(eng) -> list[str]:
    """The quantized candidates an engine dispatches to: the spec itself for
    an explicit lossy engine, the lossy subset of the autotuned winners."""
    spec = getattr(eng, "spec", None)
    if spec is not None:
        return [] if spec.lossless else [eng.name]
    report = getattr(eng, "report", None)
    if report is not None:
        from ..engine import candidate_lossless
        return [n for n in sorted(set(report.winners.values()))
                if not candidate_lossless(n)]
    return []


def _measured_quant_error(eng, st: SparseTensor, factors) -> float | None:
    """Measured MTTKRP relative error of a lossy engine, for CPResult.

    Prefers the autotuner's per-mode error probes (measured against the
    float reference during tuning); without them — an explicit fixed-point
    engine, or a legacy lossy candidate admitted with no budget — compares
    the engine's last-mode output against the float COO reference on the
    final factors directly."""
    lossy = _lossy_winners(eng)
    if not lossy:
        return None
    report = getattr(eng, "report", None)
    mode = st.ndim - 1
    if report is not None:
        errs = [e for n in lossy
                for e in getattr(report, "errors", {}).get(n, {}).values()]
        if errs:
            return max(errs)
        # No recorded errors (legacy lossy candidate, no budget): measure a
        # mode the lossy winner actually serves — the dispatcher may route
        # other modes to a lossless backend, whose float noise would be
        # reported as "quantization error".
        mode = max(m for m, w in report.winners.items() if w in lossy)
    jfactors = [jnp.asarray(f) for f in factors]
    from .mttkrp import mttkrp_coo
    ref = mttkrp_coo(tuple(jfactors), jnp.asarray(st.coords),
                     jnp.asarray(st.values), mode=mode, out_dim=st.shape[mode])
    out = jnp.asarray(eng(jfactors, mode))
    # repro-lint: disable=host-sync -- one-shot quant-error readout after tuning, reported on CPResult; never in the iteration loop
    return float(jnp.linalg.norm(out - ref)
                 / (jnp.linalg.norm(ref) + 1e-30))


def cp_als(
    st: SparseTensor,
    rank: int,
    n_iters: int = 5,
    *,
    engine: str | Callable = "ref",
    norm: str = "linf",
    seed: int = 0,
    track_diff: bool = True,
    tol: float | None = None,
    tune=None,
    **engine_kwargs,
) -> CPResult:
    """`tune` is a `repro.engine.TunePolicy` bundling the autotuner's knobs
    (candidates, warmup, reps, store, prior, max_probes, elide,
    elide_margin, accuracy_budget); its `accuracy_budget` (with
    engine="auto") admits fixed-point preset candidates to the autotuner,
    each held to that max per-mode MTTKRP relative error — the paper's
    Fig. 6 format trade-off made empirically, per workload.  The result's
    `quant_error` reports the measured quantization error whenever a lossy
    engine produced the factors, and the fit fast path stays disabled for
    it (quantization noise must not bias the reported fit).

    The nine tuning keywords are still accepted inside `**engine_kwargs` as
    deprecated shims (one `DeprecationWarning` per call folds them into the
    policy); the rest of `engine_kwargs` must be `build_engine` options
    (mem_bytes, chunk_shape, capacity, fixed_preset, ... — unknown keywords
    raise a `TypeError` naming the nearest valid spelling)."""
    from ..engine import validate_engine_kwargs
    from ..engine.tunepolicy import TunePolicy, split_tune_kwargs

    legacy = split_tune_kwargs(engine_kwargs)
    validate_engine_kwargs("cp_als", engine_kwargs,
                           extra=("plans", "autotune_modes"))
    policy = TunePolicy.resolve(tune, caller="cp_als", **legacy)

    n = st.ndim
    fit_history, diff_history, iter_times = [], [], []
    prev_fit = -np.inf
    decompose_sp = span("cp_als.decompose", shape=list(st.shape),
                        nnz=int(st.nnz), rank=rank, n_iters=n_iters)
    with decompose_sp:
        with span("cp_als.init", rank=rank, shape=list(st.shape)):
            factors = init_factors(st.shape, rank, seed)
            lam = jnp.ones((rank,), jnp.float32)
        if callable(engine):
            if policy.accuracy_budget is not None:
                raise ValueError(
                    "accuracy_budget only applies to engine='auto'; a prebuilt "
                    "engine has already made its format decision")
            eng = engine
            eng_name = getattr(engine, "name", None) or getattr(
                engine, "__name__", "custom")
        else:
            from ..engine import build_engine
            eng = build_engine(st, engine, rank, tune=policy, **engine_kwargs)
            eng_name = eng.name  # e.g. "chunked", "auto:hetero"
        decompose_sp.set(engine=eng_name)

        fit_fast = _exact_mttkrp(eng)
        for it in range(n_iters):
            iter_sp = span("cp_als.iter", iter=it)
            with iter_sp:
                t0 = time.perf_counter()
                mlast = None
                for mode in range(n):
                    # Mode spans bound host dispatch time only — the device
                    # barrier sits at iteration end, so a mode span closing
                    # does not mean the mode's kernels finished.
                    with span("cp_als.mode", mode=mode):
                        with span("cp_als.mttkrp", mode=mode):
                            m = eng([jnp.asarray(f) for f in factors], mode)
                        with span("cp_als.solve", mode=mode):
                            # Pseudo-inverse step:
                            # A = M (∘_{k≠mode} F_kᵀF_k)†  (Alg. 1 l.5-7)
                            v = jnp.ones((rank, rank), jnp.float32)
                            for k in range(n):
                                if k == mode:
                                    continue
                                fk = jnp.asarray(factors[k])
                                v = v * jnp.matmul(fk.T, fk, precision=_HIGHEST)
                            a = jnp.matmul(m, jnp.linalg.pinv(v),
                                           precision=_HIGHEST)
                            a, lam = _normalize(a, norm)
                            factors[mode] = a
                        mlast = m
                with span("cp_als.sync"):
                    # repro-lint: disable=host-sync -- timing barrier: iter_times must measure completed device work, not dispatch
                    jax.block_until_ready(factors[-1])
                dt = time.perf_counter() - t0
                # One measurement, two views: `iter_times` on the CPResult
                # and the span's `seconds` attr carry the same number (the
                # span's own duration adds only its bookkeeping).
                iter_times.append(dt)
                iter_sp.set(seconds=dt)

            # Fast-path fit: <X, X̂> = Σ λ_r Σ_i M[i,r]·F_last[i,r] reuses
            # the last mode's MTTKRP output (M is independent of F_last,
            # which was updated after M was computed), skipping the
            # O(nnz·R) reconstruct_nnz pass that the slow path pays every
            # iteration.  Only exact engines qualify (see _exact_mttkrp).
            with span("cp_als.fit", iter=it, fast=fit_fast):
                f = fit_value(st, factors, lam,
                              mlast=mlast if fit_fast else None,
                              last_mode=n - 1 if fit_fast else None)
            fit_history.append(f)
            if track_diff:
                diff_history.append(avg_abs_diff(st, factors, lam))
            if tol is not None and abs(f - prev_fit) < tol:
                break
            prev_fit = f
        decompose_sp.set(fit=fit_history[-1] if fit_history else None)

        with span("cp_als.readback"):
            result = CPResult(
                [np.asarray(f) for f in factors], np.asarray(lam),
                fit_history, diff_history, iter_times, eng_name,
                quant_error=_measured_quant_error(eng, st, factors),
                tune_report=getattr(eng, "report", None),
            )
    return result
