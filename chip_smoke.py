#!/usr/bin/env python3
"""Chip smoke test: CP-ALS and DecomposeService on a TPU at FROSTT size.

    python chip_smoke.py             # one chip: nell2 CP-ALS, parity, serving
    python chip_smoke.py --chips 4   # four chips: the distributed backend only

One chip.  Builds nell2 at the dims and nnz FROSTT publishes (uniform, from
`--seed`), runs `cp_als(engine="auto")` at rank 16 over every default
lossless backend, checks each backend's MTTKRP against the float32 COO
reference on the final factors, then has `DecomposeService` answer 64
concurrent requests and checks each answer against sequential `cp_als` with
the same kernel.  Four chips: the `distributed` backend on a 2x2
(data, model) mesh against the reference on one chip, plus three CP-ALS
iterations through it.

Every line before the last is a smoke timing or a check, not a metric.  The
last line is one JSON object, ``{"ok": true, "device": {...}}``.  Any failed
check, and a host where JAX finds no TPU, exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
RANK = 16
N_ITERS = 3
#: Relative Frobenius error every lossless backend must meet against the
#: float32 COO reference (the CPU format-parity gate's limit).
REL_TOL = 1e-5
#: Absolute limit on served factors against sequential cp_als with the same
#: kernel (factors are L-inf normalised): `tests/test_batch.py`'s bound.
SERVE_ATOL = 2e-5
SERVE_REQUESTS = 64

_T0 = time.perf_counter()


class SmokeFailure(Exception):
    """A check failed; the message says which."""


def log(msg: str) -> None:
    print(f"[smoke {time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileLog:
    """Counts backend compilations through JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.first_done = None

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event != self.EVENT:
            return
        self.count += 1
        self.seconds += duration
        if self.first_done is None:
            self.first_done = time.perf_counter() - _T0


def device_check(jax, chips: int):
    """Print the device and versions; fail unless `chips` TPUs are here."""
    from importlib import metadata

    import jaxlib

    from repro.launch.cache import enable_compile_cache

    devs = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    log(f"versions: jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu} python={sys.version.split()[0]}")
    log(f"compile cache: {enable_compile_cache(ROOT)}")
    check(devs[0].platform == "tpu",
          f"no TPU found: JAX reports platform {devs[0].platform!r}")
    check(len(devs) >= chips, f"{chips} chips requested, {len(devs)} found")
    return devs


def nell2(seed: int):
    from repro.core import FROSTT, random_tensor

    spec = FROSTT["nell2"]
    t0 = time.perf_counter()
    st = random_tensor(spec["shape"], spec["nnz"],
                       distribution=spec["distribution"], seed=seed)
    log(f"nell2 built: shape={st.shape} nnz={st.nnz:,} "
        f"({time.perf_counter() - t0:.1f}s on the host)")
    check(st.nnz == spec["nnz"], f"nell2 has {st.nnz} nonzeros, not {spec['nnz']}")
    return st


def rel_error(out, ref) -> float:
    import numpy as np

    out, ref = np.asarray(out, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(out - ref) / max(np.linalg.norm(ref), 1e-30))


def check_fit(fits, what: str) -> None:
    check(all(math.isfinite(f) for f in fits), f"{what}: non-finite fit {fits}")
    check(fits[-1] >= fits[0] - 1e-3, f"{what}: fit fell {fits}")


def large_phase(jax, st) -> None:
    """cp_als(engine="auto") at rank 16, then per-backend MTTKRP parity."""
    import jax.numpy as jnp

    from repro.core import cp_als
    from repro.core.partition import decide_kernel_partition
    from repro.engine import build_engine, default_plan_cache, eligible_backends
    from repro.kernels.ops import call_split

    expected = eligible_backends(lossless_only=True)
    t0 = time.perf_counter()
    res = cp_als(st, rank=RANK, n_iters=N_ITERS, engine="auto",
                 track_diff=False)
    log(f"cp_als(engine='auto', rank={RANK}, n_iters={N_ITERS}): "
        f"{time.perf_counter() - t0:.1f}s, engine={res.engine}, "
        f"iter_times={[round(t, 4) for t in res.iter_times]}")
    rep = res.tune_report
    for line in rep.summary().splitlines():
        log(f"  {line}")
    bad = {n: why for n, why in rep.skipped.items()
           if not why.startswith("pruned")}
    check(not bad, f"autotune skipped backends: {bad}")
    modes = range(st.ndim)
    untimed = {n: [m for m in modes if m not in rep.timings.get(n, {})]
               for n in expected}
    untimed = {n: ms for n, ms in untimed.items() if ms}
    check(not untimed, f"backends not timed on every mode: {untimed}")
    log(f"fit history: {res.fit_history}")
    check_fit(res.fit_history, "cp_als auto")

    cs, cap = decide_kernel_partition(st.shape, st.nnz)
    ct = default_plan_cache.chunked(st, cs, cap)
    calls, per = call_split(ct.num_tasks, st.ndim)
    log(f"pallas plan: chunk={cs} P={cap} T={ct.num_tasks:,} "
        f"calls/mode={calls} tasks/call={per}")
    del ct

    factors = [jnp.asarray(f) for f in res.factors]
    ref = build_engine(st, "ref", RANK)
    refs = [jax.block_until_ready(ref(factors, m)) for m in modes]
    del ref
    worst = {}
    for name in expected:
        if name == "ref":
            continue
        eng = build_engine(st, name, RANK)
        errs = [rel_error(eng(factors, m), refs[m]) for m in modes]
        del eng
        worst[name] = max(errs)
        log(f"parity {name:8s} vs ref: " + " ".join(
            f"m{m}={e:.2e}" for m, e in zip(modes, errs, strict=True)))
    over = {n: e for n, e in worst.items() if not e <= REL_TOL}
    check(not over, f"relative error above {REL_TOL}: {over}")


def serve_phase() -> None:
    """DecomposeService answering concurrent requests, against cp_als."""
    import numpy as np

    from benchmarks.serve_bench import matched_sequential, synthetic_load
    from repro.serve import DecomposeService

    tensors = synthetic_load(SERVE_REQUESTS, seed=0)
    t0 = time.perf_counter()
    with DecomposeService(rank=5, n_iters=3, max_batch=64,
                          max_wait_ms=5.0) as svc:
        futures = [svc.submit(t) for t in tensors]
        results = [f.result(timeout=900) for f in futures]
        stats = svc.stats()
    log(f"DecomposeService: {len(results)} requests in "
        f"{time.perf_counter() - t0:.1f}s, batches={stats.n_batches}, "
        f"buckets={stats.n_buckets}, max_batch_seen={stats.max_batch_seen}, "
        f"failed={stats.n_failed}, request_ms={stats.request_ms}")
    check(stats.n_failed == 0, f"{stats.n_failed} requests failed")
    t0 = time.perf_counter()
    seq = matched_sequential(tensors, results)
    worst_f = worst_l = 0.0
    for rb, rs in zip(results, seq, strict=True):
        for fb, fs in zip(rb.factors, rs.factors, strict=True):
            worst_f = max(worst_f, float(np.max(np.abs(fb - fs))))
        worst_l = max(worst_l, float(np.max(
            np.abs(rb.lam - rs.lam) / np.maximum(np.abs(rs.lam), 1e-30))))
    log(f"served vs sequential cp_als ({time.perf_counter() - t0:.1f}s): "
        f"max |dfactor|={worst_f:.2e} max rel |dlambda|={worst_l:.2e}")
    check(worst_f <= SERVE_ATOL and worst_l <= SERVE_ATOL,
          f"served results differ from sequential cp_als beyond "
          f"{SERVE_ATOL}: factors {worst_f:.3g}, lambda {worst_l:.3g}")


def distributed_phase(jax, st) -> None:
    """The `distributed` backend over a 2x2 mesh against ref on one chip."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import cp_als, init_factors
    from repro.core.blocked import mttkrp_coo_blocked
    from repro.core.distributed import DistributedMTTKRP, distributed_mttkrp_fn
    from repro.engine import EngineContext
    from repro.launch.mesh import make_mesh_compat

    mesh = make_mesh_compat((2, 2), ("data", "model"))
    ctx = EngineContext(st=st, rank=RANK, mesh=mesh)
    t0 = time.perf_counter()
    ct = ctx.chunked()
    dmt = DistributedMTTKRP(mesh, ct, RANK, reduce="psum_scatter")
    log(f"chunked + placed on the mesh: chunk={ct.chunk_shape} "
        f"T={ct.num_tasks} P={ct.capacity} ({time.perf_counter() - t0:.1f}s)")
    for name in ("task_chunk", "coords_rel", "values"):
        arr = getattr(dmt, name)
        check(len(arr.sharding.device_set) == 4,
              f"{name} spans {len(arr.sharding.device_set)} devices, not 4")

    factors = [jnp.asarray(f) for f in init_factors(st.shape, RANK, seed=1)]
    one_chip = jax.devices()[0]
    coords = jax.device_put(st.coords, one_chip)
    values = jax.device_put(st.values, one_chip)
    sharded = tuple(jax.device_put(f, NamedSharding(mesh, P(None, "model")))
                    for f in factors)
    for mode in range(st.ndim):
        fn, _ = distributed_mttkrp_fn(
            mesh, mode=mode, chunk_shape=dmt.ct.chunk_shape,
            out_dim=st.shape[mode], reduce="psum_scatter")
        compiled = fn.lower(sharded, dmt.task_chunk, dmt.coords_rel,
                            dmt.values).compile()
        hlo = compiled.as_text()
        check("reduce-scatter" in hlo or "all-reduce" in hlo,
              f"mode {mode}: no reduce-scatter or all-reduce in the HLO")
        t0 = time.perf_counter()
        out = jax.block_until_ready(
            compiled(sharded, dmt.task_chunk, dmt.coords_rel, dmt.values))
        dt = time.perf_counter() - t0
        check(len(out.sharding.device_set) == 4,
              f"mode {mode}: output spans {len(out.sharding.device_set)} "
              "devices, not 4")
        ref = mttkrp_coo_blocked(tuple(jax.device_put(f, one_chip)
                                       for f in factors),
                                 coords, values, mode=mode,
                                 out_dim=st.shape[mode])
        err = rel_error(out[: st.shape[mode]], ref)
        log(f"distributed mode {mode}: {dt:.3f}s, output sharding "
            f"{out.sharding.spec} on {len(out.sharding.device_set)} devices, "
            f"collective {'reduce-scatter' if 'reduce-scatter' in hlo else 'all-reduce'}, "
            f"rel err vs ref on one chip {err:.2e}")
        check(err <= REL_TOL, f"mode {mode}: relative error {err:.3g} > {REL_TOL}")
    del dmt, coords, values

    t0 = time.perf_counter()
    res = cp_als(st, rank=RANK, n_iters=N_ITERS, engine="distributed",
                 track_diff=False, mesh=mesh)
    log(f"cp_als(engine='distributed') on the 2x2 mesh: "
        f"{time.perf_counter() - t0:.1f}s, iter_times="
        f"{[round(t, 4) for t in res.iter_times]}, fit={res.fit_history}")
    check_fit(res.fit_history, "cp_als distributed")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: CP-ALS + serving on one chip (default); "
                         "4: the distributed backend on a 2x2 mesh only")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated tensor")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro checkout around {ROOT}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax

    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        devs = device_check(jax, args.chips)
        phases = ([("distributed", lambda: distributed_phase(jax, nell2(args.seed)))]
                  if args.chips == 4 else
                  [("large", lambda: large_phase(jax, nell2(args.seed))),
                   ("serve", serve_phase)])
        for name, run in phases:
            t0 = time.perf_counter()
            run()
            _release_caches()
            log(f"phase {name}: ok in {time.perf_counter() - t0:.1f}s")
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    stats = devs[0].memory_stats() or {}
    log(f"compilations: {compiles.count} taking {compiles.seconds:.1f}s; "
        f"first finished {compiles.first_done}s after start")
    log(f"peak_bytes_in_use (device 0): {stats.get('peak_bytes_in_use')}")
    log(f"total wall: {time.perf_counter() - _T0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


def _release_caches() -> None:
    """Drop the device-resident layouts of the process-wide caches."""
    from repro.engine import default_plan_cache
    from repro.formats.convert import default_format_cache

    default_plan_cache.clear()
    default_format_cache.clear()


if __name__ == "__main__":
    sys.exit(main())
