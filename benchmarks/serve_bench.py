"""Serving benchmark: batched decomposition service vs a sequential loop.

Drives synthetic concurrent load — many small sparse tensors across a few
(shape class, nnz band) buckets — through three paths:

  sequential — `cp_als` one tensor at a time (the pre-batching baseline);
  batched    — one `cp_als_batched` call over the whole set;
  service    — `DecomposeService` under concurrent client threads, with
               request coalescing, measuring per-request latency.

Reports throughput (tensors/s), p50/p99 request latency for the service
path, per-path probe counts, and per-tensor factor parity between the
batched and sequential paths (gated at 1e-5).  JSON lands in
`results/bench/serve_bench.json`; CI's `serve-smoke` job runs this twice
against one store and gates on the second (warm) run reporting zero probes.

  PYTHONPATH=src python -m benchmarks.serve_bench --fast \
      --store "$TMPDIR/serve-store.json"
"""
from __future__ import annotations

import argparse
import os
import threading
import time

import numpy as np

from repro.batch import BucketPlanCache, cp_als_batched
from repro.core import SparseTensor, cp_als
from repro.engine import TunePolicy
from repro.launch.cache import enable_compile_cache
from repro.obs import (
    enable_tracing,
    get_tracer,
    read_jsonl,
    summarize_text,
    write_jsonl,
)
from repro.serve import DecomposeService

from .common import save, table

RANK = 5
N_ITERS = 3


def synthetic_load(n: int, seed: int = 0) -> list[SparseTensor]:
    """`n` small tensors drawn from three shape/nnz families, shuffled — the
    arrival order interleaves buckets the way concurrent users would."""
    rng = np.random.default_rng(seed)
    families = [
        ((12, 10, 8), (40, 70)),     # 3-D, band 5/6
        ((16, 16, 16), (90, 120)),   # pow-2 dims, band 6
        ((24, 24), (50, 60)),        # 2-D, band 5
    ]
    tensors = []
    for i in range(n):
        shape, (lo, hi) = families[i % len(families)]
        nnz = int(rng.integers(lo, hi))
        coords = np.stack([rng.integers(0, d, size=nnz) for d in shape],
                          axis=1).astype(np.int32)
        values = rng.uniform(-1, 1, size=nnz).astype(np.float32)
        tensors.append(SparseTensor(coords, values, shape))
    order = rng.permutation(n)
    return [tensors[i] for i in order]


def _probes(results) -> int:
    """Total autotune probes across the unique bucket reports."""
    reports = {id(r.tune_report): r.tune_report
               for r in results if r.tune_report is not None}
    return sum(rep.n_probes for rep in reports.values())


def run_sequential(tensors, tune: TunePolicy):
    t0 = time.perf_counter()
    results = [cp_als(t, RANK, n_iters=N_ITERS, engine="ref",
                      track_diff=False) for t in tensors]
    wall = time.perf_counter() - t0
    return results, dict(path="sequential", wall_s=wall,
                         throughput=len(tensors) / wall, n_probes=0)


def run_batched(tensors, tune: TunePolicy):
    # Warm-up on a tiny disjoint load first so the row measures steady-state
    # dispatch, not one-time jit compilation of the batched kernels.
    t0 = time.perf_counter()
    results = cp_als_batched(tensors, RANK, n_iters=N_ITERS, tune=tune,
                             plans=BucketPlanCache())
    wall = time.perf_counter() - t0
    return results, dict(path="batched", wall_s=wall,
                         throughput=len(tensors) / wall,
                         n_probes=_probes(results))


def run_service(tensors, tune: TunePolicy, *, max_batch: int,
                max_wait_ms: float, clients: int):
    """Concurrent load: `clients` threads each submit a slice of the
    tensors and wait; per-request latency is submit→result."""
    latencies = [0.0] * len(tensors)
    with DecomposeService(RANK, N_ITERS, tune=tune, max_batch=max_batch,
                          max_wait_ms=max_wait_ms) as svc:
        t0 = time.perf_counter()

        def client(idxs):
            for i in idxs:
                ts = time.perf_counter()
                svc.decompose(tensors[i], timeout=600)
                latencies[i] = time.perf_counter() - ts

        threads = [threading.Thread(target=client,
                                    args=(range(c, len(tensors), clients),))
                   for c in range(clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = time.perf_counter() - t0
        stats = svc.stats()
    lat = np.asarray(latencies)
    return dict(path="service", wall_s=wall,
                throughput=len(tensors) / wall,
                p50_ms=float(np.percentile(lat, 50) * 1e3),
                p99_ms=float(np.percentile(lat, 99) * 1e3),
                n_probes=stats.n_probes,
                n_batches=stats.n_batches,
                n_buckets=stats.n_buckets,
                max_batch_seen=stats.max_batch_seen,
                bucket_decisions=stats.n_bucket_decisions,
                # Service-side histogram estimates (submit→dispatch and
                # submit→result), alongside the client-measured percentiles.
                svc_queue_wait_ms=stats.queue_wait_ms,
                svc_request_ms=stats.request_ms)


def parity(batched, sequential) -> float:
    worst = 0.0
    for rb, rs in zip(batched, sequential, strict=True):
        for fb, fs in zip(rb.factors, rs.factors, strict=True):
            worst = max(worst, float(np.max(np.abs(fb - np.asarray(fs)))))
        worst = max(worst, float(np.max(np.abs(rb.lam - np.asarray(rs.lam)))))
    return worst


def matched_sequential(tensors, batched_results):
    """Per-tensor sequential `cp_als` runs using the SAME kernel the batched
    path picked for that tensor's bucket — the parity gate compares
    like-for-like (the batched kernels are vmapped versions of the
    sequential ones, equal member-wise to float tolerance; comparing a batched-ALTO result
    against sequential-COO would only measure ALTO's different summation
    order, which the sequential path exhibits identically)."""
    from repro.engine import build_engine
    out = []
    for t, rb in zip(tensors, batched_results, strict=True):
        names = {w.removeprefix("batched:")
                 for w in rb.tune_report.winners.values()}
        if len(names) == 1:
            engine = names.pop()
        else:  # per-mode mixed winners: route each mode to its kernel
            per_mode = {m: build_engine(t, w.removeprefix("batched:"), RANK)
                        for m, w in rb.tune_report.winners.items()}
            def engine(factors, mode, _e=per_mode):
                return _e[mode](factors, mode)
        out.append(cp_als(t, RANK, n_iters=N_ITERS, engine=engine,
                          track_diff=False))
    return out


def run(n: int, *, store, max_batch: int, max_wait_ms: float, clients: int,
        seed: int = 0):
    tune = TunePolicy(store=store)
    tensors = synthetic_load(n, seed=seed)
    # One throwaway batched pass over a tiny prefix compiles the vmap'd
    # kernels so neither timed path pays one-time jit cost.
    cp_als_batched(tensors[: min(3, n)], RANK, n_iters=1)

    seq_results, seq_row = run_sequential(tensors, tune)
    bat_results, bat_row = run_batched(tensors, tune)
    svc_row = run_service(tensors, tune, max_batch=max_batch,
                          max_wait_ms=max_wait_ms, clients=clients)

    worst = parity(bat_results, matched_sequential(tensors, bat_results))
    bat_row["parity_max_abs"] = worst
    rows = [seq_row, bat_row, svc_row]
    bucket_reports = {id(r.tune_report): r.tune_report
                      for r in bat_results if r.tune_report is not None}
    payload = dict(
        n_tensors=n, rank=RANK, n_iters=N_ITERS,
        max_batch=max_batch, max_wait_ms=max_wait_ms, clients=clients,
        parity_max_abs=worst, parity_ok=worst <= 1e-5,
        batched_speedup=seq_row["wall_s"] / bat_row["wall_s"],
        bucket_reports=[rep.to_dict() for rep in bucket_reports.values()],
        rows=rows,
    )
    print(table([{k: (f"{v:.4g}" if isinstance(v, float) else v)
                  for k, v in r.items()}
                 for r in rows],
                ["path", "wall_s", "throughput", "p50_ms", "p99_ms",
                 "n_probes", "parity_max_abs"]))
    print(f"[serve_bench] batched speedup over sequential: "
          f"{payload['batched_speedup']:.2f}x; parity {worst:.2e} "
          f"({'OK' if payload['parity_ok'] else 'FAIL'})")
    return payload


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=64,
                    help="number of synthetic tensors (default 64)")
    ap.add_argument("--fast", action="store_true",
                    help="pruned load for CI (24 tensors, 2 clients)")
    ap.add_argument("--store", default=None,
                    help="TuningStore path shared across runs (warm gating)")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=20.0)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable span tracing and write the trace JSONL "
                         "here (see docs/observability.md)")
    args = ap.parse_args(argv)
    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    n = 24 if args.fast else args.n
    # Closed-loop clients: each waits for its result before submitting the
    # next request, so client concurrency caps the coalesced batch size —
    # the service's throughput ceiling on this synthetic load is set by the
    # load generator, not the coalescer.
    clients = 2 if args.fast else args.clients
    if args.trace:
        enable_tracing()
    payload = run(n, store=args.store, max_batch=args.max_batch,
                  max_wait_ms=args.max_wait_ms, clients=clients,
                  seed=args.seed)
    path = save("serve_bench", payload)
    print(f"[serve_bench] wrote {path}")
    if args.trace:
        tracer = get_tracer()
        trace_path = write_jsonl(tracer.spans(), args.trace, tracer=tracer)
        print(f"[serve_bench] wrote {trace_path} ({len(tracer)} spans)")
        print(summarize_text(*read_jsonl(trace_path)))
    if not payload["parity_ok"]:
        raise SystemExit("parity gate failed")
    return payload


if __name__ == "__main__":
    main()
