"""End-to-end driver #1 (the paper's kind): full CP-ALS decomposition of a
large-ish sparse tensor through the backend registry — heterogeneous
(dense-MXU + sparse), distributed (shard_map mesh), or the empirical
autotuner — with convergence tracking.

  PYTHONPATH=src python examples/decompose_tensor.py [--tensor amazon]
      [--rank 10] [--iters 5]
      [--engine auto|hetero|chunked|fixed|distributed|ref|alto|csf|pallas]
      [--store [PATH]] [--max-probes K]

`--store` persists autotune winners (default ~/.cache/repro/autotune.json,
or $REPRO_AUTOTUNE_CACHE): re-running the same decomposition skips the
probe phase.  `--max-probes` caps a cold start to the cost-model prior's
top-K candidates.

The distributed engine shards over however many devices this host exposes;
run under XLA_FLAGS=--xla_force_host_platform_device_count=8 to see real
sharding on a CPU host.
"""
import argparse
import time
from pathlib import Path

from repro.core import cp_als, decide_partition, table1_tensor
from repro.engine import (TunePolicy, backend_table, build_engine,
                          registered_backends)
from repro.launch.cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tensor", default="amazon")
    ap.add_argument("--rank", type=int, default=10)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--engine", default="auto",
                    choices=["auto", *sorted(registered_backends())])
    ap.add_argument("--store", nargs="?", const=True, default=None,
                    help="persist autotune winners (optional PATH; bare flag "
                         "uses the default store)")
    ap.add_argument("--max-probes", type=int, default=None,
                    help="cold-start probe budget (prior's top-K)")
    ap.add_argument("--list-backends", action="store_true")
    args = ap.parse_args()
    enable_compile_cache(Path(__file__).resolve().parents[1])

    if args.list_backends:
        print(backend_table(docs_base=None))  # terminal output: no link noise
        return

    st = table1_tensor(args.tensor)
    print(f"[decompose] {args.tensor}: dims={st.shape} nnz={st.nnz}")
    plan = decide_partition(st, args.rank, mem_bytes=256 * 1024,
                            rank_axis=args.rank)
    print(f"[decompose] plan: chunks={plan.chunk_shape} cap={plan.capacity}")

    t0 = time.time()
    engine = build_engine(st, args.engine, args.rank,
                          chunk_shape=plan.chunk_shape, capacity=plan.capacity,
                          tune=TunePolicy(store=args.store,
                                          max_probes=args.max_probes))
    if engine.report is not None:
        print(engine.report.summary())
        print(f"[decompose] tuning: source={engine.report.source} "
              f"probes={engine.report.n_probes} ({time.time()-t0:.2f}s)")

    t0 = time.time()
    res = cp_als(st, args.rank, n_iters=args.iters, engine=engine, seed=0)
    print(f"[decompose] engine={engine.name} iters={args.iters} "
          f"wall={time.time()-t0:.1f}s")
    for i, (f, d) in enumerate(zip(res.fit_history, res.diff_history, strict=True)):
        print(f"  iter {i+1}: fit={f:+.4f} avg|X-X̂|={d:.5f}")


if __name__ == "__main__":
    main()
