"""Benchmark entrypoint: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--fast] [--only fig6,fig7,...]
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced nnz/iters (CI mode)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of suites to run; unknown "
                         "names abort before any suite runs")
    ap.add_argument("--accuracy-budget", type=float, default=None,
                    help="max per-mode MTTKRP relative error for the fig6 "
                         "format-autotuning rows: admits fixed-point preset "
                         "candidates to the tuner, each policed against "
                         "this budget (CI gates on the resulting "
                         "fig6.json rows)")
    ap.add_argument("--store", default=None,
                    help="autotune persistence store path, shared by every "
                         "suite that tunes; repeat invocations against the "
                         "same path start warm (CI gates on this).  Default: "
                         "an ephemeral per-invocation store, so benchmark "
                         "numbers never depend on hidden machine state")
    args = ap.parse_args()

    import tempfile

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))

    from repro.engine import TuningStore

    from . import fig6, fig7, fig8_9, table1
    # One store for the whole benchmark invocation: a suite that autotunes
    # warms the next, and a repeat invocation against the same --store path
    # starts warm (reported as cold-vs-warm tuning overhead in fig7's rows;
    # CI gates on it).  Without --store the store is ephemeral — benchmarks
    # must be reproducible from the checkout alone, so they never read or
    # write the user-global cache implicitly.
    store_path = args.store or os.path.join(
        tempfile.mkdtemp(prefix="repro-bench-"), "autotune.json")
    store = TuningStore(store_path)
    suites = {
        "table1": lambda: table1.run(),
        "fig6": lambda: fig6.run(fast=args.fast,
                                 accuracy_budget=args.accuracy_budget),
        "fig7": lambda: fig7.run(fast=args.fast, store=store),
        "fig8_9": lambda: fig8_9.run(fast=args.fast),
    }
    # Validate the whole --only list before running anything: a typo'd name
    # ("fig8" for "fig8_9", a stray comma) must abort with the valid names,
    # not silently run the recognizable subset and exit 0.
    only = ([t.strip() for t in args.only.split(",")] if args.only
            else list(suites))
    unknown = sorted({repr(n) for n in only if n not in suites})
    if unknown:
        print(f"unknown benchmark suite(s): {', '.join(unknown)}; "
              f"valid names: {', '.join(sorted(suites))}", file=sys.stderr)
        sys.exit(2)
    failed = []
    for name in only:
        print(f"\n######## benchmarks.{name} ########", flush=True)
        t0 = time.time()
        try:
            suites[name]()
            print(f"######## {name} done in {time.time()-t0:.1f}s ########",
                  flush=True)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        # Non-zero exit so CI gates on benchmark health.
        print(f"benchmark suites failed: {failed}", file=sys.stderr)
        sys.exit(1)

    # What the invocation left behind for the next one: the store's entries
    # are both warm-start winners and the calibration's training data.
    obs = store.observations()
    if obs:
        from repro.engine import CalibratedPrior, CalibrationError, default_prior, ranking_accuracy
        line = (f"autotune store {store.path}: {len(store)} entries, "
                f"{len(obs)} observations")
        try:
            calib = CalibratedPrior.from_store(store)
            ch, total = ranking_accuracy(store, calib)
            dh, _ = ranking_accuracy(store, default_prior)
            line += (f"; calibrated prior rel err "
                     f"{calib.calibration.mean_rel_err:.0%}, top-1 "
                     f"{ch}/{total} (default prior {dh}/{total})")
        except CalibrationError as e:
            line += f"; calibration unavailable ({e})"
        print(line, flush=True)
    print(f"\nall benchmark suites passed: {only}", flush=True)


if __name__ == "__main__":
    main()
