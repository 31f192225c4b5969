#!/usr/bin/env python3
"""Chip benchmark of CP-ALS on FROSTT-sized sparse tensors.

    python bench/run.py --workload nell2.r16.pallas --seed 7 --seconds 30 --trace 0

One run is one process on one TPU.  It finds the cell in `BENCHMARK.json`,
generates the configuration's tensor (`configs/<config>.json`) with the
values drawn from `--seed`, builds the traffic's engine
(`traffic/<traffic>.json`) and warms it up with one whole decomposition:
that is set-up.  The window then runs whole `cp_als` decompositions, each
from its own initial factors, until `--seconds` have passed.  Afterwards a
decomposition drawn from the seed is held against the plain reference
(`check.py`, limits in `workloads/<cell>.json`).

`--trace 0` reports the cell's end-to-end metrics; `--trace 1` its per-layer
metrics, from a profiler trace of each mode's MTTKRP on the warmed engine
and of one more decomposition.  Each metric is computed by its
own reader, `metrics/<name>.py`.  The last line of standard output is one
JSON object; the last lines of standard error give each compared number
beside its limit.  Without a TPU the run prints no result and exits 2.

`--control` puts the reference, computed in bfloat16, in the program's place
(see `reference.py`): it gives the control's readings, and its runs are
expected to come out not correct.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import check, roofline  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.generate import generate, rng_for  # noqa: E402
from bench.reference import Reference, als  # noqa: E402

#: The traced calls of one mode's MTTKRP last at least this long together.
MIN_TIMED_S = 0.3


class SetupError(Exception):
    """The run cannot start: no chip, or a cell the files do not describe."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _in_cell(metric: dict, name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def load_cell(benchmark: Path, data: Path, name: str) -> Cell:
    spec = json.loads(benchmark.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r} in {benchmark}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=json.loads((benchmark.parent / configs[w["config"]]["file"]).read_text()),
        traffic=json.loads((data / "traffic" / f"{w['traffic']}.json").read_text()),
        workload=json.loads((data / "workloads" / f"{name}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _in_cell(m, name)],
        per_layer=[m for m in spec["per_layer"] if _in_cell(m, name)],
    )


class CompileLog:
    """Counts backend compilations through JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1


@dataclasses.dataclass
class Observations:
    """Everything a metric reader may read (see `metrics/`)."""

    shape: tuple[int, ...]
    nnz: int
    rank: int
    peaks: dict
    setup_s: float
    window_s: float
    iterations: int
    window_compiles: int
    layout_build_s: float
    mode_s: list[float] | None = None     # device seconds per mode's MTTKRP (traced runs)
    trace: tr.Summary | None = None       # one traced decomposition (traced runs)


def read_metric(name: str, obs: Observations):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(obs)


def init_seed(seed: int, k: int) -> int:
    """The initial-factor seed of the window's `k`-th decomposition (-1: warm-up)."""
    return int(rng_for(seed, 3, k + 1).integers(1 << 62))


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T0:8.2f}s] {msg}", file=sys.stderr, flush=True)


def device_check(jax, chips: int, require_tpu: bool):
    devs = jax.devices()
    log(f"devices: {len(devs)} x {devs[0].platform} ({devs[0].device_kind})")
    if require_tpu and devs[0].platform != "tpu":
        raise SetupError(f"no TPU: JAX reports platform {devs[0].platform!r}")
    if require_tpu and len(devs) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs


def traced_pass(system, factors, seed: int, keep: Path | None
                ) -> tuple[list[float] | None, tr.Summary]:
    """Profile each mode's MTTKRP on the warmed engine, then one whole
    decomposition.  Returns the device seconds per call of each mode's
    MTTKRP (busy time inside the mode's annotation over its calls; None
    where the trace holds no device op) and the decomposition's summary."""
    import jax
    import jax.numpy as jnp

    from repro.obs.tracing import capture, get_tracer

    factors = [jnp.asarray(f) for f in factors]
    reps = []
    for m in range(len(factors)):  # one untraced call sizes the repeats
        t0 = time.perf_counter()
        jax.block_until_ready(system.mttkrp(factors, m))
        reps.append(max(1, math.ceil(MIN_TIMED_S / max(time.perf_counter() - t0, 1e-6))))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            for m, n in enumerate(reps):
                with jax.profiler.TraceAnnotation(f"bench.mttkrp.mode{m}"):
                    for _ in range(n):
                        res = system.mttkrp(factors, m)
                    jax.block_until_ready(res)
            with jax.profiler.TraceAnnotation("bench.traced_window"):
                pc0 = time.perf_counter()
                with capture() as spans, jax.profiler.TraceAnnotation("bench.decompose"):
                    system.decompose(seed)
        finally:
            jax.profiler.stop_trace()
        events = tr.load(d)
    named = {e.name: e for e in events if e.name.startswith("bench.")}
    mode_s = [tr.busy_seconds(events, (a.start_ns, a.end_ns)) / n
              for a, n in ((named[f"bench.mttkrp.mode{m}"], n) for m, n in enumerate(reps))]
    window = named["bench.traced_window"]
    # The program's spans are perf_counter offsets: put them on the trace's clock.
    shift = window.start_ns - int(pc0 * 1e9)
    epoch = get_tracer().epoch_mono
    host = [e for e in events if e.name.startswith("bench.")]
    host += [tr.Event("program", "spans", s.name, int((epoch + s.t_start) * 1e9) + shift,
                      int((epoch + s.t_start + s.duration) * 1e9) + shift) for s in spans]
    if keep is not None:
        keep.mkdir(parents=True, exist_ok=True)
        kept = tr.device_ops(events) + host
        (keep / "trace_events.json").write_text(json.dumps([e.to_json() for e in kept]))
    summary = tr.summarize(events, (window.start_ns, window.end_ns), host)
    return (mode_s if all(t > 0 for t in mode_s) else None), summary


def run(args, cell: Cell, *, require_tpu: bool, compile_cache: Path | None) -> dict:
    import jax

    if compile_cache is not None:
        jax.config.update("jax_compilation_cache_dir", str(compile_cache))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    devs = device_check(jax, cell.chips, require_tpu)
    kind = devs[0].device_kind
    try:
        peaks = roofline.peaks_for(kind) if require_tpu else {}
    except KeyError as e:
        raise SetupError(e.args[0]) from None

    from bench.systems import Control, Program

    cfg, traffic = cell.config, cell.traffic
    shape, rank, n_iters = tuple(cfg["shape"]), traffic["rank"], traffic["n_iters"]
    t0 = time.perf_counter()
    coords, values = generate(cfg, args.seed)
    log(f"generated {cfg['name']}: shape={shape} nnz={coords.shape[0]:,} "
        f"({time.perf_counter() - t0:.2f}s)")
    if args.control:
        system = Control(coords, values, shape, traffic, "bfloat16")
    else:
        system = Program(coords, values, shape, traffic)
    log(f"engine {traffic['engine']!r} built in {system.layout_build_s:.2f}s")
    warm = system.decompose(init_seed(args.seed, -1))
    log(f"warm-up decomposition done, fits {warm.fits!r}")
    del warm

    w0 = time.perf_counter()
    setup_s = w0 - T0
    c0 = compiles.count
    attempted = failed = done = 0
    sample = sample_seed = None
    pick = rng_for(args.seed, 5)
    while True:
        seed_k = init_seed(args.seed, attempted)
        attempted += 1
        try:
            d = system.decompose(seed_k)
        except Exception:  # a failed decomposition is counted, and the run goes on
            traceback.print_exc()
            d = None
        if d is None or not d.finite():
            failed += 1
        else:
            done += 1
            if pick.integers(done) == 0:  # reservoir: each finished one equally likely
                sample, sample_seed = d, seed_k
        if time.perf_counter() - w0 >= args.seconds:
            break
    window_s = time.perf_counter() - w0
    window_compiles = compiles.count - c0
    log(f"window: {attempted} decompositions ({failed} failed) in {window_s:.3f}s, "
        f"{window_compiles} compilations")

    obs = Observations(shape, int(coords.shape[0]), rank, peaks, setup_s,
                       window_s, done * n_iters, window_compiles, system.layout_build_s)
    if args.trace and sample is not None:
        obs.mode_s, obs.trace = traced_pass(system, sample.factors,
                                            init_seed(args.seed, attempted), args.keep_trace)
        log(f"MTTKRP per mode (device trace): {obs.mode_s}")
        log(f"traced decomposition: busy {obs.trace.busy_s!r}s of {obs.trace.window_s!r}s; "
            f"device ops {obs.trace.device_ops}; idle gaps {obs.trace.idle_gaps}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)

    numbers = dict.fromkeys(check.NUMBERS, math.inf)
    if sample is not None:
        outs = [np.asarray(system.mttkrp(sample.factors, m)) for m in range(len(shape))]
        system.close()
        t0 = time.perf_counter()
        ref = Reference.put(coords, values, shape)
        ref_outs = [ref.mttkrp(sample.factors, m) for m in range(len(shape))]
        norm_x = float(np.linalg.norm(values.astype(np.float64)))
        r = als(ref, norm_x, rank, n_iters, sample_seed)
        numbers = check.gaps(outs, ref_outs, sample.factors, r.factors, sample.lam, r.lam,
                             sample.fits, r.fits)
        log(f"reference: {time.perf_counter() - t0:.2f}s; fits {sample.fits!r} "
            f"against the reference's {r.fits!r}")
    limits = cell.workload["limits"]
    correct = check.judge(numbers, limits, attempted, failed)

    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = read_metric(m["name"], obs)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace and obs.trace is not None:
        device.update(busy_s=obs.trace.busy_s, window_s=obs.trace.window_s)
        result["breakdown"] = {"device_ops": obs.trace.device_ops,
                               "idle_gaps": obs.trace.idle_gaps}
    # A number that could not be read (no decomposition finished) prints as null.
    result["checks"] = {k: {"value": numbers[k] if math.isfinite(numbers[k]) else None,
                            "limit": limits[k]} for k in check.NUMBERS}
    return result


def main(argv=None, *, benchmark: Path = ROOT / "BENCHMARK.json", data: Path = BENCH,
         require_tpu: bool = True, compile_cache: Path | None = ROOT / ".jax_cache") -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="a cell of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True,
                    help="draws the tensor's values and every decomposition's start")
    ap.add_argument("--seconds", type=float, required=True, help="length of the window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="0: end-to-end metrics; 1: per-layer metrics")
    ap.add_argument("--control", action="store_true",
                    help="run the reference in bfloat16 in the program's place")
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="write the traced decomposition's device ops and spans here")
    args = ap.parse_args(argv)
    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise SetupError(f"no system under test: {ROOT / 'src' / 'repro'} is missing")
        sys.path.insert(0, str(ROOT / "src"))
        cell = load_cell(benchmark, data, args.workload)
        result = run(args, cell, require_tpu=require_tpu, compile_cache=compile_cache)
    except (SetupError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
