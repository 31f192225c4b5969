"""MTTKRP backend registry.

The paper's central finding is that the best spMTTKRP execution strategy is
workload-dependent — PIM wins on some tensors, CPU/heterogeneous
collaboration on others.  This registry is the seam where execution
strategies plug in: each backend registers itself with a capability
declaration, and selection (explicit name or the `auto` autotuner) goes
through one API instead of an if/elif ladder.

A backend is a *builder*: ``build(ctx: EngineContext) -> engine`` where
``engine(factors, mode) -> (I_mode, R) f32``.  Builders run once per
(tensor, rank, options); the returned closure serves every CP-ALS
iteration, with chunking shared through ``ctx.plans`` (see plan.py).
"""
from __future__ import annotations

import dataclasses
from collections.abc import Callable

import jax

from ..core.sptensor import SparseTensor
from ..formats.convert import FormatCache, default_format_cache
from .plan import PlanCache, default_plan_cache

__all__ = [
    "BackendSpec",
    "Engine",
    "EngineContext",
    "backend_table",
    "build_candidate",
    "candidate_lossless",
    "eligible_backends",
    "get_backend",
    "parse_candidate",
    "preset_candidates",
    "register_backend",
    "registered_backends",
]


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """Capability declaration for one registered execution strategy.

    needs_chunking       — consumes the PRISM chunked format (built once,
                           shared through the plan cache).
    supports_fixed_point — runs the paper's Alg.-2 Qm.n arithmetic.
    lossless             — bit-compatible with the float COO reference (up
                           to reduction order); lossy backends (fixed point)
                           are excluded from autotuning unless the caller
                           grants an explicit `accuracy_budget` — format
                           choice is an accuracy decision, and the tuner
                           only makes it against a declared error budget.
    presets              — the Qm.n fixed-point presets this backend can run
                           (`FIXED_PRESETS` names).  Each preset becomes its
                           own autotune candidate `"name:preset"` when an
                           accuracy budget admits lossy candidates.
    min_devices          — minimum jax device count to be eligible.
    """

    name: str
    build: Callable
    needs_chunking: bool = False
    supports_fixed_point: bool = False
    lossless: bool = True
    presets: tuple[str, ...] = ()
    min_devices: int = 1
    description: str = ""


_REGISTRY: dict[str, BackendSpec] = {}


def register_backend(
    name: str,
    *,
    needs_chunking: bool = False,
    supports_fixed_point: bool = False,
    lossless: bool = True,
    presets: tuple[str, ...] = (),
    min_devices: int = 1,
    description: str = "",
):
    """Decorator registering a builder under `name` (last wins, so tests
    and downstream code can override a backend)."""
    if ":" in name:
        raise ValueError(
            f"backend name {name!r} may not contain ':' — that separator is "
            "reserved for preset candidate ids (e.g. 'fixed:int7')")
    def deco(build: Callable) -> Callable:
        _REGISTRY[name] = BackendSpec(
            name=name,
            build=build,
            needs_chunking=needs_chunking,
            supports_fixed_point=supports_fixed_point,
            lossless=lossless,
            presets=tuple(presets),
            min_devices=min_devices,
            description=description,
        )
        return build
    return deco


def get_backend(name: str) -> BackendSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown engine {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def registered_backends() -> dict[str, BackendSpec]:
    return dict(_REGISTRY)


# ---------------------------------------------------------------------------
# Candidate ids: "backend" or "backend:preset"
#
# The autotuner's candidate space is (backend × fixed-point preset): a lossy
# backend contributes one candidate per Qm.n preset it declares, spelled
# "name:preset" ("fixed:int7").  These helpers are the single parser/builder
# for that spelling — the tuning store, cost model and autotuner all agree on
# it because they all come through here.
# ---------------------------------------------------------------------------

def parse_candidate(candidate: str) -> tuple[str, str | None]:
    """Split a candidate id into (backend name, preset or None), validating
    both halves against the registry."""
    name, _, preset = candidate.partition(":")
    spec = get_backend(name)
    if not preset:
        return name, None
    if preset not in spec.presets:
        raise ValueError(
            f"backend {name!r} has no preset {preset!r}; "
            f"registered presets: {list(spec.presets) or 'none'}")
    return name, preset


def candidate_lossless(candidate: str) -> bool:
    """Whether a candidate id names a lossless backend.  Unknown candidates
    count as lossy — nothing is known about their output, so accuracy-
    sensitive callers (the cp_als fit fast path) must not trust them."""
    try:
        name, _preset = parse_candidate(candidate)
    except ValueError:
        return False
    return _REGISTRY[name].lossless


def build_candidate(candidate: str, ctx: EngineContext):
    """Build a candidate id against `ctx`, overriding `ctx.fixed_preset`
    when the id pins one.  The preset-pinned context shares the plan cache
    (and therefore the chunking) with the original."""
    name, preset = parse_candidate(candidate)
    spec = _REGISTRY[name]
    if preset is not None and preset != ctx.fixed_preset:
        ctx = dataclasses.replace(ctx, fixed_preset=preset)
    return spec.build(ctx)


def preset_candidates(*, n_devices: int | None = None) -> list[str]:
    """Every lossy (backend, preset) candidate id this process could build:
    what an accuracy budget adds to the default candidate set.  Sorted by
    name so the enumeration (and everything keyed on it: probe order,
    store fingerprints, tie-breaks) is independent of registration order."""
    if n_devices is None:
        n_devices = len(jax.devices())
    return [
        f"{s.name}:{p}"
        for s in sorted(_REGISTRY.values(), key=lambda s: s.name)
        if not s.lossless and n_devices >= s.min_devices
        for p in s.presets
    ]


def eligible_backends(
    *,
    n_devices: int | None = None,
    lossless_only: bool = False,
) -> list[str]:
    """Backends whose device requirements this process satisfies, sorted by
    name — registration (import) order must not leak into probe order or
    autotune tie-breaks."""
    if n_devices is None:
        n_devices = len(jax.devices())
    return [
        s.name
        for s in sorted(_REGISTRY.values(), key=lambda s: s.name)
        if n_devices >= s.min_devices and (s.lossless or not lossless_only)
    ]


def backend_table(docs_base: str | None = "docs/candidates.md") -> str:
    """Markdown capability table (used by the README and `--help` text).

    Each backend row cites its section of the candidate-id documentation
    (`docs_base` anchors, e.g. ``docs/candidates.md#csf``), and each preset
    its entry under the preset grammar; pass ``docs_base=None`` for plain
    terminal output without link noise."""
    def _name(n: str) -> str:
        return f"[`{n}`]({docs_base}#{n})" if docs_base else f"`{n}`"

    def _preset(p: str) -> str:
        return (f"[`{p}`]({docs_base}#preset-{p})" if docs_base else f"`{p}`")

    rows = [
        "| backend | chunked | fixed-point | lossless | presets | min devices | description |",
        "|---------|---------|-------------|----------|---------|-------------|-------------|",
    ]
    for s in sorted(_REGISTRY.values(), key=lambda s: s.name):
        presets = " ".join(_preset(p) for p in s.presets) if s.presets else "—"
        rows.append(
            f"| {_name(s.name)} | {'✓' if s.needs_chunking else '—'} "
            f"| {'✓' if s.supports_fixed_point else '—'} "
            f"| {'✓' if s.lossless else '—'} "
            f"| {presets} "
            f"| {s.min_devices} | {s.description} |"
        )
    return "\n".join(rows)


# ---------------------------------------------------------------------------
# Build context + engine handle
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EngineContext:
    """Everything a builder may need, with chunking resolved lazily ONCE.

    `chunk_shape`/`capacity` default to the Fig.-5 partition decider's plan
    for (st, rank, mem_bytes); all chunk-based backends built from the same
    context therefore share one ChunkedTensor via `plans`.
    """

    st: SparseTensor
    rank: int
    mem_bytes: int | None = None
    chunk_shape: tuple[int, ...] | None = None
    capacity: int | None = None
    fixed_preset: str = "int7"
    lockfree_mode: bool = False
    dense_fraction: float | None = None
    mesh: object | None = None      # distributed backend; None → local mesh
    reduce: str = "psum"            # distributed reduction strategy
    #: pallas: interpret mode.  None resolves from the JAX backend: the
    #: interpreter only on a CPU host, the compiled kernel everywhere else.
    interpret: bool | None = None
    plans: PlanCache = dataclasses.field(default_factory=lambda: default_plan_cache)
    #: Sparse-layout cache (repro.formats): CSF trees / ALTO linearization
    #: built once per tensor and shared across backends and autotune probes,
    #: exactly as `plans` shares the chunking.
    formats: FormatCache = dataclasses.field(
        default_factory=lambda: default_format_cache)

    def __post_init__(self):
        # Validate up front: `capacity or plan.capacity` downstream would
        # silently turn an explicit 0 into the plan's value.
        if self.capacity is not None and self.capacity < 1:
            raise ValueError(
                f"capacity must be >= 1 nonzero slot per chunk task (got "
                f"{self.capacity}); pass capacity=None to let the partition "
                "decider choose")
        if self.interpret is None:
            self.interpret = jax.default_backend() == "cpu"

    def resolve_chunking(self) -> tuple[tuple[int, ...], int | None]:
        """chunk_shape/capacity, from the partition decider where unset.
        The context itself is left as the caller built it, so a builder
        with its own plan (`pallas`) can tell explicit options apart."""
        if self.chunk_shape is not None:
            return self.chunk_shape, self.capacity
        plan = self.plans.plan(
            self.st, self.rank,
            mem_bytes=self.mem_bytes or 64 * 1024 * 1024)
        return (plan.chunk_shape,
                self.capacity if self.capacity is not None else plan.capacity)

    def chunked(self):
        cs, cap = self.resolve_chunking()
        return self.plans.chunked(self.st, cs, cap)

    def device_arrays(self) -> dict:
        cs, cap = self.resolve_chunking()
        return self.plans.device_arrays(self.st, cs, cap)


class Engine:
    """Callable engine handle: `engine(factors, mode) -> (I_mode, R)`.

    Carries the metadata CP-ALS and the benchmarks report on (`name`), plus
    the build context and — for autotuned engines — the timing report.
    """

    def __init__(self, name: str, fn: Callable, *, spec: BackendSpec | None = None,
                 context: EngineContext | None = None, report=None):
        self.name = name
        self._fn = fn
        self.spec = spec
        self.context = context
        self.report = report

    def __call__(self, factors, mode: int):
        return self._fn(factors, mode)

    def __repr__(self) -> str:
        return f"Engine({self.name!r})"
