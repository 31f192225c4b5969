"""repro — PRISM sparse-MTTKRP tensor decomposition, reproduced on JAX.

The supported product surface, re-exported from the subsystems:

- `repro.core`    — `SparseTensor`, CP-ALS (`cp_als`), the MTTKRP kernels'
                    reference implementations, fixed-point `QFormat`s.
- `repro.engine`  — `build_engine`/`autotune_engine` (backend registry,
                    persistent autotuner, calibrated cost prior) and
                    `TunePolicy`, the one bundle of tuning knobs every
                    tuning-aware entry point accepts as `tune=`.
- `repro.formats` — pluggable sparse layouts (COO/CSF/ALTO) + `FormatStats`.
- `repro.sweep`   — offline design-space sweeps shipping warm tuning stores.
- `repro.batch`   — many-small-tensor batched CP-ALS (`cp_als_batched`):
                    bucket by (shape class, nnz band), vmap the kernel, one
                    autotune decision per bucket.
- `repro.serve`   — `DecomposeService`, the coalescing request loop over
                    the batched path.
- `repro.obs`     — span tracing (`span`/`traced`/`enable_tracing`) and
                    `MetricsRegistry` counters and histograms, wired
                    through the tune/decompose/serve stack; traces export
                    to Perfetto (docs/observability.md).

Everything importable from `repro` directly is API; subpackages not
re-exported here (`repro.models`, `repro.configs`, the LM launch/optim/data
stack) are quarantined growth-seed scaffolding kept only for their seed
tests — see docs/static-analysis.md#import-orphans.
"""
from __future__ import annotations

from repro.batch import cp_als_batched
from repro.core import (
    TABLE1,
    CPResult,
    QFormat,
    SparseTensor,
    cp_als,
    random_tensor,
    table1_tensor,
)
from repro.engine import (
    AutotuneReport,
    TunePolicy,
    TuningStore,
    autotune_engine,
    build_engine,
    register_backend,
    registered_backends,
)
from repro.formats import (
    FormatCache,
    FormatStats,
    register_format,
    registered_formats,
)
from repro.obs import (
    MetricsRegistry,
    enable_tracing,
    get_tracer,
    span,
    traced,
)
from repro.serve import DecomposeService
from repro.sweep import SweepConfig, load_config, pareto_report, run_sweep

__all__ = [
    "TABLE1",
    "AutotuneReport",
    "CPResult",
    "DecomposeService",
    "FormatCache",
    "FormatStats",
    "MetricsRegistry",
    "QFormat",
    "SparseTensor",
    "SweepConfig",
    "TunePolicy",
    "TuningStore",
    "autotune_engine",
    "build_engine",
    "cp_als",
    "cp_als_batched",
    "enable_tracing",
    "get_tracer",
    "load_config",
    "pareto_report",
    "random_tensor",
    "register_backend",
    "register_format",
    "registered_backends",
    "registered_formats",
    "run_sweep",
    "span",
    "table1_tensor",
    "traced",
]
