"""Kernel shape contracts: dataflow rules over `kernels/` + `core/mttkrp.py`.

The public MTTKRP surface has one contract the whole stack leans on —
every variant returns `(dims[mode], rank)` — plus a set of internal
agreements no runtime test states explicitly: `segment_sum` calls must
pass the `num_segments`/`indices_are_sorted` the producing sort
guarantees, the Pallas one-hot matmuls must contract over the chunk
extent, and every BlockSpec block must evenly divide its operand (the
grid would silently read a ragged final block otherwise).

These rules pin that contract in `kernel_contracts.json` (mirroring
`schema_manifest.json`) and *prove* it per function with the
`dataflow.py` abstract interpreter, instantiating each pinned function
over a small case grid of (ndim, mode) so mode-rotation bugs (the
`chunk_shape[m]` vs `chunk_shape[mode]` class) can't hide behind a
symmetric case:

  kernel-contract-drift — the pinned signatures vs the live ASTs: a
      renamed kwarg, a new positional arg, a dropped `static_argnames`
      entry, or a vanished function fails until `--regen-contracts`
      re-pins it (making API drift a reviewed diff, like the persist
      schema).
  kernel-shape-contract — interpreter-derived return shape/dtype vs the
      pinned `(dims[mode], rank)` contract, broadcast/contraction
      mismatches found *inside* the bodies, dtype-demoting stores, and
      `segment_sum` call-site agreement with the pinned
      num_segments/sorted facts.
  pallas-blockspec — BlockSpec rank/divisibility vs the operands
      (including the `rank_multiple=128` lane-padding algebra: padded
      extents are `ceil(x, b)` symbols the divisibility check consumes),
      index_map arity vs grid rank + scalar-prefetch count, and operand
      count vs `in_specs`.

The contract cases deliberately pin `rank_multiple=128` for the Pallas
wrappers so the lane-padding path — the real-TPU ROADMAP precondition —
is the one proven, not the no-op default.
"""
from __future__ import annotations

import ast
import json
import re
from pathlib import Path

from . import dataflow as df
from .engine import Finding, ProjectContext, register_rule

__all__ = [
    "CONTRACT_CASES",
    "CONTRACT_MODULES",
    "check_kernel_contract_drift",
    "check_kernel_shape_contract",
    "check_pallas_blockspec",
    "contract_report",
    "extract_signature",
    "load_contracts",
    "regen_contracts",
]

_CONTRACTS = "src/repro/analysis/kernel_contracts.json"

#: The modules whose `__all__` functions the contract file pins.
CONTRACT_MODULES = (
    "src/repro/core/mttkrp.py",
    "src/repro/core/baselines.py",
    "src/repro/kernels/ops.py",
    "src/repro/kernels/mttkrp_kernel.py",
    "src/repro/kernels/ref.py",
)

#: (ndim, mode) instantiations every contracted function is proven over.
#: 3-mode covers every mode role (output / inner / mid); the 4-mode case
#: exercises the extra mid-factor multiply in the fixed Alg.-2 chain.
CONTRACT_CASES = ((3, 0), (3, 1), (3, 2), (4, 1))


# ---------------------------------------------------------------------------
# Signature pinning
# ---------------------------------------------------------------------------

def _dotted(node: ast.AST) -> str | None:
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def extract_signature(fndef: ast.FunctionDef) -> dict:
    """Static signature fingerprint: arg names/order, kw-only set, which
    params carry defaults, vararg, and the jit/static_argnames wrapper —
    everything a caller can observe without running the function."""
    a = fndef.args
    jit = False
    static: list[str] = []
    for dec in fndef.decorator_list:
        if isinstance(dec, ast.Call):
            fn = _dotted(dec.func) or ""
            if fn.split(".")[-1] == "partial" and dec.args:
                inner = _dotted(dec.args[0]) or ""
                if inner.split(".")[-1] == "jit":
                    jit = True
                    for kw in dec.keywords:
                        if kw.arg == "static_argnames":
                            try:
                                v = ast.literal_eval(kw.value)
                            except ValueError:
                                continue
                            static = [v] if isinstance(v, str) else list(v)
            elif fn.split(".")[-1] == "jit":
                jit = True
        elif (_dotted(dec) or "").split(".")[-1] == "jit":
            jit = True
    return {
        "args": [p.arg for p in a.posonlyargs + a.args],
        "vararg": a.vararg.arg if a.vararg else None,
        "kwonly": [p.arg for p in a.kwonlyargs],
        "defaults": len(a.defaults),
        "kw_defaults": [p.arg for p, d in zip(a.kwonlyargs, a.kw_defaults)
                        if d is not None],
        "jit": jit,
        "static_argnames": static,
    }


def _module_all(tree: ast.Module) -> list[str]:
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__":
                    try:
                        return [str(n) for n in ast.literal_eval(node.value)]
                    except ValueError:
                        return []
    return []


def _module_functions(tree: ast.Module) -> dict[str, ast.FunctionDef]:
    return {n.name: n for n in tree.body if isinstance(n, ast.FunctionDef)}


def load_contracts(root: Path) -> dict | None:
    """The pinned contracts, or None when missing/unparseable (the drift
    rule reports that; the shape rules just go quiet)."""
    p = Path(root) / _CONTRACTS
    if not p.is_file():
        return None
    try:
        data = json.loads(p.read_text(encoding="utf-8"))
    except json.JSONDecodeError:
        return None
    return data if isinstance(data, dict) else None


def regen_contracts(root: Path) -> dict:
    """Re-pin the signature blocks from the live ASTs, preserving the
    hand-written shape/segment-sum contracts of surviving functions and
    dropping entries for vanished ones — the intentional-drift workflow:
    change the API, run `python -m repro.analysis --regen-contracts`,
    review + commit the JSON diff (new functions arrive with
    `"params": null`, i.e. signature-pinned only, until someone writes
    their shape contract)."""
    root = Path(root)
    data = load_contracts(root) or {}
    old = data.get("functions", {})
    functions: dict[str, dict] = {}
    for rel in CONTRACT_MODULES:
        p = root / rel
        if not p.is_file():
            continue
        tree = ast.parse(p.read_text(encoding="utf-8"))
        defs = _module_functions(tree)
        for name in _module_all(tree):
            fndef = defs.get(name)
            if fndef is None:
                continue
            key = f"{rel}::{name}"
            entry = dict(old.get(key) or
                         {"params": None, "returns": None,
                          "segment_sums": None})
            entry["signature"] = extract_signature(fndef)
            functions[key] = entry
    out = {
        "modules": list(CONTRACT_MODULES),
        "functions": {k: functions[k] for k in sorted(functions)},
    }
    if "qformat" in data:
        out["qformat"] = data["qformat"]
    (root / _CONTRACTS).write_text(
        json.dumps(out, indent=2) + "\n", encoding="utf-8")
    return out


# ---------------------------------------------------------------------------
# Contract instantiation
# ---------------------------------------------------------------------------

_CEIL_RE = re.compile(r"^ceil\((.+),\s*(.+)\)$")


def _parse_dim(token, ndim: int, mode: int) -> df.Dim:
    """The contract shape grammar: ints, `N` (tensor order — concrete, it
    must broadcast against literal coordinate columns), `dim[mode]` /
    `S[mode]` (mode-indexed tensor extent / chunk size), `ceil(a,b)`
    (least multiple of b ≥ a — the padding algebra), or a named symbol
    from the per-case table (nnz, T, P, R, F, I0.., S0..)."""
    if isinstance(token, int):
        return df.Dim.const_(int(token))
    if token == "N":
        return df.Dim.const_(ndim)
    if token == "dim[mode]":
        return df.Dim.sym(f"I{mode}")
    if token == "S[mode]":
        return df.Dim.sym(f"S{mode}")
    m = _CEIL_RE.match(token)
    if m:
        return df.Dim.atom(df.CeilMul(_parse_dim(m.group(1), ndim, mode),
                                      _parse_dim(m.group(2), ndim, mode)))
    if token.strip().isdigit():
        return df.Dim.const_(int(token))
    return df.Dim.sym(token)


def _dtype(name: str) -> df.DType:
    dt = df.parse_dtype(name)
    if dt is None:
        raise ValueError(f"unknown dtype {name!r} in kernel contract")
    return df.canonicalize(dt)


def _alto_case_positions(ndim: int) -> tuple[tuple[int, ...], ...]:
    # Mode-major round-robin with 5 bits per mode (shape 32^ndim): every
    # position < 32, so the contract case packs into one key word.
    bits = 5
    return tuple(tuple(m + b * ndim for b in range(bits))
                 for m in range(ndim))


def _build_param(spec: dict, ndim: int, mode: int) -> df.AVal:
    kind = spec["kind"]
    if kind == "factors":
        dt = _dtype(spec.get("dtype", "float32"))
        return df.ATuple([
            df.AArray((df.Dim.sym(f"I{m}"), df.Dim.sym("R")), dt)
            for m in range(ndim)])
    if kind == "factors-padded":
        dt = _dtype(spec.get("dtype", "float32"))
        return df.ATuple([
            df.AArray((df.Dim.atom(df.CeilMul(df.Dim.sym(f"I{m}"),
                                              df.Dim.sym(f"S{m}"))),
                       df.Dim.sym("R")), dt)
            for m in range(ndim)])
    if kind == "factors-padded-t":
        dt = _dtype(spec.get("dtype", "float32"))
        return df.ATuple([
            df.AArray((df.Dim.sym("R"),
                       df.Dim.atom(df.CeilMul(df.Dim.sym(f"I{m}"),
                                              df.Dim.sym(f"S{m}")))), dt)
            for m in range(ndim)])
    if kind in ("array", "array-per-mode"):
        dt = _dtype(spec.get("dtype", "float32"))
        shape = tuple(_parse_dim(t, ndim, mode) for t in spec["shape"])
        if kind == "array-per-mode":
            return df.ATuple([df.AArray(shape, dt) for _ in range(ndim)])
        return df.AArray(shape, dt)
    if kind == "mode":
        return df.AConst(mode)
    if kind == "out-dim":
        return df.AInt(df.Dim.sym(f"I{mode}"))
    if kind == "dims":
        return df.ATuple([df.AInt(df.Dim.sym(f"S{m}")) for m in range(ndim)])
    if kind == "dim":
        return df.AInt(df.Dim.sym(spec["sym"]))
    if kind == "const":
        return df.AConst(spec["value"])
    if kind == "input-modes":
        return df.AConst(tuple(m for m in range(ndim) if m != mode))
    if kind == "inner-mode":
        return df.AConst(ndim - 1 if mode != ndim - 1 else 0)
    if kind == "mid-modes":
        inner = ndim - 1 if mode != ndim - 1 else 0
        return df.AConst(tuple(m for m in range(ndim)
                               if m not in (mode, inner)))
    if kind == "alto-positions":
        return df.AConst(_alto_case_positions(ndim))
    raise ValueError(f"unknown contract param kind {kind!r}")


def _instantiate(params: dict, sig_args: list[str], sig_kwonly: list[str],
                 ndim: int, mode: int) -> tuple[list, dict]:
    args: list[df.AVal] = []
    for name in sig_args:
        if name not in params:
            break
        args.append(_build_param(params[name], ndim, mode))
    kwargs = {name: _build_param(params[name], ndim, mode)
              for name in sig_kwonly if name in params}
    return args, kwargs


# ---------------------------------------------------------------------------
# The shared interpretation pass (computed once per ProjectContext)
# ---------------------------------------------------------------------------

def contract_report(ctx: ProjectContext) -> dict:
    """Interpret every contracted function over the case grid; cache on the
    context so the three rules consuming it share one pass.  Returns
    {"shape": [...], "pallas": [...]} of (rel, line, message) triples,
    deduplicated — symmetric cases produce identical messages."""
    cached = getattr(ctx, "_kernel_contract_report", None)
    if cached is not None:
        return cached
    shape: set[tuple] = set()
    pallas: set[tuple] = set()
    report = {"shape": shape, "pallas": pallas}
    contracts = load_contracts(ctx.root)
    if contracts is None:
        ctx._kernel_contract_report = report   # drift rule reports the why
        return report

    sources = {fc.rel: fc.source for fc in ctx.walk("src/repro")}
    program = df.Program(sources)

    for key, entry in contracts.get("functions", {}).items():
        params = entry.get("params")
        if params is None:
            continue
        rel, _, name = key.partition("::")
        module = program.module(rel)
        fndef = module.functions.get(name) if module else None
        sig = entry.get("signature") or {}
        if fndef is None or not sig:
            continue                           # drift rule owns these
        for ndim, mode in CONTRACT_CASES:
            interp = df.Interpreter(program)
            try:
                args, kwargs = _instantiate(
                    params, sig.get("args", []), sig.get("kwonly", []),
                    ndim, mode)
                result = interp.call_function(fndef, module, args, kwargs)
            except (ValueError, RecursionError):
                continue
            for p in interp.problems:
                dest = pallas if p.category == "pallas" else shape
                dest.add((p.rel or rel, p.line, p.message))
            _check_returns(entry, result, rel, fndef, ndim, mode, shape)
            _check_segment_sums(entry, interp.segment_sums, rel, fndef,
                                ndim, mode, shape)

    ctx._kernel_contract_report = report
    return report


def _check_returns(entry: dict, result: df.AVal, rel: str,
                   fndef: ast.FunctionDef, ndim: int, mode: int,
                   out: set) -> None:
    ret = entry.get("returns")
    if ret is None:
        return
    expected = tuple(_parse_dim(t, ndim, mode) for t in ret["shape"])
    want_dt = _dtype(ret["dtype"])
    if isinstance(result, df.AUnknown):
        return                                  # quiet on ignorance
    if not isinstance(result, df.AArray):
        out.add((rel, fndef.lineno,
                 f"{fndef.name} is contracted to return an array but the "
                 f"interpreter derives {type(result).__name__}"))
        return
    if len(result.shape) != len(expected):
        out.add((rel, fndef.lineno,
                 f"{fndef.name} returns rank {len(result.shape)} "
                 f"({_fmt(result.shape)}) but the contract pins rank "
                 f"{len(expected)} ({_fmt(expected)})"))
        return
    for i, (got, want) in enumerate(zip(result.shape, expected)):
        if got.has_opaque or want.has_opaque:
            continue
        if got != want:
            out.add((rel, fndef.lineno,
                     f"{fndef.name} return dim {i} is {got} but the "
                     f"contract pins {want}"))
    if result.dtype != want_dt:
        out.add((rel, fndef.lineno,
                 f"{fndef.name} returns dtype {result.dtype} but the "
                 f"contract pins {want_dt}"))


def _check_segment_sums(entry: dict, calls: list, rel: str,
                        fndef: ast.FunctionDef, ndim: int, mode: int,
                        out: set) -> None:
    specs = entry.get("segment_sums")
    if specs is None:
        return
    if len(calls) != len(specs):
        out.add((rel, fndef.lineno,
                 f"{fndef.name} is contracted to make {len(specs)} "
                 f"segment_sum call(s); the interpreter observed "
                 f"{len(calls)}"))
        return
    for i, (call, spec) in enumerate(zip(calls, specs)):
        want_ns = _parse_dim(spec["num_segments"], ndim, mode)
        if call.num_segments is None:
            out.add((call.rel or rel, call.line,
                     f"segment_sum call #{i} passes no num_segments; the "
                     f"contract pins {want_ns} (without it the output is "
                     "sized from the data — a silent shape change)"))
        elif not call.num_segments.has_opaque \
                and call.num_segments != want_ns:
            out.add((call.rel or rel, call.line,
                     f"segment_sum call #{i} passes num_segments="
                     f"{call.num_segments}; the contract pins {want_ns}"))
        if call.indices_are_sorted != bool(spec["sorted"]):
            out.add((call.rel or rel, call.line,
                     f"segment_sum call #{i} has indices_are_sorted="
                     f"{call.indices_are_sorted}; the contract pins "
                     f"{bool(spec['sorted'])} (the flag must match what "
                     "the producing sort guarantees — wrong either way: "
                     "silently wrong sums or a wasted sorted-path win)"))


def _fmt(shape: tuple) -> str:
    return "(" + ", ".join(str(d) for d in shape) + ")"


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------

@register_rule(
    "kernel-contract-drift",
    scope="project",
    tier="dataflow",
    description=("public kernel signatures must match the pinned "
                 "analysis/kernel_contracts.json; drift without "
                 "--regen-contracts fails"),
    rationale=("every engine backend and benchmark calls this surface by "
               "keyword; a silent rename or a dropped static_argnames "
               "entry breaks callers (or retraces per call) with no test "
               "naming the contract — pinning makes API drift a reviewed "
               "JSON diff, exactly like the persist schema manifest"),
    example=("signature of mttkrp_chunked drifted from the pinned "
             "contract — run --regen-contracts"),
)
def check_kernel_contract_drift(ctx: ProjectContext):
    contracts = load_contracts(ctx.root)
    if contracts is None:
        yield ctx.finding(
            "kernel-contract-drift", _CONTRACTS, 1,
            "kernel_contracts.json is missing or unparseable — run "
            "`python -m repro.analysis --regen-contracts` and commit it")
        return
    pinned = contracts.get("functions", {})
    if list(contracts.get("modules", [])) != list(CONTRACT_MODULES):
        yield ctx.finding(
            "kernel-contract-drift", _CONTRACTS, 1,
            "pinned module list differs from shape_rules.CONTRACT_MODULES "
            "— run --regen-contracts")
    live: set[str] = set()
    for rel in CONTRACT_MODULES:
        fc = ctx.file(rel)
        if fc is None:
            yield ctx.finding(
                "kernel-contract-drift", _CONTRACTS, 1,
                f"contracted module {rel} is gone — update "
                "CONTRACT_MODULES and --regen-contracts")
            continue
        try:
            tree = fc.tree
        except SyntaxError:
            continue                            # syntax-error meta rule owns it
        defs = _module_functions(tree)
        for name in _module_all(tree):
            fndef = defs.get(name)
            if fndef is None:
                continue
            key = f"{rel}::{name}"
            live.add(key)
            entry = pinned.get(key)
            if entry is None:
                yield ctx.finding(
                    "kernel-contract-drift", rel, fndef.lineno,
                    f"public function {name} has no entry in "
                    "kernel_contracts.json — run --regen-contracts")
                continue
            if entry.get("signature") != extract_signature(fndef):
                yield ctx.finding(
                    "kernel-contract-drift", rel, fndef.lineno,
                    f"signature of {name} drifted from the pinned contract "
                    "— run --regen-contracts (and review the JSON diff)")
    for key in sorted(set(pinned) - live):
        yield ctx.finding(
            "kernel-contract-drift", _CONTRACTS, 1,
            f"pinned entry {key} matches no live public function — run "
            "--regen-contracts to drop it")


@register_rule(
    "kernel-shape-contract",
    scope="project",
    tier="dataflow",
    description=("abstract interpretation proves every contracted kernel "
                 "returns (dims[mode], rank) with the pinned dtype and "
                 "makes exactly the pinned segment_sum calls"),
    rationale=("the MTTKRP variants are interchangeable backends — the "
               "autotuner swaps them per mode, so a shape/dtype deviation "
               "or a wrong num_segments/indices_are_sorted in ONE variant "
               "corrupts results only for the workloads that pick it; "
               "symbolic interpretation over the (ndim, mode) case grid "
               "proves the contract without running a single kernel"),
    example=("segment_sum call #1 passes num_segments=F; the contract "
             "pins I1"),
)
def check_kernel_shape_contract(ctx: ProjectContext):
    for rel, line, message in sorted(contract_report(ctx)["shape"]):
        yield ctx.finding("kernel-shape-contract", rel, line, message)


@register_rule(
    "pallas-blockspec",
    scope="project",
    tier="dataflow",
    description=("Pallas BlockSpecs must divide their operands evenly, "
                 "index_maps must match grid rank + scalar prefetch, and "
                 "operand count must match in_specs"),
    rationale=("interpret=True masks all of this today; on real TPU "
               "(ROADMAP) a non-dividing block or a short index_map is a "
               "compile error at best and silent garbage at worst — the "
               "padded-extent algebra (rows to whole chunks, rank to the "
               "128-lane boundary) is exactly what the divisibility proof "
               "consumes"),
    example=("BlockSpec in_spec dim 0: block size S1 does not evenly "
             "divide operand dim I1"),
)
def check_pallas_blockspec(ctx: ProjectContext):
    for rel, line, message in sorted(contract_report(ctx)["pallas"]):
        yield ctx.finding("pallas-blockspec", rel, line, message)
