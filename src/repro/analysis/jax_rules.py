"""JAX tracing-hygiene rules.

These passes walk the kernel/engine hot paths (`src/repro/{kernels,core,
engine,formats}` by default) for the hazard classes that stay invisible
under `interpret=True` CPU runs but bite on real hardware (ROADMAP's
TPU `interpret=False` item): silent per-call retraces, host-device syncs
inside loops, tracers escaping a jitted scope, and nondeterministic seeds.

Every rule is a generator over `FileContext` yielding `Finding`s; the
fixture tests in `tests/test_analysis.py` hold one bad snippet (must fire)
and one good snippet (must stay quiet) per rule.
"""
from __future__ import annotations

import ast
from collections.abc import Iterator

from .engine import FileContext, Finding, register_rule

__all__ = [
    "check_dict_order",
    "check_host_sync",
    "check_nondeterminism",
    "check_retrace",
    "check_trace_in_jit",
    "check_tracer_leak",
]

JAX_TARGETS = (
    "src/repro/kernels",
    "src/repro/core",
    "src/repro/engine",
    "src/repro/formats",
    "src/repro/batch",
    "src/repro/serve",
    "src/repro/obs",
)


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------

def _dotted(node: ast.AST) -> str | None:
    """`a.b.c` → "a.b.c" for Name/Attribute chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _mentions_jax(node: ast.AST) -> bool:
    """Does the subtree reference jax/jnp/lax — i.e. plausibly produce a
    traced/device value?  Purely lexical: we cannot type-infer, so the
    host-sync rule only fires where the device-ness is visible in the
    expression itself (keeps the false-positive rate low enough for a
    zero-findings gate)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in ("jnp", "jax", "lax"):
            return True
    return False


def _jit_decoration(fn: ast.FunctionDef | ast.AsyncFunctionDef):
    """(is_jitted, static_names, lineno) for a function's decorators.

    Recognizes `@jax.jit`, `@jit`, `@partial(jax.jit, static_argnums=…/
    static_argnames=…)` and `@functools.partial(...)`.  static_argnums are
    mapped through the positional parameter list (self-less functions in
    this tree, but we index args as written).
    """
    static: set[str] = set()
    jitted = False
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = _dotted(target) or ""
        inner = None
        if name.endswith("partial") and isinstance(dec, ast.Call) and dec.args:
            inner = _dotted(dec.args[0]) or ""
            if inner not in ("jax.jit", "jit"):
                continue
        elif name not in ("jax.jit", "jit"):
            continue
        jitted = True
        if not isinstance(dec, ast.Call):
            continue
        params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        for kw in dec.keywords:
            if kw.arg not in ("static_argnums", "static_argnames"):
                continue
            values = (kw.value.elts if isinstance(kw.value, (ast.Tuple, ast.List))
                      else [kw.value])
            for v in values:
                if isinstance(v, ast.Constant):
                    if isinstance(v.value, int) and kw.arg == "static_argnums":
                        if 0 <= v.value < len(params):
                            static.add(params[v.value])
                    elif isinstance(v.value, str):
                        static.add(v.value)
    return jitted, static


def _jitted_functions(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            jitted, static = _jit_decoration(node)
            if jitted:
                yield node, static


_LOOPS = (ast.For, ast.While, ast.AsyncFor)


def _loop_depth_map(tree: ast.AST) -> dict[ast.AST, int]:
    """node → number of enclosing for/while loops (function bodies reset
    the count: a nested def is not 'inside' its enclosing loop at runtime
    until called, and flagging it would double-report)."""
    depth: dict[ast.AST, int] = {}

    def visit(node: ast.AST, d: int) -> None:
        depth[node] = d
        for child in ast.iter_child_nodes(node):
            nd = d
            if isinstance(node, _LOOPS) and child in node.body + getattr(node, "orelse", []):
                nd = d + 1
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                visit(child, 0)
            else:
                visit(child, nd)

    visit(tree, 0)
    return depth


# ---------------------------------------------------------------------------
# retrace-control
# ---------------------------------------------------------------------------

@register_rule(
    "retrace-control",
    packages=JAX_TARGETS,
    description=("jit-retrace hazards: `jax.jit` applied inside a loop "
                 "body, or a non-static parameter of a jitted function "
                 "driving Python `if`/`while`/`range` control flow"),
    rationale=("jitting in a loop recompiles every iteration; Python "
               "control flow on a traced argument either crashes "
               "(ConcretizationTypeError) or silently retraces per value — "
               "either way the compile cache is defeated exactly where the "
               "TPU path is hottest"),
    example=("parameter 'mode' of jitted 'mttkrp' drives `if` at line 12 "
             "but is not in static_argnums/static_argnames"),
)
def check_retrace(ctx: FileContext) -> Iterator[Finding]:
    tree = ctx.tree
    depth = _loop_depth_map(tree)

    # (a) jax.jit(...) evaluated inside a loop body → recompile per iteration
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and (_dotted(node.func) in ("jax.jit", "jit"))
                and depth.get(node, 0) > 0):
            yield ctx.finding(
                "retrace-control", node,
                "`jax.jit` called inside a loop body — each iteration "
                "builds a fresh jitted callable and retraces; hoist the "
                "jit out of the loop")

    # (b) traced (non-static) parameter driving Python control flow
    for fn, static in _jitted_functions(tree):
        params = {a.arg for a in fn.args.posonlyargs + fn.args.args
                  + fn.args.kwonlyargs} - static
        # Names rebound in the body stop being "the traced parameter".
        rebound = {t.id for node in ast.walk(fn)
                   for t in getattr(node, "targets", [])
                   if isinstance(t, ast.Name)}
        traced = params - rebound

        def param_in(expr: ast.AST) -> str | None:
            for sub in ast.walk(expr):
                if isinstance(sub, ast.Name) and sub.id in traced:
                    return sub.id
            return None

        for node in ast.walk(fn):
            if isinstance(node, (ast.If, ast.While)):
                hit = param_in(node.test)
                kind = "if" if isinstance(node, ast.If) else "while"
                if hit:
                    yield ctx.finding(
                        "retrace-control", node,
                        f"parameter '{hit}' of jitted '{fn.name}' drives "
                        f"Python `{kind}` control flow but is not declared "
                        "in static_argnums/static_argnames — this traces "
                        "per value (or raises ConcretizationTypeError); "
                        "mark it static or use lax.cond/lax.while_loop")
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Name)
                  and node.func.id == "range"):
                hit = next((h for h in map(param_in, node.args) if h), None)
                if hit:
                    yield ctx.finding(
                        "retrace-control", node,
                        f"parameter '{hit}' of jitted '{fn.name}' sizes a "
                        "Python `range` loop but is not static — the loop "
                        "is unrolled per traced value; mark it static or "
                        "use lax.fori_loop")


# ---------------------------------------------------------------------------
# dict-order-enumeration
# ---------------------------------------------------------------------------

def _module_dicts(tree: ast.AST) -> set[str]:
    """Module-level names bound to dict literals / dict() — the mutable
    registries whose iteration order is registration (import side-effect)
    order."""
    names: set[str] = set()
    body = tree.body if isinstance(tree, ast.Module) else []
    for node in body:
        value = None
        targets: list[ast.expr] = []
        if isinstance(node, ast.Assign):
            value, targets = node.value, node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            value, targets = node.value, [node.target]
        if value is None:
            continue
        is_dict = isinstance(value, (ast.Dict, ast.DictComp)) or (
            isinstance(value, ast.Call) and _dotted(value.func) == "dict")
        if not is_dict:
            continue
        for t in targets:
            if isinstance(t, ast.Name):
                names.add(t.id)
    return names


def _sorted_wrapped(node: ast.AST, parents: dict[ast.AST, ast.AST]) -> bool:
    """Is `node` (an iteration source) inside a sorted()/sorted-by-key
    normalization — sorted(...), dict(sorted(...)), min/max, len()?"""
    _ORDER_FREE = ("sorted", "len", "min", "max", "set", "frozenset", "sum",
                   "any", "all")
    cur = node
    for _ in range(6):
        parent = parents.get(cur)
        if parent is None:
            return False
        if isinstance(parent, ast.Call):
            fname = _dotted(parent.func)
            if fname in _ORDER_FREE:
                return True
        cur = parent
    return False


@register_rule(
    "dict-order-enumeration",
    packages=JAX_TARGETS,
    description=("candidate/registry enumeration that iterates a mutable "
                 "module-level dict in insertion (registration) order "
                 "without sorting"),
    rationale=("registration order is an import-side-effect: two processes "
               "importing modules differently enumerate candidates "
               "differently, so autotune tie-breaks, probe budgets, and "
               "persisted winner lists silently diverge between runs"),
    example=("iteration over module-level dict '_REGISTRY' depends on "
             "registration order; wrap in sorted(...)"),
)
def check_dict_order(ctx: FileContext) -> Iterator[Finding]:
    tree = ctx.tree
    dicts = _module_dicts(tree)
    if not dicts:
        return
    parents: dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node

    def source_name(expr: ast.AST) -> str | None:
        """The registry name if `expr` enumerates one order-dependently:
        NAME, NAME.values(), NAME.items(), NAME.keys(), iter(NAME)…"""
        if isinstance(expr, ast.Name) and expr.id in dicts:
            return expr.id
        if isinstance(expr, ast.Call):
            f = expr.func
            if (isinstance(f, ast.Attribute)
                    and f.attr in ("values", "items", "keys")
                    and isinstance(f.value, ast.Name)
                    and f.value.id in dicts):
                return f.value.id
            if (isinstance(f, ast.Name) and f.id in ("iter", "list", "tuple",
                                                     "enumerate")
                    and expr.args):
                return source_name(expr.args[0])
        return None

    sources: list[tuple[ast.AST, str]] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.For, ast.AsyncFor)):
            name = source_name(node.iter)
            if name:
                sources.append((node.iter, name))
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                               ast.GeneratorExp)):
            for gen in node.generators:
                name = source_name(gen.iter)
                if name:
                    sources.append((gen.iter, name))
        elif isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id in ("list", "tuple", "next"):
            # list(_REGISTRY.values()) materializes registration order too
            name = source_name(node)
            if name:
                sources.append((node, name))

    seen: set[tuple[int, str]] = set()
    for expr, name in sources:
        if _sorted_wrapped(expr, parents):
            continue
        key = (expr.lineno, name)
        if key in seen:
            continue
        seen.add(key)
        yield ctx.finding(
            "dict-order-enumeration", expr,
            f"iteration over module-level dict '{name}' depends on "
            "registration (import side-effect) order — wrap the "
            "enumeration in sorted(...) or document why order is "
            "load-bearing")


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

_SYNC_METHODS = ("item", "tolist", "block_until_ready")


@register_rule(
    "host-sync",
    packages=JAX_TARGETS,
    description=("host-device synchronization on a visibly-JAX value: "
                 "float()/int() over a jnp/jax expression, .item()/"
                 ".tolist(), np.asarray/np.array of a jax expression, "
                 "block_until_ready, jax.device_get"),
    rationale=("each sync stalls the dispatch pipeline; inside the probe/"
               "iteration hot loops one stray float() serializes the "
               "device queue and the measured timings stop measuring the "
               "kernel — on TPU the stall is a full round-trip"),
    example=("host sync inside a loop: `float(...)` forces a device→host "
             "transfer each iteration"),
)
def check_host_sync(ctx: FileContext) -> Iterator[Finding]:
    tree = ctx.tree
    depth = _loop_depth_map(tree)

    def emit(node: ast.AST, what: str) -> Finding:
        d = depth.get(node, 0)
        where = "inside a loop: " if d else ""
        return ctx.finding(
            "host-sync", node,
            f"host sync {where}{what} forces a device→host transfer"
            + ("; hoist it out of the loop or keep the value on device"
               if d else "; keep the reduction on device if this feeds "
               "further computation"))

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fname = _dotted(node.func)
        # float(x)/int(x)/bool(x) over a visibly-jax expression
        if (isinstance(node.func, ast.Name)
                and node.func.id in ("float", "int", "bool")
                and node.args and _mentions_jax(node.args[0])):
            yield emit(node, f"`{node.func.id}(...)` over a jax expression")
        # np.asarray / np.array / np.float64(...) of a jax expression
        elif (fname in ("np.asarray", "np.array", "numpy.asarray",
                        "numpy.array")
                and node.args and _mentions_jax(node.args[0])):
            yield emit(node, f"`{fname}(...)` over a jax expression")
        # jax.device_get / jax.block_until_ready module functions
        elif fname in ("jax.device_get", "jax.block_until_ready"):
            yield emit(node, f"`{fname}(...)`")
        # .item() / .tolist() / .block_until_ready() methods
        elif (isinstance(node.func, ast.Attribute)
                and node.func.attr in _SYNC_METHODS):
            yield emit(node, f"`.{node.func.attr}()`")


# ---------------------------------------------------------------------------
# tracer-leak
# ---------------------------------------------------------------------------

@register_rule(
    "tracer-leak",
    packages=JAX_TARGETS,
    description=("a jitted function stores a value on `self` or a module "
                 "global — the stored object is a tracer that outlives "
                 "its trace"),
    rationale=("a leaked tracer raises UnexpectedTracerError on first "
               "touch after the trace ends, but only on the *second* call "
               "pattern that reuses it — the classic works-once-then-"
               "explodes bug"),
    example=("jitted 'step' assigns to `self.state` — the stored value is "
             "a tracer"),
)
def check_tracer_leak(ctx: FileContext) -> Iterator[Finding]:
    for fn, _static in _jitted_functions(ctx.tree):
        globals_declared: set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Global):
                globals_declared.update(node.names)
        for node in ast.walk(fn):
            targets: list[ast.expr] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
                targets = [node.target]
            for t in targets:
                if (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"):
                    yield ctx.finding(
                        "tracer-leak", node,
                        f"jitted '{fn.name}' assigns to `self.{t.attr}` — "
                        "the stored value is a tracer that outlives its "
                        "trace (UnexpectedTracerError on reuse); return "
                        "the value instead")
                elif (isinstance(t, ast.Name)
                        and t.id in globals_declared):
                    yield ctx.finding(
                        "tracer-leak", node,
                        f"jitted '{fn.name}' assigns module global "
                        f"'{t.id}' — the stored value is a tracer that "
                        "outlives its trace; return it instead")


# ---------------------------------------------------------------------------
# trace-in-jit
# ---------------------------------------------------------------------------

#: Observability entrypoints (repro.obs) that must never run under a trace:
#: bare-name calls and attribute-call leaves, matched lexically.
_OBS_NAME_CALLS = ("span", "record_span")
_OBS_ATTR_CALLS = ("span", "record", "record_span", "observe", "inc")


@register_rule(
    "trace-in-jit",
    packages=JAX_TARGETS,
    description=("a span or metric emission (`span(...)`, `record_span`, "
                 "`.observe()`, `.inc()`, `tracer.record`) "
                 "inside the body of a jitted function"),
    rationale=("span/metric calls are host-side Python: under `jax.jit` "
               "they run once at trace time — recording bogus trace-time "
               "durations instead of per-call ones — and any data they "
               "capture is a tracer; instrumentation belongs around the "
               "jitted call, never inside it (the repro.obs overhead "
               "contract assumes the disabled check is host code)"),
    example=("jitted 'step' calls `span(...)` at line 7 — the span runs at "
             "trace time, not per call; move it around the jitted call"),
)
def check_trace_in_jit(ctx: FileContext) -> Iterator[Finding]:
    for fn, _static in _jitted_functions(ctx.tree):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            what = None
            if (isinstance(node.func, ast.Name)
                    and node.func.id in _OBS_NAME_CALLS):
                what = f"`{node.func.id}(...)`"
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _OBS_ATTR_CALLS):
                what = f"`.{node.func.attr}(...)`"
            if what:
                yield ctx.finding(
                    "trace-in-jit", node,
                    f"jitted '{fn.name}' calls {what} — span/metric "
                    "emission inside a jitted body runs at trace time, not "
                    "per call; move the instrumentation around the jitted "
                    "call")


# ---------------------------------------------------------------------------
# nondeterminism
# ---------------------------------------------------------------------------

#: numpy.random module-level calls that draw from the hidden global state;
#: Generator construction (default_rng/Generator/SeedSequence) and state
#: plumbing are the sanctioned seeded paths.
_NP_RANDOM_OK = ("default_rng", "Generator", "SeedSequence", "PCG64",
                 "Philox", "get_state", "set_state", "seed")
_RANDOM_OK = ("Random", "SystemRandom", "seed", "getstate", "setstate")


@register_rule(
    "nondeterminism",
    packages=("src/repro",),
    description=("wall-clock or hidden-global-state randomness in product "
                 "code: `time.time()`, module-level `random.*`, legacy "
                 "`np.random.*` (global RNG) outside bench timing code"),
    rationale=("the sweep/persist pipeline promises exact-fingerprint "
               "resumability and parity gates at 1e-5 — an unseeded draw "
               "or wall-clock dependency anywhere in the data path makes "
               "reruns incomparable and CI flaky"),
    example=("`np.random.rand(...)` draws from the hidden global RNG; use "
             "np.random.default_rng(seed)"),
)
def check_nondeterminism(ctx: FileContext) -> Iterator[Finding]:
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        fname = _dotted(node.func)
        if fname is None:
            continue
        if fname in ("time.time", "time.time_ns"):
            yield ctx.finding(
                "nondeterminism", node,
                f"`{fname}()` wall clock in product code — timestamps in "
                "persisted/compared data make reruns diverge; use "
                "time.perf_counter() for intervals or thread a timestamp "
                "in from the caller")
        elif fname.startswith("random.") and fname.count(".") == 1:
            leaf = fname.split(".")[1]
            if leaf not in _RANDOM_OK:
                yield ctx.finding(
                    "nondeterminism", node,
                    f"`{fname}()` draws from the process-global `random` "
                    "state — seedless and shared across callers; use "
                    "random.Random(seed) or np.random.default_rng(seed)")
        elif (fname.startswith(("np.random.", "numpy.random."))
                and fname.split(".")[-1] not in _NP_RANDOM_OK):
            yield ctx.finding(
                "nondeterminism", node,
                f"`{fname}(...)` draws from numpy's hidden global RNG; "
                "use np.random.default_rng(seed) so every draw is "
                "reproducible from the workload fingerprint")
