"""Integer-width rules: the places where a silently wrapped index loses.

The stack has exactly one deliberate width seam: host-side packing code
(`core/chunking.py`, `core/baselines.py`, `formats/*.py`) runs its
linearization arithmetic in `np.int64`/`np.uint64` — linearized chunk
keys, ALTO bit-packed keys, lexsort permutations — while everything a
device ever touches is `jnp.int32` (coordinates) or `jnp.uint32` (key
words).  Each crossing of that seam is a narrowing cast whose safety is
an argument about reachable magnitudes, and nothing at runtime checks
it: NumPy's `astype` wraps, device int arithmetic wraps, and the wrong
answer looks like a plausible tensor.

Three rules pin the arguments down:

  int32-index-width — dataflow over each host function: names holding
      64-bit signed values (explicit ``dtype=np.int64`` creation,
      ``.astype(np.int64)``, ``np.argsort`` — which returns the platform
      64-bit index type) are tracked through assignments, and every
      ``.astype(np.int32)`` whose operand mentions a tracked name is
      flagged unless the function visibly guards the magnitude (an
      ``if``-gated ``raise`` mentioning the int32 limit).  The
      chunking-grid downcast this PR guards is the canonical site.
  alto-key-width — the ALTO key-bit accounting is one invariant spread
      over two modules: `formats/alto.py` packs `sum(ceil(log2(dim)))`
      bits into 32-bit words behind a ``> MAX_KEY_BITS`` raise, and
      `core/mttkrp.py::_alto_decode` unpacks with the same word
      geometry.  Every hard-coded word constant (``// 32``, ``% 32``,
      ``32 * w``, the ``0xFFFFFFFF`` mask, the 4-bytes-per-word size
      model) must agree — the BLCO 64-bit lift on the ROADMAP will touch
      all of them at once, and this rule is what makes touching only
      some of them fail.
  qformat-accumulator — re-derives the int32 accumulator overflow bound
      of the fixed path from `core/qformat.py`'s preset table (factor
      products must fit int32, and nnz-per-row beyond
      ``(2^31-1) >> (frac + 15 - value_frac - prec_shift)`` can wrap),
      cross-checks the values pinned in `kernel_contracts.json`, and
      checks the Alg.-2 renormalizing shifts are still present in the
      three fixed inner loops the derivation assumes.
"""
from __future__ import annotations

import ast
import re

from .engine import FileContext, ProjectContext, register_rule
from .shape_rules import load_contracts

__all__ = [
    "check_alto_key_width",
    "check_int32_index_width",
    "check_qformat_accumulator",
]

_WIDTH_TARGETS = ("src/repro/core", "src/repro/formats")


# ---------------------------------------------------------------------------
# int32-index-width
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


_INT64_CREATORS = {"np.asarray", "np.array", "np.zeros", "np.empty",
                   "np.full", "np.arange"}


def _mentions_int64(node: ast.AST) -> bool:
    return any(_dotted(n) == "np.int64" for n in ast.walk(node))


def _mentions_int32(node: ast.AST) -> bool:
    return any(_dotted(n) in ("np.int32", "jnp.int32")
               for n in ast.walk(node))


def _is_wide_expr(node: ast.AST, wide: set[str]) -> bool:
    """Does this RHS *itself* produce a 64-bit signed value?  Deliberately
    shallow — a producer call, a tracked name, index/slice/arithmetic on
    one — so a value laundered through an untracked library call drops
    out of the analysis instead of producing speculative findings."""
    if isinstance(node, ast.Name):
        return node.id in wide
    if isinstance(node, ast.Subscript):
        return _is_wide_expr(node.value, wide)
    if isinstance(node, ast.BinOp):
        return (_is_wide_expr(node.left, wide)
                or _is_wide_expr(node.right, wide))
    if isinstance(node, ast.UnaryOp):
        return _is_wide_expr(node.operand, wide)
    if isinstance(node, ast.Call):
        fn = _dotted(node.func)
        if fn == "np.argsort":
            return True
        if fn in _INT64_CREATORS and any(
                kw.arg == "dtype" and _mentions_int64(kw.value)
                for kw in node.keywords):
            return True
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "astype" \
                and any(_mentions_int64(a) for a in node.args):
            return True
    return False


def _wide_names(fn: ast.FunctionDef) -> set[str]:
    wide: set[str] = set()
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                continue
            name = node.targets[0].id
            if name not in wide and _is_wide_expr(node.value, wide):
                wide.add(name)
                changed = True
    return wide


_GUARD_RE = re.compile(r"iinfo\s*\(\s*np\.int32\s*\)|2\s*\*\*\s*31"
                       r"|2147483647|1\s*<<\s*31")


def _has_int32_guard(fn: ast.FunctionDef, source: str) -> bool:
    """An `if`-gated `raise` whose test talks about the int32 limit — the
    shape of the chunking-grid guard.  Per-function: one guard vouches
    for every downcast after it in the same function."""
    for node in ast.walk(fn):
        if isinstance(node, ast.If) \
                and any(isinstance(n, ast.Raise) for n in ast.walk(node)):
            seg = ast.get_source_segment(source, node.test) or ""
            if _GUARD_RE.search(seg):
                return True
    return False


@register_rule(
    "int32-index-width",
    scope="file",
    tier="dataflow",
    packages=_WIDTH_TARGETS,
    description=("a 64-bit index value (int64 creation, .astype(np.int64), "
                 "np.argsort) narrowed with .astype(np.int32) in a function "
                 "with no visible int32 magnitude guard"),
    rationale=("host packing code linearizes in np.int64 while device "
               "coordinates are jnp.int32 — NumPy's astype wraps silently, "
               "so an unguarded narrowing turns a >2^31 extent into "
               "negative coordinates that scatter into wrong output rows "
               "with no error anywhere; an explicit if/raise naming the "
               "int32 limit is both the fix and what quiets the rule"),
    example=("chunking.py: `st.coords // cs.astype(np.int32)` where "
             "cs = np.asarray(chunk_shape, dtype=np.int64)"),
)
def check_int32_index_width(ctx: FileContext):
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        wide = _wide_names(fn)
        if not wide:
            continue
        guarded = _has_int32_guard(fn, ctx.source)
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "astype"
                    and any(_mentions_int32(a) for a in node.args)):
                continue
            names = sorted({n.id for n in ast.walk(node.func.value)
                            if isinstance(n, ast.Name) and n.id in wide})
            if not names or guarded:
                continue
            yield ctx.finding(
                "int32-index-width", node,
                f"{fn.name} narrows 64-bit index value(s) "
                f"{', '.join(names)} with .astype(np.int32) and has no "
                "int32 magnitude guard — astype wraps silently past 2^31; "
                "gate the cast with an if/raise naming np.iinfo(np.int32)")


# ---------------------------------------------------------------------------
# alto-key-width
# ---------------------------------------------------------------------------

_ALTO_FILE = "src/repro/formats/alto.py"
_ALTO_DECODE_FILE = "src/repro/core/mttkrp.py"
#: functions whose word-geometry constants must agree with the 32-bit pack
_ALTO_WORD_FNS = {
    _ALTO_FILE: ("build_alto", "alto_decode_mode"),
    _ALTO_DECODE_FILE: ("_alto_decode",),
}
_WORD_SUSPECTS = (8, 16, 64, 128)          # a //,% or shift by these ≠ 32
_MASK_SUSPECTS = {(1 << 8) - 1, (1 << 16) - 1, (1 << 64) - 1}


def _module_const(tree: ast.Module, name: str):
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == name \
                        and isinstance(node.value, ast.Constant):
                    return node.value.value
    return None


def _fn(tree: ast.Module, name: str) -> ast.FunctionDef | None:
    for node in tree.body:
        if isinstance(node, ast.FunctionDef) and node.name == name:
            return node
    return None


@register_rule(
    "alto-key-width",
    scope="project",
    tier="dataflow",
    description=("ALTO key-bit accounting: MAX_KEY_BITS capacity raise in "
                 "build_alto, 32-bit word geometry (// 32, % 32, 32*w, "
                 "0xFFFFFFFF, 4 bytes/word) consistent across alto.py and "
                 "core/mttkrp.py::_alto_decode"),
    rationale=("the packed key layout is one invariant implemented twice — "
               "host pack/decode in formats/alto.py, device decode in "
               "core/mttkrp.py — plus a byte-size model the autotuner "
               "costs with; the ROADMAP BLCO lift to >64-bit keys must "
               "change every one of these together, and a partial edit "
               "decodes garbage coordinates with no runtime error"),
    example="_alto_decode splits words with p // 64 but alto.py packs 32-bit words",
)
def check_alto_key_width(ctx: ProjectContext):
    alto = ctx.file(_ALTO_FILE)
    if alto is None:
        yield ctx.finding("alto-key-width", _ALTO_FILE, 1,
                          "formats/alto.py is gone — update alto-key-width's "
                          "anchors if the format moved")
        return
    try:
        tree = alto.tree
    except SyntaxError:
        return                              # syntax-error meta rule owns it

    max_bits = _module_const(tree, "MAX_KEY_BITS")
    if max_bits is None:
        yield ctx.finding(
            "alto-key-width", _ALTO_FILE, 1,
            "MAX_KEY_BITS constant not found in formats/alto.py — the "
            "capacity raise and this rule both key off it")
    elif max_bits > 64:
        yield ctx.finding(
            "alto-key-width", _ALTO_FILE, 1,
            f"MAX_KEY_BITS={max_bits} exceeds 64, but the packed key is "
            "built in a np.uint64 before word-splitting — lifting the cap "
            "(BLCO) needs a multi-word build path first")

    build = _fn(tree, "build_alto")
    if build is None:
        yield ctx.finding("alto-key-width", _ALTO_FILE, 1,
                          "build_alto not found in formats/alto.py")
    else:
        has_guard = any(
            isinstance(n, ast.If)
            and any(isinstance(r, ast.Raise) for r in ast.walk(n))
            and any(isinstance(m, ast.Name) and m.id == "MAX_KEY_BITS"
                    for m in ast.walk(n.test))
            for n in ast.walk(build))
        if not has_guard:
            yield ctx.finding(
                "alto-key-width", _ALTO_FILE, build.lineno,
                "build_alto has no `raise` gated on MAX_KEY_BITS — tensors "
                "whose key exceeds the uint64 build word would pack "
                "truncated keys silently")

    for rel, names in _ALTO_WORD_FNS.items():
        fc = ctx.file(rel)
        if fc is None:
            continue
        try:
            ftree = fc.tree
        except SyntaxError:
            continue
        for name in names:
            fn = _fn(ftree, name)
            if fn is None:
                yield ctx.finding(
                    "alto-key-width", rel, 1,
                    f"{name} not found in {rel} — alto-key-width anchors "
                    "the word-geometry check there")
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.BinOp) \
                        and isinstance(node.op, (ast.FloorDiv, ast.Mod)) \
                        and isinstance(node.right, ast.Constant) \
                        and node.right.value in _WORD_SUSPECTS:
                    yield ctx.finding(
                        "alto-key-width", rel, node.lineno,
                        f"{name} splits key words by {node.right.value}, "
                        "but the pack geometry is 32-bit words — every "
                        "`// 32`/`% 32` site must change together")
                if isinstance(node, ast.Constant) \
                        and node.value in _MASK_SUSPECTS:
                    yield ctx.finding(
                        "alto-key-width", rel, node.lineno,
                        f"{name} masks with {node.value:#x}; the 32-bit "
                        "word mask is 0xFFFFFFFF")

    size_fn = _fn(tree, "alto_index_bytes")
    if size_fn is None:
        yield ctx.finding("alto-key-width", _ALTO_FILE, 1,
                          "alto_index_bytes not found in formats/alto.py")
    else:
        bad = [n for n in ast.walk(size_fn)
               if isinstance(n, ast.Constant) and n.value in (2, 8, 16)]
        has4 = any(isinstance(n, ast.Constant) and n.value == 4
                   for n in ast.walk(size_fn))
        if bad or not has4:
            yield ctx.finding(
                "alto-key-width", _ALTO_FILE, size_fn.lineno,
                "alto_index_bytes must cost 4 bytes per uint32 key word — "
                "the autotuner's footprint model reads this; it drifted "
                "from the 32-bit word geometry")


# ---------------------------------------------------------------------------
# qformat-accumulator
# ---------------------------------------------------------------------------

_QFORMAT_FILE = "src/repro/core/qformat.py"
#: (rel, function) triples that implement the Alg.-2 shift discipline the
#: overflow derivation assumes: one `>> matrix_frac` per factor multiply,
#: one `>> (value_frac + prec_shift)` after the value multiply.
_SHIFT_SITES = (
    ("src/repro/core/mttkrp.py", "_fixed_partials"),
)


def _qformat_presets(tree: ast.Module) -> dict[str, tuple[int, int, int]]:
    """FIXED_PRESETS as {name: (int_bits, frac_bits, prec_shift)}, read
    straight off the AST (analysis never imports the runtime)."""
    qdefs: dict[str, tuple[int, int]] = {}
    presets: dict[str, tuple[int, int, int]] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            name, v = node.targets[0].id, node.value
        elif isinstance(node, ast.AnnAssign) \
                and isinstance(node.target, ast.Name) \
                and node.value is not None:
            name, v = node.target.id, node.value
        else:
            continue
        if isinstance(v, ast.Call) and _dotted(v.func) == "QFormat" \
                and len(v.args) == 2 \
                and all(isinstance(a, ast.Constant) for a in v.args):
            qdefs[name] = (v.args[0].value, v.args[1].value)
        elif name == "FIXED_PRESETS" and isinstance(v, ast.Dict):
            for k, item in zip(v.keys, v.values):
                if not (isinstance(k, ast.Constant)
                        and isinstance(item, ast.Tuple)
                        and len(item.elts) == 2
                        and isinstance(item.elts[0], ast.Name)
                        and isinstance(item.elts[1], ast.Constant)):
                    continue
                q = qdefs.get(item.elts[0].id)
                if q is not None:
                    presets[k.value] = (q[0], q[1], item.elts[1].value)
    return presets


def _is_shift_by(node: ast.AST, match) -> bool:
    """A right shift — `>>`, jnp.right_shift, lax.shift_right_arithmetic —
    whose shift amount satisfies `match`."""
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.RShift):
        return match(node.right)
    if isinstance(node, ast.Call) and _dotted(node.func) in (
            "jnp.right_shift", "lax.shift_right_arithmetic",
            "jax.lax.shift_right_arithmetic") and len(node.args) == 2:
        return match(node.args[1])
    return False


def _names_in(node: ast.AST) -> set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


@register_rule(
    "qformat-accumulator",
    scope="project",
    tier="dataflow",
    description=("fixed-point overflow bounds: factor products fit int32 "
                 "for every FIXED_PRESETS entry, the pinned "
                 "accumulator_safe_nnz values match a re-derivation from "
                 "the preset table, and the Alg.-2 renormalizing shifts "
                 "are present in all three fixed inner loops"),
    rationale=("device int32 arithmetic wraps without trapping, so a "
               "preset whose Q format breaks `2*frac+1 <= 31`, a pinned "
               "safe-nnz bound that no longer follows from the presets, or "
               "a dropped `>> matrix_frac` all corrupt results only on "
               "inputs big enough that nobody unit-tests them — the bound "
               "must be re-derived statically every run"),
    example=("FIXED_PRESETS entry Q20.18 breaks the int32 product bound "
             "(2*18+1 > 31)"),
)
def check_qformat_accumulator(ctx: ProjectContext):
    fc = ctx.file(_QFORMAT_FILE)
    if fc is None:
        yield ctx.finding("qformat-accumulator", _QFORMAT_FILE, 1,
                          "core/qformat.py is gone — update the rule anchors")
        return
    try:
        tree = fc.tree
    except SyntaxError:
        return

    presets = _qformat_presets(tree)
    if not presets:
        yield ctx.finding(
            "qformat-accumulator", _QFORMAT_FILE, 1,
            "could not read FIXED_PRESETS / QFormat literals from "
            "core/qformat.py — the overflow derivation has nothing to "
            "check against")
        return

    contracts = load_contracts(ctx.root) or {}
    qpin = contracts.get("qformat") or {}
    value_frac = qpin.get("value_frac", 7)
    pinned = qpin.get("safe_nnz") or {}

    for name, (int_bits, frac, shift) in sorted(presets.items()):
        if int_bits + frac > 32:
            yield ctx.finding(
                "qformat-accumulator", _QFORMAT_FILE, 1,
                f"preset {name}: Q{int_bits}.{frac} needs "
                f"{int_bits + frac} storage bits (> 32)")
        if 2 * frac + 1 > 31:
            yield ctx.finding(
                "qformat-accumulator", _QFORMAT_FILE, 1,
                f"preset {name}: the product of two Q·.{frac} factor "
                f"values spans {2 * frac + 1} bits and overflows the "
                "int32 multiply Alg. 2 renormalizes (2*frac+1 must be "
                "<= 31)")
        if frac + 15 + 1 > 31:
            yield ctx.finding(
                "qformat-accumulator", _QFORMAT_FILE, 1,
                f"preset {name}: a Q·.{frac} partial times a 16-bit "
                "value spans more than 31 bits before the value shift")
        derived = (2**31 - 1) >> max(frac + 15 - value_frac - shift, 0)
        if name not in pinned:
            yield ctx.finding(
                "qformat-accumulator", _QFORMAT_FILE, 1,
                f"preset {name} has no pinned safe_nnz in "
                f"kernel_contracts.json (derived bound: {derived}) — add "
                "it to the qformat block")
        elif pinned[name] != derived:
            yield ctx.finding(
                "qformat-accumulator", _QFORMAT_FILE, 1,
                f"pinned safe_nnz[{name}]={pinned[name]} but the preset "
                f"table derives {derived} — a preset changed; update the "
                "qformat block in kernel_contracts.json (and any callers "
                "sized by the old bound)")

    for stale in sorted(set(pinned) - set(presets)):
        yield ctx.finding(
            "qformat-accumulator", _QFORMAT_FILE, 1,
            f"pinned safe_nnz entry {stale!r} matches no FIXED_PRESETS "
            "preset — drop it from kernel_contracts.json")

    if not any(isinstance(n, ast.FunctionDef)
               and n.name == "accumulator_safe_nnz"
               for n in ast.walk(tree)):
        yield ctx.finding(
            "qformat-accumulator", _QFORMAT_FILE, 1,
            "accumulator_safe_nnz is missing from core/qformat.py — "
            "callers must be able to ask for the bound the analysis "
            "proves")

    for rel, fname in _SHIFT_SITES:
        sfc = ctx.file(rel)
        if sfc is None:
            continue
        try:
            stree = sfc.tree
        except SyntaxError:
            continue
        fn = None
        for node in ast.walk(stree):
            if isinstance(node, ast.FunctionDef) and node.name == fname:
                fn = node
                break
        if fn is None:
            yield ctx.finding(
                "qformat-accumulator", rel, 1,
                f"{fname} not found in {rel} — the Alg.-2 shift check "
                "anchors there; update _SHIFT_SITES if it moved")
            continue
        has_matrix = any(
            _is_shift_by(n, lambda a: isinstance(a, ast.Name)
                         and a.id == "matrix_frac")
            for n in ast.walk(fn))
        has_value = any(
            _is_shift_by(n, lambda a: {"value_frac", "prec_shift"}
                         <= _names_in(a))
            for n in ast.walk(fn))
        if not has_matrix:
            yield ctx.finding(
                "qformat-accumulator", rel, fn.lineno,
                f"{fname} has no right shift by matrix_frac — without the "
                "per-multiply renormalization the int32 product bound "
                "(and accumulator_safe_nnz) no longer holds")
        if not has_value:
            yield ctx.finding(
                "qformat-accumulator", rel, fn.lineno,
                f"{fname} has no right shift by value_frac + prec_shift — "
                "the accumulator magnitude derivation assumes it")
