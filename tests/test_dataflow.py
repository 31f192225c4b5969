"""Dataflow analysis tier: the shape/dtype lattices (property tests), the
abstract interpreter on fixture snippets, the kernel contract rules against
scratch repo copies (seeded shape mutations must fail), the width rules,
and the chunking int32-boundary regression the width analysis demanded."""
import json
import shutil
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, strategies as st

from repro.analysis import dataflow as df
from repro.analysis import engine as _engine
from repro.analysis import shape_rules as sr
from repro.analysis import width_rules as wr
from repro.analysis.__main__ import main as cli_main
from repro.analysis.engine import ProjectContext
from repro.core.chunking import chunk_tensor
from repro.core.qformat import FIXED_PRESETS, accumulator_safe_nnz
from repro.core.sptensor import SparseTensor

REPO = _engine.default_root()


def _src(snippet: str) -> str:
    return textwrap.dedent(snippet).lstrip("\n")


# ---------------------------------------------------------------------------
# Dim algebra
# ---------------------------------------------------------------------------

def test_dim_ceil_pad_idiom_normalizes():
    # rows + (-rows) % chunk  ==  least multiple of chunk >= rows
    rows, chunk = df.Dim.sym("I0"), df.Dim.sym("S0")
    padded = rows + ((-rows) % chunk)
    assert padded == df.Dim.atom(df.CeilMul(rows, chunk))
    assert padded.divisible_by(chunk)
    assert not rows.divisible_by(chunk)


def test_dim_negfloordiv_ceil_idiom():
    # -(-out // c) * c  ==  ceil-pad of out to c
    out, c = df.Dim.sym("I1"), df.Dim.sym("S1")
    padded = (-((-out) // c)) * c
    assert padded == df.Dim.atom(df.CeilMul(out, c))
    assert padded.divisible_by(c)


def test_dim_const_arithmetic_and_exact_div():
    d = df.Dim.const_(12) * df.Dim.sym("R")
    assert d.divisible_by(df.Dim.const_(4))
    assert d.divisible_by(df.Dim.sym("R"))
    padded = df.Dim.atom(df.CeilMul(df.Dim.sym("R"), df.Dim.const_(128)))
    assert padded.divisible_by(df.Dim.const_(128))


def test_join_dims_absorbs_padding():
    # if rpad or cpad: f = pad(f)  — the two branches join to the padded dim
    base = df.Dim.sym("I0")
    padded = df.Dim.atom(df.CeilMul(base, df.Dim.sym("S0")))
    assert df.join_dims(base, padded) == padded
    assert df.join_dims(padded, base) == padded
    assert df.join_dims(base, base) == base


def test_join_dims_unequal_has_no_refinement():
    # unrelated symbols have no common refinement; the interpreter then
    # falls back to a fresh opaque dim (never to either branch's value)
    assert df.join_dims(df.Dim.sym("A"), df.Dim.sym("B")) is None


_DIMS = st.sampled_from(["nnz", "T", "P", "R", "I0", "S0"])


@settings(max_examples=50, deadline=None)
@given(a=_DIMS, b=_DIMS, ca=st.integers(min_value=0, max_value=7),
       cb=st.integers(min_value=0, max_value=7))
def test_join_dims_commutative_idempotent(a, b, ca, cb):
    da = df.Dim.sym(a) + ca
    dbv = df.Dim.sym(b) + cb
    assert df.join_dims(da, da) == da
    j1, j2 = df.join_dims(da, dbv), df.join_dims(dbv, da)
    # commutative: both directions refine to the same dim, or neither does
    assert j1 == j2


# ---------------------------------------------------------------------------
# DType lattice
# ---------------------------------------------------------------------------

_STRONG = ["bool", "int8", "int16", "int32", "uint8", "uint16", "uint32",
           "float16", "float32"]


@settings(max_examples=60, deadline=None)
@given(a=st.sampled_from(_STRONG), b=st.sampled_from(_STRONG))
def test_promote_matches_jnp_x64_off(a, b):
    got = df.promote(df.parse_dtype(a), df.parse_dtype(b))
    want = (jnp.zeros((), a) + jnp.zeros((), b)).dtype
    assert str(got) == str(want), (a, b, str(got), str(want))


@settings(max_examples=40, deadline=None)
@given(a=st.sampled_from(_STRONG), b=st.sampled_from(_STRONG))
def test_promote_commutative_idempotent(a, b):
    da, dbv = df.parse_dtype(a), df.parse_dtype(b)
    assert df.promote(da, da) == df.canonicalize(da)
    assert df.promote(da, dbv) == df.promote(dbv, da)


def test_weak_scalar_promotion():
    # python float scalar + int32 array stays... float32 (weak float adopts
    # the array's category-promoted width), python int + int16 stays int16
    i16 = df.parse_dtype("int16")
    weak_int = df.DType("int", 32, weak=True)
    weak_float = df.DType("float", 32, weak=True)
    assert df.promote(weak_int, i16) == i16
    assert str(df.promote(weak_float, i16)) == str(
        (jnp.zeros((), "int16") + 1.0).dtype)


def test_canonicalize_x64_off():
    assert df.canonicalize(df.parse_dtype("int64")).bits == 32
    assert df.canonicalize(df.parse_dtype("float64")).bits == 32


# ---------------------------------------------------------------------------
# Interpreter fixtures
# ---------------------------------------------------------------------------

def _interpret(source, fname, args, kwargs=None):
    program = df.Program({"src/repro/core/snippet.py": _src(source)})
    module = program.module("src/repro/core/snippet.py")
    interp = df.Interpreter(program)
    result = interp.call_function(module.functions[fname], module,
                                  list(args), dict(kwargs or {}))
    return result, interp


DOT_MISMATCH = """
    import jax.numpy as jnp

    def f(a, b):
        return jnp.dot(a, b)
"""


def test_interpreter_flags_dot_contraction_mismatch():
    a = df.AArray((df.Dim.sym("P"), df.Dim.sym("S0")), df.parse_dtype("float32"))
    b = df.AArray((df.Dim.sym("S1"), df.Dim.sym("R")), df.parse_dtype("float32"))
    _, interp = _interpret(DOT_MISMATCH, "f", [a, b])
    assert any("contract" in p.message or "dot" in p.message
               for p in interp.problems), interp.problems


def test_interpreter_quiet_on_matching_dot():
    a = df.AArray((df.Dim.sym("P"), df.Dim.sym("S0")), df.parse_dtype("float32"))
    b = df.AArray((df.Dim.sym("S0"), df.Dim.sym("R")), df.parse_dtype("float32"))
    out, interp = _interpret(DOT_MISMATCH, "f", [a, b])
    assert not interp.problems
    assert isinstance(out, df.AArray)
    assert out.shape == (df.Dim.sym("P"), df.Dim.sym("R"))


def test_interpreter_flags_broadcast_mismatch_in_binop():
    src = """
        def f(a, b):
            return a * b
    """
    a = df.AArray((df.Dim.sym("T"), df.Dim.sym("P")), df.parse_dtype("float32"))
    b = df.AArray((df.Dim.sym("T"), df.Dim.sym("R")), df.parse_dtype("float32"))
    _, interp = _interpret(src, "f", [a, b])
    assert any("broadcast" in p.message for p in interp.problems)


def test_interpreter_quiet_on_unknowns():
    src = """
        def f(a):
            b = some_unknown_library_call(a)
            return b * a
    """
    a = df.AArray((df.Dim.sym("T"),), df.parse_dtype("float32"))
    _, interp = _interpret(src, "f", [a])
    assert not interp.problems


def test_interpreter_segment_sum_record():
    src = """
        import jax

        def f(part, seg, n):
            return jax.ops.segment_sum(part, seg, num_segments=n,
                                       indices_are_sorted=True)
    """
    part = df.AArray((df.Dim.sym("nnz"), df.Dim.sym("R")),
                     df.parse_dtype("float32"))
    seg = df.AArray((df.Dim.sym("nnz"),), df.parse_dtype("int32"))
    out, interp = _interpret(src, "f", [part, seg, df.AInt(df.Dim.sym("F"))])
    assert len(interp.segment_sums) == 1
    rec = interp.segment_sums[0]
    assert rec.num_segments == df.Dim.sym("F")
    assert rec.indices_are_sorted is True
    assert isinstance(out, df.AArray)
    assert out.shape == (df.Dim.sym("F"), df.Dim.sym("R"))


# ---------------------------------------------------------------------------
# Kernel contracts on the live tree and on mutated scratch copies
# ---------------------------------------------------------------------------

def _scratch_repo(tmp_path, mutate=None):
    """Copy src/repro (sources + contracts) to tmp; `mutate` is a
    (rel, old, new) source replacement applied on the way."""
    live = ProjectContext(REPO)
    for fc in live.walk("src/repro"):
        dst = tmp_path / fc.rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        src = fc.source
        if mutate and fc.rel == mutate[0]:
            assert mutate[1] in src, f"mutation anchor gone from {fc.rel}"
            src = src.replace(mutate[1], mutate[2])
        dst.write_text(src)
    shutil.copy(REPO / sr._CONTRACTS, tmp_path / sr._CONTRACTS)
    return ProjectContext(tmp_path)


def test_live_tree_contracts_clean():
    ctx = ProjectContext(REPO)
    report = sr.contract_report(ctx)
    assert report["shape"] == set(), sorted(report["shape"])
    assert report["pallas"] == set(), sorted(report["pallas"])
    assert list(sr.check_kernel_contract_drift(ctx)) == []


def test_mutation_num_segments_swap_is_caught(tmp_path):
    ctx = _scratch_repo(tmp_path, (
        "src/repro/core/mttkrp.py",
        "num_segments=n_fibers,", "num_segments=out_dim,"))
    report = sr.contract_report(ctx)
    assert any("num_segments" in msg for _, _, msg in report["shape"])


def test_mutation_sorted_flag_drop_is_caught(tmp_path):
    ctx = _scratch_repo(tmp_path, (
        "src/repro/core/mttkrp.py",
        "num_segments=out_dim, indices_are_sorted=True)",
        "num_segments=out_dim)"))
    report = sr.contract_report(ctx)
    assert any("indices_are_sorted" in msg for _, _, msg in report["shape"])


def test_mutation_blockspec_mode_rotation_is_caught(tmp_path):
    ctx = _scratch_repo(tmp_path, (
        "src/repro/kernels/mttkrp_kernel.py",
        "(rank, chunk_shape[m])", "(rank, chunk_shape[mode])"))
    report = sr.contract_report(ctx)
    assert any("divide" in msg for _, _, msg in report["pallas"])


def test_mutation_return_shape_is_caught(tmp_path):
    # transposing the output of the COO reference must break the
    # (dims[mode], rank) contract
    ctx = _scratch_repo(tmp_path, (
        "src/repro/core/mttkrp.py",
        "return out.at[coords[:, mode]].add(part, mode=\"drop\")",
        "return out.at[coords[:, mode]].add(part, mode=\"drop\").T"))
    report = sr.contract_report(ctx)
    assert report["shape"], "transposed return escaped the contract"


def test_signature_drift_is_caught(tmp_path):
    ctx = _scratch_repo(tmp_path, (
        "src/repro/core/mttkrp.py",
        "def mttkrp_coo(factors, coords, values, *, mode: int, out_dim: int):",
        "def mttkrp_coo(factors, coords, values, *, mode: int, n_rows: int):"))
    findings = list(sr.check_kernel_contract_drift(ctx))
    assert any("drifted" in f.message for f in findings)


def test_contract_json_drift_is_caught(tmp_path):
    ctx = _scratch_repo(tmp_path)
    contracts = json.loads((tmp_path / sr._CONTRACTS).read_text())
    key = "src/repro/core/mttkrp.py::mttkrp_coo"
    contracts["functions"][key]["signature"]["static_argnames"] = ["mode"]
    (tmp_path / sr._CONTRACTS).write_text(json.dumps(contracts))
    findings = list(sr.check_kernel_contract_drift(ctx))
    assert any("mttkrp_coo" in f.message and "drifted" in f.message
               for f in findings)


def test_missing_contract_file_is_one_clear_finding(tmp_path):
    ctx = _scratch_repo(tmp_path)
    (tmp_path / sr._CONTRACTS).unlink()
    findings = list(sr.check_kernel_contract_drift(ctx))
    assert len(findings) == 1
    assert "--regen-contracts" in findings[0].message


def test_regen_contracts_roundtrip_is_noop(tmp_path):
    _scratch_repo(tmp_path)
    before = (tmp_path / sr._CONTRACTS).read_text()
    sr.regen_contracts(tmp_path)
    assert (tmp_path / sr._CONTRACTS).read_text() == before


def test_regen_preserves_hand_contracts_drops_vanished(tmp_path):
    _scratch_repo(tmp_path)
    contracts = json.loads((tmp_path / sr._CONTRACTS).read_text())
    contracts["functions"]["src/repro/kernels/ref.py::vanished_fn"] = {
        "signature": None, "params": None, "returns": None,
        "segment_sums": None}
    (tmp_path / sr._CONTRACTS).write_text(json.dumps(contracts))
    out = sr.regen_contracts(tmp_path)
    assert "src/repro/kernels/ref.py::vanished_fn" not in out["functions"]
    kept = out["functions"]["src/repro/core/mttkrp.py::mttkrp_csf"]
    assert kept["segment_sums"] == [
        {"num_segments": "F", "sorted": True},
        {"num_segments": "dim[mode]", "sorted": True}]


# ---------------------------------------------------------------------------
# Width rules
# ---------------------------------------------------------------------------

INT32_NARROW_BAD = """
    import numpy as np

    def pack(coords, chunk_shape):
        cs = np.asarray(chunk_shape, dtype=np.int64)
        return coords // cs.astype(np.int32)
"""

INT32_NARROW_GOOD_GUARDED = """
    import numpy as np

    def pack(coords, chunk_shape):
        cs = np.asarray(chunk_shape, dtype=np.int64)
        if int(cs.max()) > np.iinfo(np.int32).max:
            raise ValueError("chunk extent exceeds int32")
        return coords // cs.astype(np.int32)
"""

INT32_NARROW_GOOD_NOT_WIDE = """
    import numpy as np

    def pack(coords):
        uniq = np.unique(coords, axis=0)
        return uniq.astype(np.int32)
"""


def _file_findings(rule_fn, source, rel="src/repro/core/snippet.py"):
    fc = _engine.FileContext.from_source(_src(source), rel)
    return list(rule_fn(fc))


def test_int32_index_width_fires_on_unguarded_narrow():
    findings = _file_findings(wr.check_int32_index_width, INT32_NARROW_BAD)
    assert len(findings) == 1
    assert "cs" in findings[0].message


def test_int32_index_width_quiet_when_guarded():
    assert _file_findings(wr.check_int32_index_width,
                          INT32_NARROW_GOOD_GUARDED) == []


def test_int32_index_width_quiet_on_untracked_values():
    assert _file_findings(wr.check_int32_index_width,
                          INT32_NARROW_GOOD_NOT_WIDE) == []


def test_int32_index_width_tracks_argsort():
    src = """
        import numpy as np

        def order(key):
            perm = np.argsort(key, kind="stable")
            return perm.astype(np.int32)
    """
    findings = _file_findings(wr.check_int32_index_width, src)
    assert len(findings) == 1 and "perm" in findings[0].message


def test_width_rules_clean_on_live_tree():
    ctx = ProjectContext(REPO)
    assert list(wr.check_alto_key_width(ctx)) == []
    assert list(wr.check_qformat_accumulator(ctx)) == []


def test_alto_key_width_catches_word_geometry_drift(tmp_path):
    ctx = _scratch_repo(tmp_path, (
        "src/repro/core/mttkrp.py",
        "key_words[:, p // 32]", "key_words[:, p // 64]"))
    findings = list(wr.check_alto_key_width(ctx))
    assert any("_alto_decode" in f.message and "64" in f.message
               for f in findings)


def test_alto_key_width_catches_byte_model_drift(tmp_path):
    ctx = _scratch_repo(tmp_path, (
        "src/repro/formats/alto.py",
        "return 4 * nnz * n_words", "return 8 * nnz * n_words"))
    findings = list(wr.check_alto_key_width(ctx))
    assert any("alto_index_bytes" in f.message for f in findings)


def test_qformat_accumulator_catches_overwide_preset(tmp_path):
    ctx = _scratch_repo(tmp_path, (
        "src/repro/core/qformat.py",
        "Q17_15 = QFormat(17, 15)", "Q17_15 = QFormat(17, 18)"))
    findings = list(wr.check_qformat_accumulator(ctx))
    assert any("int32" in f.message or "32" in f.message for f in findings)
    # the pinned safe_nnz no longer matches the re-derivation either
    assert any("safe_nnz" in f.message for f in findings)


def test_qformat_accumulator_catches_dropped_shift(tmp_path):
    ctx = _scratch_repo(tmp_path, (
        "src/repro/core/mttkrp.py",
        "part = jnp.right_shift(part, matrix_frac)", "pass"))
    findings = list(wr.check_qformat_accumulator(ctx))
    assert any("matrix_frac" in f.message for f in findings)


def test_accumulator_safe_nnz_pinned_values():
    assert accumulator_safe_nnz("int3") == 1048575
    assert accumulator_safe_nnz("int7") == 65535
    assert accumulator_safe_nnz("int15-12") == 2047
    for preset, (qf, shift) in FIXED_PRESETS.items():
        bound = accumulator_safe_nnz(preset)
        step = 1 << (qf.frac_bits + 15 - 7 - shift)
        assert bound * step <= 2**31 - 1 < (bound + 1) * step


# ---------------------------------------------------------------------------
# chunking int32 boundary regression (the fixed true positive)
# ---------------------------------------------------------------------------

def _tensor_with_shape(shape):
    coords = np.zeros((1, len(shape)), dtype=np.int32)
    return SparseTensor(coords, np.ones(1, dtype=np.float32), tuple(shape))


def test_chunk_tensor_rejects_past_int32_extent():
    # padded extent 2^31 + 8: max row index no longer fits int32
    st_big = _tensor_with_shape((2**31 + 1, 4))
    with pytest.raises(ValueError, match="int32"):
        chunk_tensor(st_big, (8, 4))


def test_chunk_tensor_accepts_near_boundary_extent():
    # padded extent == ceil(dim/chunk)*chunk == 2^31 - 8 < int32 max
    dim = 2**31 - 8
    ct = chunk_tensor(_tensor_with_shape((dim, 4)), (8, 4))
    assert ct.task_chunk.dtype == np.int32
    assert ct.coords_rel.dtype == np.int32


def test_chunk_tensor_small_unchanged():
    st_small = _tensor_with_shape((16, 8))
    ct = chunk_tensor(st_small, (4, 4))
    assert ct.task_chunk.shape[0] >= 1


# ---------------------------------------------------------------------------
# CLI: tiers, sarif, baseline
# ---------------------------------------------------------------------------

def test_cli_tier_split(capsys):
    assert cli_main(["--root", str(REPO), "--tier", "syntactic",
                     "--strict"]) == 0
    capsys.readouterr()
    assert cli_main(["--root", str(REPO), "--tier", "dataflow",
                     "--strict"]) == 0
    capsys.readouterr()


def test_cli_sarif_is_valid(capsys):
    assert cli_main(["--root", str(REPO), "--format", "sarif"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["version"] == "2.1.0"
    run = report["runs"][0]
    assert run["tool"]["driver"]["name"] == "repro.analysis"
    ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"kernel-shape-contract", "pallas-blockspec",
            "int32-index-width"} <= ids
    for r in run["tool"]["driver"]["rules"]:
        assert r["helpUri"].startswith("docs/static-analysis.md#")


def test_cli_baseline_masks_known_failures_only(tmp_path, capsys):
    # a scratch repo with one deliberate finding: baseline it, rerun clean,
    # then introduce a second finding and expect only that one to fail
    bad = _src("""
        import numpy as np

        def pack(x):
            k = np.asarray(x, dtype=np.int64)
            return k.astype(np.int32)
    """)
    repo = tmp_path / "repo"
    dst = repo / "src/repro/core/snippet.py"
    dst.parent.mkdir(parents=True, exist_ok=True)
    dst.write_text(bad)
    base = tmp_path / "baseline.json"
    args = ["--root", str(repo), "--rules", "int32-index-width"]
    assert cli_main(args) == 1
    capsys.readouterr()
    assert cli_main([*args, "--write-baseline", str(base)]) == 0
    capsys.readouterr()
    assert cli_main([*args, "--baseline", str(base)]) == 0
    capsys.readouterr()
    dst.write_text(bad + _src("""
        def pack2(x):
            k2 = np.asarray(x, dtype=np.int64)
            return k2.astype(np.int32)
    """))
    assert cli_main([*args, "--baseline", str(base), "--format",
                     "json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["counts"]["new"] == 1
    assert report["counts"]["active"] == 2
    assert "pack2" in report["new_findings"][0]["message"]


def test_cli_regen_contracts_noop_on_clean_tree(capsys):
    before = (REPO / sr._CONTRACTS).read_text()
    assert cli_main(["--root", str(REPO), "--regen-contracts"]) == 0
    capsys.readouterr()
    assert (REPO / sr._CONTRACTS).read_text() == before


def test_suppression_for_unselected_tier_not_flagged_unused():
    # hetero.py carries an int32-index-width suppression (dataflow tier);
    # a strict syntactic-only run must not call it unused
    result = _engine.run_analysis(REPO, tier="syntactic", strict=True)
    assert result.ok, [f.render() for f in result.findings]
