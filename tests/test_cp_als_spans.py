"""The spans of CP-ALS and of the layout build: the catalogued tree, the
disabled path that never reaches the JAX profiler, the bridge that puts
every span on the profiler's host plane, the Pallas layout's slot-fill
counters, and the jit names by which a device trace finds the MTTKRP."""
from __future__ import annotations

import os
import re
import subprocess
import sys
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core import cp_als, random_tensor
from repro.core.blocked import mttkrp_coo_blocked
from repro.core.chunking import chunk_tensor
from repro.core.partition import decide_kernel_partition
from repro.engine import PlanCache, build_engine
from repro.kernels import ops
from repro.obs import capture, default_registry, disable_tracing, get_tracer, span

#: Each `cp_als.*` span and the span it nests in (None: a root).
TREE = {
    "cp_als.decompose": None,
    "cp_als.init": "cp_als.decompose",
    "engine.build": "cp_als.decompose",
    "cp_als.iter": "cp_als.decompose",
    "cp_als.mode": "cp_als.iter",
    "cp_als.mttkrp": "cp_als.mode",
    "cp_als.solve": "cp_als.mode",
    "cp_als.sync": "cp_als.iter",
    "cp_als.fit": "cp_als.decompose",
    "cp_als.fit_norm": "cp_als.fit",
    "cp_als.fit_readback": "cp_als.fit",
    "cp_als.readback": "cp_als.decompose",
    "layout.chunk": "engine.build",
    "layout.kernel": "engine.build",
}
SHAPE, NNZ, RANK, ITERS = (12, 10, 8), 60, 3, 2


@pytest.fixture(autouse=True)
def _tracer_off():
    disable_tracing()
    yield
    disable_tracing()


@pytest.mark.parametrize("engine", ["ref", "pallas"])
def test_cp_als_emits_the_catalogued_tree(engine):
    st = random_tensor(SHAPE, NNZ, seed=0)
    with capture() as spans:
        cp_als(st, rank=RANK, n_iters=ITERS, engine=engine, track_diff=False)
    names = {s.span_id: s.name for s in spans}
    for s in spans:
        assert names.get(s.parent_id) == TREE[s.name], s.name
    n = len(SHAPE)
    want = {"cp_als.decompose": 1, "cp_als.init": 1, "engine.build": 1,
            "cp_als.iter": ITERS, "cp_als.mode": ITERS * n, "cp_als.mttkrp": ITERS * n,
            "cp_als.solve": ITERS * n, "cp_als.sync": ITERS, "cp_als.fit": ITERS,
            "cp_als.fit_norm": 1, "cp_als.fit_readback": ITERS, "cp_als.readback": 1}
    if engine == "pallas":
        want.update({"layout.chunk": 1, "layout.kernel": 1})
    assert Counter(s.name for s in spans) == want
    attrs = {s.name: s.attrs for s in spans}
    assert attrs["cp_als.init"] == {"rank": RANK, "shape": list(SHAPE)}
    assert attrs["cp_als.fit_norm"] == {"nnz": NNZ}
    assert attrs["engine.build"] == {"engine": engine, "nnz": NNZ, "rank": RANK}
    assert attrs["cp_als.decompose"]["engine"] == engine
    assert [s.attrs["mode"] for s in spans if s.name == "cp_als.mttkrp"] == [0, 1, 2] * ITERS
    assert [s.attrs["mode"] for s in spans if s.name == "cp_als.solve"] == [0, 1, 2] * ITERS


def test_tracing_off_emits_nothing_and_never_reaches_the_profiler(monkeypatch):
    def refuse(name):
        raise AssertionError(f"TraceAnnotation({name!r}) on the disabled path")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    st = random_tensor(SHAPE, NNZ, seed=1)
    before = len(get_tracer())
    cp_als(st, rank=RANK, n_iters=ITERS, engine="ref", track_diff=False)
    assert len(get_tracer()) == before
    with capture(), pytest.raises(AssertionError, match="TraceAnnotation"):
        with span("cp_als.decompose"):
            pass


def test_the_tracer_imports_jax_only_on_the_enabled_path():
    """`repro.obs.tracing` loaded alone: a disabled span leaves `jax`
    unimported; the first enabled span imports it for the annotation."""
    path = Path(__file__).resolve().parents[1] / "src" / "repro" / "obs" / "tracing.py"
    code = f"""
import importlib.util, sys
spec = importlib.util.spec_from_file_location("tracing_alone", {str(path)!r})
tracing = sys.modules["tracing_alone"] = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tracing)
with tracing.span("cp_als.iter"):
    pass
assert "jax" not in sys.modules, "a disabled span imported jax"
tracing.enable_tracing()
with tracing.span("cp_als.iter"):
    pass
assert "jax" in sys.modules and len(tracing.get_tracer()) == 1
"""
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr


def test_every_span_appears_on_the_profilers_host_plane(tmp_path):
    st = random_tensor(SHAPE, NNZ, seed=2)
    cp_als(st, rank=RANK, n_iters=1, engine="ref", track_diff=False)  # compile untraced
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with capture() as spans:
            cp_als(st, rank=RANK, n_iters=ITERS, engine="ref", track_diff=False)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    host = Counter(ev.name
                   for plane in jax.profiler.ProfileData.from_file(str(path)).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith(("cp_als.", "engine.")))
    assert host == Counter(s.name for s in spans)
    assert host["cp_als.fit_readback"] == ITERS


def test_kernel_layout_counts_its_slot_fill():
    """The `layout.kernel` span's attributes and the registry's counters
    give the layout's fill, nnz / (T·P), with T padded to whole calls."""
    def read(name, field="value"):
        return default_registry.snapshot().get(name, {}).get(field, 0)

    st = random_tensor((300, 40, 50), 1500, seed=3)
    ct = chunk_tensor(st, *decide_kernel_partition(st.shape, st.nnz))
    calls, per = ops.call_split(ct.num_tasks, st.ndim)
    before = [read("layout.kernel_nonzeros"), read("layout.kernel_slots"),
              read("layout.chunk_seconds", "count")]
    with capture() as spans:
        build_engine(st, "pallas", 4, plans=PlanCache())
    after = [read("layout.kernel_nonzeros"), read("layout.kernel_slots"),
             read("layout.chunk_seconds", "count")]
    attrs = {s.name: s.attrs for s in spans}
    assert attrs["layout.kernel"] == {"nnz": st.nnz, "tasks": calls * per,
                                      "slots_per_task": ct.capacity, "calls": calls}
    assert attrs["layout.chunk"] == {"chunk_shape": list(ct.chunk_shape),
                                     "tasks": ct.num_tasks, "capacity": ct.capacity}
    assert [a - b for a, b in zip(after, before)] == [st.nnz, calls * per * ct.capacity, 1]
    assert 0 < st.nnz / (calls * per * ct.capacity) < 1


def _module_name(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def test_mttkrp_executables_keep_their_jit_names():
    """A device trace's "XLA Modules" line names each executable after its
    jitted function; the benchmark finds the MTTKRP's by `mttkrp` in it."""
    st = random_tensor((300, 40, 50), 1500, seed=4)
    factors = tuple(jnp.ones((d, 4), jnp.float32) for d in st.shape)
    coo = mttkrp_coo_blocked.lower(factors, jnp.asarray(st.coords), jnp.asarray(st.values),
                                   mode=0, out_dim=st.shape[0])
    kt = ops.kernel_tensor(chunk_tensor(st, *decide_kernel_partition(st.shape, st.nnz)))
    pallas = ops._mttkrp_pallas.lower(
        factors, kt.task_chunk, kt.coords, kt.values, mode=0, chunk_shape=kt.chunk_shape,
        out_dim=st.shape[0], tasks_per_call=kt.tasks_per_call, interpret=True)
    assert [_module_name(coo), _module_name(pallas)] == [
        "jit_mttkrp_coo_blocked", "jit__mttkrp_pallas"]
