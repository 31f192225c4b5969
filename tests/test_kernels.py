"""Pallas kernel validation (interpret mode): shape sweeps against the
pure-jnp oracle in kernels/ref.py, plus hypothesis property tests.  Whether
the kernel compiles for the chip is tests/test_tpu_compile.py's job."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container — deterministic replay shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import random_tensor
from repro.core.chunking import chunk_tensor
from repro.core.mttkrp import mttkrp_coo
from repro.kernels import kernel_tensor, mttkrp_pallas
from repro.kernels import ref as kref
from repro.kernels.mttkrp_kernel import mttkrp_pallas_local
from repro.kernels.ops import pad_factor

SWEEP = [
    # shape, nnz, chunk_shape, capacity, rank
    ((32, 32, 32), 400, (8, 8, 8), 16, 4),
    ((40, 30, 50), 600, (16, 8, 16), 32, 8),
    ((17, 23, 9), 200, (8, 8, 4), 16, 3),
    ((20, 12, 20, 12), 300, (8, 4, 8, 4), 32, 5),
    ((8, 8, 8, 8, 8), 200, (4, 4, 4, 4, 4), 16, 2),
]


def _setup(shape, nnz, cs, cap, rank, seed=0):
    st_ = random_tensor(shape, nnz, seed=seed)
    rng = np.random.default_rng(seed + 1)
    factors = tuple(
        jnp.asarray(rng.uniform(-1, 1, (d, rank)).astype(np.float32))
        for d in shape)
    ct = chunk_tensor(st_, cs, capacity=cap)
    return st_, factors, ct


@pytest.mark.parametrize(("shape", "nnz", "cs", "cap", "rank"), SWEEP)
def test_float_kernel_local_vs_oracle(shape, nnz, cs, cap, rank):
    st_, factors, ct = _setup(shape, nnz, cs, cap, rank)
    padded = tuple(pad_factor(f, cs[m]) for m, f in enumerate(factors))
    # 8 tasks per call: every case spans several calls, the last padded.
    kt = kernel_tensor(ct, tasks_per_call=8)
    assert kt.calls > 1
    tc = jnp.asarray(ct.task_chunk)
    cr = jnp.asarray(ct.coords_rel)
    vals = jnp.asarray(ct.values)
    for mode in range(len(shape)):
        got = mttkrp_pallas_local(
            tuple(f.T for f in padded), kt.task_chunk, kt.coords, kt.values,
            mode=mode, chunk_shape=ct.chunk_shape,
            tasks_per_call=kt.tasks_per_call, interpret=True)
        want = kref.mttkrp_local_ref(padded, tc, cr, vals, mode=mode,
                                     chunk_shape=ct.chunk_shape)
        np.testing.assert_allclose(got[: ct.num_tasks].transpose(0, 2, 1),
                                   want, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(got[ct.num_tasks:], 0.0)


@pytest.mark.parametrize(("shape", "nnz", "cs", "cap", "rank"), SWEEP[:2])
def test_full_pallas_op_vs_coo(shape, nnz, cs, cap, rank):
    st_, factors, ct = _setup(shape, nnz, cs, cap, rank, seed=3)
    for mode in range(len(shape)):
        ref = mttkrp_coo(factors, jnp.asarray(st_.coords),
                         jnp.asarray(st_.values), mode=mode,
                         out_dim=shape[mode])
        out = mttkrp_pallas(factors, kernel_tensor(ct), mode=mode,
                            out_dim=shape[mode], interpret=True)
        np.testing.assert_allclose(ref, out, rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(
    dims=st.tuples(*[st.integers(6, 24)] * 3),
    nnz=st.integers(20, 300),
    rank=st.integers(1, 9),
    chunk=st.sampled_from([4, 8, 16]),
    cap=st.sampled_from([8, 16, 64]),
    seed=st.integers(0, 10_000),
)
def test_property_pallas_float_any_shape(dims, nnz, rank, chunk, cap, seed):
    st_ = random_tensor(dims, nnz, seed=seed)
    rng = np.random.default_rng(seed)
    factors = tuple(
        jnp.asarray(rng.uniform(-1, 1, (d, rank)).astype(np.float32))
        for d in dims)
    cs = tuple(min(chunk, d) for d in dims)
    ct = chunk_tensor(st_, cs, capacity=cap)
    mode = seed % 3
    ref = mttkrp_coo(factors, jnp.asarray(st_.coords), jnp.asarray(st_.values),
                     mode=mode, out_dim=dims[mode])
    out = mttkrp_pallas(factors, kernel_tensor(ct, tasks_per_call=5),
                        mode=mode, out_dim=dims[mode], interpret=True)
    np.testing.assert_allclose(ref, out, rtol=2e-4, atol=2e-4)
