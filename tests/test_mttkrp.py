"""Core spMTTKRP correctness: chunked == COO reference for every mode, every
engine, sweeping tensor shapes/orders; fixed point bit-exact vs Algorithm-2
oracle; baselines agree."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Q17_15, Q9_7, random_tensor, value_qformat
from repro.core.baselines import alto_order, mttkrp_alto, mttkrp_plain_coo
from repro.core.chunking import chunk_tensor
from repro.core.hetero import (densify_tasks, hetero_arrays, mttkrp_hetero,
                               split_tasks)
from repro.core.mttkrp import (dequantize_output, mttkrp_chunked,
                               mttkrp_chunked_fixed, mttkrp_coo,
                               mttkrp_coo_fixed)

CASES = [
    ((40, 30, 50), 500, (16, 8, 16), 32),
    ((17, 23, 9), 300, (8, 8, 4), 16),          # non-divisible dims
    ((64, 64, 64, 16), 800, (16, 16, 16, 8), 64),  # mode-4
    ((12, 10, 8, 6, 14), 400, (4, 4, 4, 4, 8), 32),  # mode-5
]


def _factors(shape, rank, seed=2):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.uniform(-1, 1, (d, rank)).astype(np.float32))
                 for d in shape)


@pytest.mark.parametrize(("shape", "nnz", "cs", "cap"), CASES)
def test_chunked_matches_coo_all_modes(shape, nnz, cs, cap):
    st = random_tensor(shape, nnz, seed=1)
    rank = 8
    factors = _factors(shape, rank)
    ct = chunk_tensor(st, cs, capacity=cap)
    assert ct.nnz == st.nnz
    for mode in range(len(shape)):
        ref = mttkrp_coo(factors, jnp.asarray(st.coords), jnp.asarray(st.values),
                         mode=mode, out_dim=shape[mode])
        out = mttkrp_chunked(factors, jnp.asarray(ct.task_chunk),
                             jnp.asarray(ct.coords_rel), jnp.asarray(ct.values),
                             mode=mode, chunk_shape=ct.chunk_shape,
                             out_dim=shape[mode])
        np.testing.assert_allclose(ref, out, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize(("qf", "prec_shift"), [(Q9_7, 0), (Q17_15, 3)])
@pytest.mark.parametrize(("shape", "nnz", "cs", "cap"), CASES[:3])
def test_fixed_chunked_bit_exact(shape, nnz, cs, cap, qf, prec_shift):
    st = random_tensor(shape, nnz, seed=3)
    rank = 6
    factors = _factors(shape, rank, seed=4)
    vq = value_qformat(st.values)
    qfs = tuple(qf.quantize(f) for f in factors)
    ct = chunk_tensor(st, cs, capacity=cap)
    qvals = jnp.asarray(vq.quantize_np(ct.values))
    qcoo = jnp.asarray(vq.quantize_np(st.values))
    for mode in range(len(shape)):
        ref = mttkrp_coo_fixed(qfs, jnp.asarray(st.coords), qcoo, mode=mode,
                               out_dim=shape[mode], matrix_frac=qf.frac_bits,
                               value_frac=vq.frac_bits, prec_shift=prec_shift)
        out = mttkrp_chunked_fixed(qfs, jnp.asarray(ct.task_chunk),
                                   jnp.asarray(ct.coords_rel), qvals,
                                   mode=mode, chunk_shape=ct.chunk_shape,
                                   out_dim=shape[mode],
                                   matrix_frac=qf.frac_bits,
                                   value_frac=vq.frac_bits,
                                   prec_shift=prec_shift)
        assert bool(jnp.all(ref == out)), f"mode {mode} not bit-exact"


def test_fixed_approximates_float():
    st = random_tensor((40, 30, 50), 600, seed=5)
    factors = _factors(st.shape, 8, seed=6)
    vq = value_qformat(st.values)
    qfs = tuple(Q9_7.quantize(f) for f in factors)
    qcoo = jnp.asarray(vq.quantize_np(st.values))
    ref = mttkrp_coo(factors, jnp.asarray(st.coords), jnp.asarray(st.values),
                     mode=0, out_dim=40)
    qout = mttkrp_coo_fixed(qfs, jnp.asarray(st.coords), qcoo, mode=0,
                            out_dim=40, matrix_frac=7, value_frac=vq.frac_bits)
    out = dequantize_output(qout, 7, 0)
    # Q9.7 quantization noise per partial ~2^-7; sums stay close.
    err = np.max(np.abs(np.asarray(out) - np.asarray(ref)))
    assert err < 0.5, err


def test_baselines_match():
    st = random_tensor((30, 40, 20), 700, seed=7)
    factors = _factors(st.shape, 5, seed=8)
    order = alto_order(st.coords, st.shape)
    for mode in range(3):
        ref = mttkrp_coo(factors, jnp.asarray(st.coords),
                         jnp.asarray(st.values), mode=mode,
                         out_dim=st.shape[mode])
        alto = mttkrp_alto(factors, jnp.asarray(st.coords[order]),
                           jnp.asarray(st.values[order]), mode=mode,
                           out_dim=st.shape[mode])
        plain = mttkrp_plain_coo(factors, jnp.asarray(st.coords),
                                 jnp.asarray(st.values), mode=mode,
                                 out_dim=st.shape[mode])
        np.testing.assert_allclose(ref, alto, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(ref, plain, rtol=1e-5, atol=1e-5)


def test_hetero_split_paths_match():
    st = random_tensor((24, 16, 24), 2500, seed=9)
    rank = 5
    factors = _factors(st.shape, rank, seed=10)
    ct = chunk_tensor(st, (8, 8, 8), capacity=512)
    for frac in (0.0, 0.5, 1.0):
        split = split_tasks(ct, rank, dense_fraction=frac)
        db = jnp.asarray(densify_tasks(ct, split.dense_idx))
        arrays = hetero_arrays(ct, split)
        for mode in range(3):
            ref = mttkrp_coo(factors, jnp.asarray(st.coords),
                             jnp.asarray(st.values), mode=mode,
                             out_dim=st.shape[mode])
            out = mttkrp_hetero(factors, ct, split, db, mode=mode,
                                out_dim=st.shape[mode], arrays=arrays)
            np.testing.assert_allclose(ref, out, rtol=1e-4, atol=1e-4)


def test_hetero_cost_model_split_is_valid():
    st = random_tensor((24, 16, 24), 2500, seed=11)
    ct = chunk_tensor(st, (8, 8, 8), capacity=64)
    split = split_tasks(ct, 8)
    all_idx = np.sort(np.concatenate([split.dense_idx, split.sparse_idx]))
    np.testing.assert_array_equal(all_idx, np.arange(ct.num_tasks))


@pytest.mark.parametrize("kernel", ["coo", "alto", "csf", "chunked"])
@pytest.mark.parametrize("block", [64, 97])
def test_blocked_kernels_match_unblocked(kernel, block):
    """The nnz-blocked drivers sum the same kernel over blocks of `block`
    nonzeros (97: the last block is clamped and partly masked)."""
    from repro.core import blocked
    from repro.formats.alto import build_alto
    from repro.formats.csf import build_csf_tree
    shape = (30, 20, 40)
    st = random_tensor(shape, 600, seed=4)
    factors = _factors(shape, 8)
    for mode in range(3):
        ref = mttkrp_coo(factors, jnp.asarray(st.coords),
                         jnp.asarray(st.values), mode=mode, out_dim=shape[mode])
        if kernel == "coo":
            out = blocked.mttkrp_coo_blocked(
                factors, jnp.asarray(st.coords), jnp.asarray(st.values),
                mode=mode, out_dim=shape[mode], block=block)
        elif kernel == "alto":
            at = build_alto(st)
            out = blocked.mttkrp_alto_blocked(
                factors, jnp.asarray(at.key_words), jnp.asarray(at.values),
                mode=mode, positions=at.positions, out_dim=shape[mode],
                block=block)
        elif kernel == "csf":
            t = build_csf_tree(st, mode)
            assert t.n_fibers > block  # fiber windows slide
            out = blocked.mttkrp_csf_blocked(
                factors, jnp.asarray(t.inner_coord), jnp.asarray(t.values),
                jnp.asarray(t.fiber_ids), jnp.asarray(t.fiber_coords),
                mode=mode, inner_mode=t.inner_mode, mid_modes=t.mid_modes,
                out_dim=shape[mode], n_fibers=t.n_fibers, block=block)
        else:
            # capacity 40 < block: whole tasks per block; then one task of
            # 600 slots > block: slices of a task per block.
            for cap in (40, None):
                ct = chunk_tensor(st, (16, 8, 16), capacity=cap)
                out = blocked.mttkrp_chunked_blocked(
                    factors, jnp.asarray(ct.task_chunk),
                    jnp.asarray(ct.coords_rel), jnp.asarray(ct.values),
                    mode=mode, chunk_shape=ct.chunk_shape,
                    out_dim=shape[mode], block=block)
                np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
            ct = chunk_tensor(st, shape, capacity=None)
            assert ct.capacity > block
            out = blocked.mttkrp_chunked_blocked(
                factors, jnp.asarray(ct.task_chunk), jnp.asarray(ct.coords_rel),
                jnp.asarray(ct.values), mode=mode, chunk_shape=ct.chunk_shape,
                out_dim=shape[mode], block=block)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
