"""nnz-blocked drivers for the jnp MTTKRP kernels.

Every jnp kernel in `mttkrp.py` materialises one (nnz, R) f32 partial.  On a
TPU its rank axis pads to 128 lanes, so at FROSTT nell2's size (76.9M nnz,
R = 16) that one temporary needs 39 GB of the chip's 16 GB.  These drivers
run the same kernels over fixed-size blocks of nonzeros in a `fori_loop`
and sum the block outputs: MTTKRP is linear in the values, so the sum is the
same MTTKRP up to summation order.  A tensor that fits in one block takes
the kernel's own path unchanged.

Every block has one static size.  The last block's start is clamped back
into bounds, and the rows an earlier block already covered get value 0.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .mttkrp import mttkrp_alto, mttkrp_chunked, mttkrp_coo, mttkrp_csf

__all__ = [
    "NNZ_BLOCK",
    "mttkrp_alto_blocked",
    "mttkrp_chunked_blocked",
    "mttkrp_coo_blocked",
    "mttkrp_csf_blocked",
]

#: Nonzeros per block: a (block, R) f32 partial padded to 128 lanes is 512 MiB.
NNZ_BLOCK = 1 << 20


def _clamped(k, n: int, size: int):
    """Start of block `k` of `size` rows out of `n`, clamped into bounds,
    and the mask of its rows that no earlier block covered."""
    start = jnp.minimum(k * size, n - size)
    return start, start + jnp.arange(size) >= k * size


def _block_sum(fn, n_blocks: int, init):
    return lax.fori_loop(0, n_blocks, lambda k, acc: acc + fn(k), init)


def _zeros(factors, out_dim: int):
    return jnp.zeros((out_dim, factors[0].shape[1]), jnp.float32)


@partial(jax.jit, static_argnames=("mode", "out_dim", "block"))
def mttkrp_coo_blocked(factors, coords, values, *, mode: int, out_dim: int,
                       block: int = NNZ_BLOCK):
    """`mttkrp_coo` over blocks of `block` nonzeros."""
    n = values.shape[0]
    if n <= block:
        return mttkrp_coo(factors, coords, values, mode=mode, out_dim=out_dim)

    def one(k):
        s, keep = _clamped(k, n, block)
        v = jnp.where(keep, lax.dynamic_slice_in_dim(values, s, block), 0.0)
        return mttkrp_coo(factors, lax.dynamic_slice_in_dim(coords, s, block),
                          v, mode=mode, out_dim=out_dim)
    return _block_sum(one, -(-n // block), _zeros(factors, out_dim))


@partial(jax.jit, static_argnames=("mode", "positions", "out_dim", "block"))
def mttkrp_alto_blocked(factors, key_words, values, *, mode: int,
                        positions: tuple[tuple[int, ...], ...], out_dim: int,
                        block: int = NNZ_BLOCK):
    """`mttkrp_alto` over blocks of `block` nonzeros of the key stream."""
    n = values.shape[0]
    if n <= block:
        return mttkrp_alto(factors, key_words, values, mode=mode,
                           positions=positions, out_dim=out_dim)

    def one(k):
        s, keep = _clamped(k, n, block)
        v = jnp.where(keep, lax.dynamic_slice_in_dim(values, s, block), 0.0)
        return mttkrp_alto(factors, lax.dynamic_slice_in_dim(key_words, s, block),
                           v, mode=mode, positions=positions, out_dim=out_dim)
    return _block_sum(one, -(-n // block), _zeros(factors, out_dim))


@partial(jax.jit, static_argnames=("mode", "inner_mode", "mid_modes",
                                   "out_dim", "n_fibers", "block"))
def mttkrp_csf_blocked(factors, inner_coord, values, fiber_ids, fiber_coords,
                       *, mode: int, inner_mode: int,
                       mid_modes: tuple[int, ...], out_dim: int,
                       n_fibers: int, block: int = NNZ_BLOCK):
    """`mttkrp_csf` over blocks of `block` nonzeros.  Fiber ids are sorted
    and dense, so a block's fibers are a window of at most `block`
    consecutive fibers; a fiber cut by a block edge contributes a partial
    fiber sum from each side, which the output sum adds back together."""
    n = values.shape[0]
    if n <= block:
        return mttkrp_csf(factors, inner_coord, values, fiber_ids,
                          fiber_coords, mode=mode, inner_mode=inner_mode,
                          mid_modes=mid_modes, out_dim=out_dim,
                          n_fibers=n_fibers)
    window = min(block, n_fibers)

    def one(k):
        s, keep = _clamped(k, n, block)
        fid = lax.dynamic_slice_in_dim(fiber_ids, s, block)
        base = jnp.minimum(fid[0], n_fibers - window)
        v = jnp.where(keep, lax.dynamic_slice_in_dim(values, s, block), 0.0)
        return mttkrp_csf(
            factors, lax.dynamic_slice_in_dim(inner_coord, s, block), v,
            fid - base, lax.dynamic_slice_in_dim(fiber_coords, base, window),
            mode=mode, inner_mode=inner_mode, mid_modes=mid_modes,
            out_dim=out_dim, n_fibers=window)
    return _block_sum(one, -(-n // block), _zeros(factors, out_dim))


@partial(jax.jit, static_argnames=("mode", "chunk_shape", "out_dim", "block"))
def mttkrp_chunked_blocked(factors, task_chunk, coords_rel, values, *,
                           mode: int, chunk_shape: tuple[int, ...],
                           out_dim: int, block: int = NNZ_BLOCK):
    """`mttkrp_chunked` over blocks of about `block` nonzero slots: whole
    tasks when a task holds fewer, else slices of one task's slots."""
    t, p = values.shape
    if t * p <= block:
        return mttkrp_chunked(factors, task_chunk, coords_rel, values,
                              mode=mode, chunk_shape=chunk_shape,
                              out_dim=out_dim)
    pb = min(p, block)
    tb = max(1, block // p)
    n_p = -(-p // pb)

    def one(k):
        t0, keep_t = _clamped(k // n_p, t, tb)
        p0, keep_p = _clamped(k % n_p, p, pb)
        v = lax.dynamic_slice(values, (t0, p0), (tb, pb))
        v = jnp.where(keep_t[:, None] & keep_p[None, :], v, 0.0)
        return mttkrp_chunked(
            factors,
            lax.dynamic_slice_in_dim(task_chunk, t0, tb),
            lax.dynamic_slice(coords_rel, (t0, p0, 0),
                              (tb, pb, coords_rel.shape[2])),
            v, mode=mode, chunk_shape=chunk_shape, out_dim=out_dim)
    return _block_sum(one, -(-t // tb) * n_p, _zeros(factors, out_dim))
