"""Heterogeneous execution (paper §IV-D), adapted to TPU.

The paper splits spMTTKRP between UPMEM PIM (chunks dense enough to fill a
DPU) and the CPU (the rest, via ALTO).  The TPU-native analogue keeps the
same *scheduler* but retargets the two executors:

  * dense path  — chunks above a density threshold are densified into small
    dense blocks and dispatched to an einsum that runs on the MXU at full
    systolic throughput (the "device the work fits best" ≡ PIM role);
  * sparse path — remaining chunks run the gather/scatter chunked kernel
    (≡ CPU/ALTO role).

The split is decided statically from per-task density with a FLOP/byte cost
model, mirroring the paper's densest-first, fits-in-one-DPU ordering.
"""
from __future__ import annotations

import dataclasses
import math
import string
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .chunking import ChunkedTensor
from .blocked import mttkrp_chunked_blocked
from .mttkrp import gather_factor_blocks

__all__ = ["HeteroSplit", "split_tasks", "hetero_arrays", "mttkrp_hetero",
           "dense_path_cost", "sparse_path_cost"]


def dense_path_cost(chunk_shape, rank: int) -> float:
    """MACs for one densified chunk on the MXU (all modes share one block)."""
    return math.prod(chunk_shape) * rank * (len(chunk_shape) - 1)


def sparse_path_cost(capacity: int, chunk_shape, rank: int) -> float:
    """MACs + gather overhead for one task on the sparse path."""
    n = len(chunk_shape)
    mults = capacity * rank * n
    gather_overhead = capacity * rank * 2  # index arithmetic / one-hot waste
    return mults + gather_overhead


@dataclasses.dataclass(frozen=True)
class HeteroSplit:
    dense_idx: np.ndarray   # task indices on the dense (MXU) path
    sparse_idx: np.ndarray  # task indices on the sparse path
    threshold: float

    @property
    def dense_fraction(self) -> float:
        total = self.dense_idx.size + self.sparse_idx.size
        return self.dense_idx.size / max(total, 1)


MAX_DENSE_VOLUME = 1 << 22  # dense blocks must fit the executor (the DPU-
                            # capacity analogue for the MXU path)


def split_tasks(
    ct: ChunkedTensor,
    rank: int,
    *,
    dense_fraction: float | None = None,
    max_dense_volume: int = MAX_DENSE_VOLUME,
) -> HeteroSplit:
    """Static split.  Default threshold from the cost model: a task goes dense
    when densifying is cheaper than gathering.  `dense_fraction` overrides the
    threshold with a paper-style static workload fraction (densest-first).
    Chunks whose dense form exceeds `max_dense_volume` elements never go
    dense — mirroring the paper's only-what-fits-a-DPU rule."""
    density = ct.nnz_per_task / max(math.prod(ct.chunk_shape), 1)
    if math.prod(ct.chunk_shape) > max_dense_volume:
        return HeteroSplit(np.zeros((0,), np.int32),
                           np.arange(ct.num_tasks, dtype=np.int32),
                           float("inf"))
    if dense_fraction is not None:
        k = int(round(dense_fraction * ct.num_tasks))
        order = np.argsort(-density, kind="stable")
        dense = order[:k]
        sparse = order[k:]
        thr = float(density[dense[-1]]) if k else float("inf")
    else:
        cost_d = dense_path_cost(ct.chunk_shape, rank)
        # Per-task sparse cost scales with its live nonzeros.
        cost_s = np.array(
            [sparse_path_cost(int(c), ct.chunk_shape, rank) for c in ct.nnz_per_task]
        )
        dense_mask = cost_d < cost_s
        dense = np.nonzero(dense_mask)[0]
        sparse = np.nonzero(~dense_mask)[0]
        thr = cost_d / max(
            sparse_path_cost(1, ct.chunk_shape, rank) * math.prod(ct.chunk_shape), 1
        )
    # repro-lint: disable=int32-index-width -- task-index permutation; task count is nnz/capacity and nnz is itself int32-bounded (coords are int32)
    return HeteroSplit(dense.astype(np.int32), sparse.astype(np.int32), thr)


def densify_tasks(ct: ChunkedTensor, idx: np.ndarray) -> np.ndarray:
    """(Td, S_0, ..., S_{N-1}) dense blocks for the selected tasks."""
    n = ct.ndim
    out = np.zeros((idx.size, *ct.chunk_shape), dtype=np.float32)
    for o, i in enumerate(idx):
        c = int(ct.nnz_per_task[i])
        if c:
            np.add.at(out[o], tuple(ct.coords_rel[i, :c].T), ct.values[i, :c])
    return out


@partial(jax.jit, static_argnames=("mode", "chunk_shape", "out_dim"))
def _dense_path(
    factors, dense_blocks, dense_task_chunk, *, mode, chunk_shape, out_dim
):
    """einsum over densified chunks: e.g. mode-2 3D → 'tij k,tir,tjr->tkr'."""
    n = len(factors)
    rank = factors[0].shape[1]
    offsets = dense_task_chunk * jnp.asarray(chunk_shape, dtype=jnp.int32)
    letters = string.ascii_lowercase
    t_sub = "t" + "".join(letters[m] for m in range(n))
    operands, subs = [dense_blocks], [t_sub]
    for m in range(n):
        if m == mode:
            continue
        blk = gather_factor_blocks(factors[m], offsets[:, m], chunk_shape[m])
        operands.append(blk)
        subs.append(f"t{letters[m]}r")
    out_sub = f"t{letters[mode]}r"
    # HIGHEST: at default precision the MXU rounds the f32 operands to
    # bf16, which would make this path lossy.
    local = jnp.einsum(",".join(subs) + "->" + out_sub, *operands,
                       precision=jax.lax.Precision.HIGHEST)  # (Td, S, R)
    out = jnp.zeros((out_dim, rank), jnp.float32)
    rows = offsets[:, mode : mode + 1] + jnp.arange(chunk_shape[mode])[None, :]
    return out.at[rows.reshape(-1)].add(local.reshape(-1, rank), mode="drop")


def hetero_arrays(ct: ChunkedTensor, split: HeteroSplit,
                  full: dict | None = None) -> dict:
    """Device arrays of both paths, placed once per engine.  `full` holds
    the whole tensor's chunked arrays (`chunked_device_arrays`); when no
    task goes dense they are the sparse path's arrays, and nothing is
    copied."""
    if full is not None and not split.dense_idx.size:
        sparse = full
    else:
        sparse = dict(task_chunk=jnp.asarray(ct.task_chunk[split.sparse_idx]),
                      coords_rel=jnp.asarray(ct.coords_rel[split.sparse_idx]),
                      values=jnp.asarray(ct.values[split.sparse_idx]))
    return dict(dense_task_chunk=jnp.asarray(ct.task_chunk[split.dense_idx]),
                sparse=sparse)


def mttkrp_hetero(
    factors,
    ct: ChunkedTensor,
    split: HeteroSplit,
    dense_blocks,
    *,
    mode: int,
    out_dim: int,
    arrays: dict,
):
    """Run both paths and sum (the paper's final CPU+PIM combine).
    `arrays` is `hetero_arrays(ct, split)`, placed on the device once."""
    out = jnp.zeros((out_dim, factors[0].shape[1]), jnp.float32)
    if split.dense_idx.size:
        out = out + _dense_path(
            factors,
            dense_blocks,
            arrays["dense_task_chunk"],
            mode=mode,
            chunk_shape=ct.chunk_shape,
            out_dim=out_dim,
        )
    if split.sparse_idx.size:
        sparse = arrays["sparse"]
        out = out + mttkrp_chunked_blocked(
            factors,
            sparse["task_chunk"],
            sparse["coords_rel"],
            sparse["values"],
            mode=mode,
            chunk_shape=ct.chunk_shape,
            out_dim=out_dim,
        )
    return out
