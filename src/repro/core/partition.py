"""Hierarchical partition decider (paper §IV-B, Fig. 5).

Partitioning preference order (replication-minimizing):
  1. rank partitioning      — free: no factor replication, tensor replicated
                              once and resident across CP-ALS iterations;
  2. dimension-size part.   — bounds factor bytes per device, replicates
                              factor rows at chunk boundaries;
  3. nonzero partitioning   — bounds tensor bytes per device, maximal
                              replication + output sum reduction.

The decider iteratively shrinks the chunk shape (halving the largest chunk
dim) until the *device density* — nonzeros a device can hold given the factor
slice it must also hold — reaches the tensor density.  For balanced tensors
this lands on the minimum number of chunks with no nonzero partitioning; for
imbalanced tensors it stops early and lets nonzero partitioning absorb the
hot chunks rather than over-shrinking the grid (paper Fig. 5).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from .sptensor import SparseTensor

__all__ = ["PartitionPlan", "decide_kernel_partition", "decide_partition",
           "DPU_MRAM_BYTES"]

DPU_MRAM_BYTES = 64 * 1024 * 1024  # UPMEM per-DPU MRAM; the per-PE budget knob.

#: Chunk edge of the Pallas kernel's plan: a chunk's (R, S) factor block is
#: lane-major in S, so S is a multiple of 128 (or a whole mode), and its
#: (S, P) one-hot tiles must sit in VMEM.
KERNEL_CHUNK = 256
#: Task capacity P of the kernel plan, in lanes: [128, 1024].
KERNEL_CAPACITY = (128, 1024)


def decide_kernel_partition(shape: tuple[int, ...],
                            nnz: int) -> tuple[tuple[int, ...], int]:
    """(chunk_shape, capacity) for the Pallas kernel.

    The MRAM-sized plan of `decide_partition` gives chunks of thousands of
    rows and tasks of millions of nonzeros, whose one-hot tiles could never
    fit VMEM.  Here chunks are `KERNEL_CHUNK` rows per mode (a smaller mode
    is one whole chunk), and P is the expected population of an occupied
    chunk plus four standard deviations, rounded up to whole 128-lane
    vectors, so a uniform tensor rarely splits a chunk.  Skewed tensors
    split hot chunks into several tasks (nonzero partitioning)."""
    chunk = tuple(min(int(d), KERNEL_CHUNK) for d in shape)
    cells = math.prod(-(-int(d) // s) for d, s in zip(shape, chunk, strict=True))
    pop = nnz / max(1, min(cells, nnz))
    lo, hi = KERNEL_CAPACITY
    cap = 128 * math.ceil((pop + 4 * math.sqrt(pop)) / 128)
    return chunk, int(min(max(cap, lo), hi))


@dataclasses.dataclass(frozen=True)
class PartitionPlan:
    chunk_shape: tuple[int, ...]
    capacity: int                  # max nonzeros per task
    rank_block: int                # ranks per device (rank partitioning)
    n_rank_partitions: int
    est_chunks: int                # grid size (upper bound on nonempty chunks)
    factor_bytes_per_device: int
    tensor_bytes_per_device: int
    device_density: float
    tensor_density: float
    kernel_iterations: int         # >1 when partitions exceed device count

    @property
    def mem_bytes_per_device(self) -> int:
        return self.factor_bytes_per_device + self.tensor_bytes_per_device


def decide_partition(
    st: SparseTensor,
    rank: int,
    *,
    mem_bytes: int = DPU_MRAM_BYTES,
    factor_elt_bytes: int = 2,     # Q9.7 int16 (paper's preferred mode-3 format)
    value_bytes: int = 2,          # 16-bit tensor values (paper §IV-C)
    coord_bytes: int = 4,
    n_devices: int = 2560,
    rank_axis: int | None = None,  # fixed rank partitions (mesh model axis)
) -> PartitionPlan:
    """Run the Fig. 5 decider. Returns a PartitionPlan; the actual chunking is
    done by `chunking.chunk_tensor(st, plan.chunk_shape, plan.capacity)`."""
    n = st.ndim
    nnz_bytes = value_bytes + coord_bytes * n
    tensor_density = st.density

    # Rank partitioning first (paper: favored — no replication).  Each rank
    # partition handles `rank_block` columns of every factor matrix; default:
    # as many rank partitions as possible while one tensor partition can
    # still use all devices (the decider below refines tensor partitions).
    n_rank = (rank_axis if rank_axis is not None
              else max(1, min(rank, n_devices)))
    rank_block = -(-rank // n_rank)

    chunk_shape = [int(d) for d in st.shape]

    def factor_bytes(cs):
        # One factor slice per mode, rank_block columns each.
        return sum(s * rank_block * factor_elt_bytes for s in cs)

    def capacity_for(cs):
        avail = mem_bytes - factor_bytes(cs)
        return avail // nnz_bytes

    while True:
        cap = capacity_for(chunk_shape)
        if cap >= 1:
            device_density = cap / math.prod(chunk_shape)
            if device_density >= tensor_density:
                break
        # Halve the largest chunk dimension (paper: iterative dim-size step).
        m = int(np.argmax(chunk_shape))
        if chunk_shape[m] == 1:
            # Cannot shrink further — tensor region denser than a device can
            # mirror; rely on nonzero partitioning.
            cap = max(int(cap), 1)
            device_density = cap / math.prod(chunk_shape)
            break
        chunk_shape[m] = -(-chunk_shape[m] // 2)

    cap = max(int(capacity_for(chunk_shape)), 1)
    grid = [int(-(-i // s)) for i, s in zip(st.shape, chunk_shape, strict=True)]
    est_chunks = math.prod(grid)
    # Expected tasks ≈ nonempty chunks (+ splits); bound by nnz.
    est_tasks = min(est_chunks, st.nnz)
    total_partitions = est_tasks * n_rank
    kernel_iterations = max(1, -(-total_partitions // n_devices))

    return PartitionPlan(
        chunk_shape=tuple(chunk_shape),
        capacity=cap,
        rank_block=rank_block,
        n_rank_partitions=n_rank,
        est_chunks=est_chunks,
        factor_bytes_per_device=factor_bytes(chunk_shape),
        tensor_bytes_per_device=cap * nnz_bytes,
        device_density=float(cap / math.prod(chunk_shape)),
        tensor_density=float(tensor_density),
        kernel_iterations=int(kernel_iterations),
    )
