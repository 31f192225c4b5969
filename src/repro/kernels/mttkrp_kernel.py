"""Pallas TPU kernel for chunked spMTTKRP (float path).

TPU codesign of the PRISM "DPU program" (DESIGN.md §2):

  * grid = one step per chunk *task* (the DPU analogue);
  * the task's nonzero block (values + relative coords) is streamed
    HBM→VMEM by the Pallas pipeline — the UPMEM *sequential readers*;
  * the factor blocks each task needs are fetched with **data-dependent
    BlockSpec index maps driven by the scalar-prefetched task table**: block
    index of factor m at grid step i is `tc[i·N + m]`.  This is the
    chunked format's defining property (a chunk pins its factor rows) turned
    into a hardware prefetch rule;
  * per-nonzero gathers/scatters are re-expressed as one-hot matmuls so the
    MXU does them (UPMEM's cheap near-memory random access has no TPU
    equivalent; the systolic array is the TPU-native substitute);
  * each task writes a private (R, S_out) partial block; the global sum
    reduction happens outside the kernel — exactly where the paper puts it
    (host-side reduction of per-DPU partials).

Layout.  Everything the kernel streams is lane-major along a task's P
nonzero slots, and the whole computation runs transposed:

  coords[m] : (T, 1, P) int32 per mode; values : (T, 1, P) f32
  factors   : (R, G_m·S_m) f32 — transposed, so a chunk's block is (R, S_m)
  out       : (T, R, S_mode) f32 per-task partials

  rowsᵀ (R, P) = F_blkᵀ (R, S) @ onehotᵀ (S, P)   per input mode
  outᵀ  (R, S) = partᵀ (R, P) @ onehot_outᵀ (S, P)ᵀ

A (T, P, N) coordinate block would put N on the 128-wide lane axis, which
HBM then pads 128/N-fold, and a (1, P) values block is not a legal TPU
tile; (1, 1, P) blocks of (T, 1, P) arrays are both legal and unpadded.
Chunk sizes S_m must be multiples of 128 or the full (padded) mode size.

The (T·N,) task table lives in SMEM, which holds about 1 MiB, so one call
covers at most `max_tasks_per_call(N)` tasks; `ops.py` runs the calls over
the task axis.  Products are exact f32: the one-hot operands are exact, and
`precision=HIGHEST` keeps the factor values from being rounded to bf16 on
the MXU.

VMEM per step (S=256, P=1024, R=16, f32): one-hot tiles 2·S·P·4 = 2 MiB,
factor blocks N·R·S·4 = 48 KiB, nonzero blocks (N+1)·P·4 = 16 KiB, out
R·S·4 = 16 KiB — double-buffered, well inside the 16 MiB default scope.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["SMEM_TABLE_WORDS", "kernel_call", "max_tasks_per_call",
           "mttkrp_pallas_local"]

#: int32 words of task table one call may scalar-prefetch (256 KiB of the
#: ~1 MiB of SMEM the v5e compiler allows).
SMEM_TABLE_WORDS = 1 << 16

_HIGHEST = lax.Precision.HIGHEST


def max_tasks_per_call(ndim: int) -> int:
    """Tasks one `pallas_call` may cover: its (tasks·N,) table fits SMEM."""
    return max(1, SMEM_TABLE_WORDS // ndim)


def _kernel(mode, input_modes, chunk_shape, first_ref, tc_ref, *refs):
    del first_ref, tc_ref  # consumed by the index maps
    n = len(chunk_shape)
    coord_refs, values_ref = refs[:n], refs[n]
    factor_refs, out_ref = refs[n + 1:-1], refs[-1]
    p = values_ref.shape[-1]
    part = values_ref[0]  # (1, P)
    for j, m in enumerate(input_modes):
        onehot = (lax.broadcasted_iota(jnp.int32, (chunk_shape[m], p), 0)
                  == coord_refs[m][0])  # (S_m, P)
        rows = jnp.dot(factor_refs[j][...], onehot.astype(jnp.float32),
                       precision=_HIGHEST,
                       preferred_element_type=jnp.float32)  # (R, P) on MXU
        part = part * rows
    # Padding slots have value 0 → their scatter contribution is 0.
    oh_out = (lax.broadcasted_iota(jnp.int32, (chunk_shape[mode], p), 0)
              == coord_refs[mode][0])  # (S_out, P)
    out_ref[0] = lax.dot_general(
        part, oh_out.astype(jnp.float32), (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32)  # (R, S_out)


def kernel_call(factors_t, task_chunk, coords, values, call, *,
                mode: int, chunk_shape: tuple[int, ...], tasks_per_call: int,
                interpret: bool = False):
    """Partials of tasks [call·tasks_per_call, (call+1)·tasks_per_call):
    (tasks_per_call, R, S_mode) f32.  `call` may be traced (a scan index)."""
    n = len(chunk_shape)
    per = tasks_per_call
    rank = factors_t[0].shape[0]
    p = values.shape[-1]
    s_out = chunk_shape[mode]
    input_modes = tuple(m for m in range(n) if m != mode)
    first = jnp.reshape(call * per, (1,)).astype(jnp.int32)
    table = lax.dynamic_slice_in_dim(task_chunk, call * per * n, per * n)

    nz = pl.BlockSpec((1, 1, p), lambda i, f, tc: (f[0] + i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(per,),
        in_specs=[
            *[nz for _ in range(n + 1)],
            *[
                pl.BlockSpec(
                    (rank, chunk_shape[m]),
                    # Data-dependent fetch: which factor block this task needs.
                    functools.partial(lambda i, f, tc, m=m: (0, tc[i * n + m])),
                )
                for m in input_modes
            ],
        ],
        out_specs=pl.BlockSpec((1, rank, s_out), lambda i, f, tc: (i, 0, 0)),
    )
    kernel = functools.partial(_kernel, mode, input_modes, chunk_shape)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((per, rank, s_out), jnp.float32),
        interpret=interpret,
        name=f"mttkrp_mode{mode}",
    )(first, table, *coords, values, *[factors_t[m] for m in input_modes])


@functools.partial(
    jax.jit,
    static_argnames=("mode", "chunk_shape", "tasks_per_call", "interpret"))
def mttkrp_pallas_local(
    factors_t, task_chunk, coords, values, *,
    mode: int, chunk_shape: tuple[int, ...], tasks_per_call: int,
    interpret: bool = False,
):
    """Per-task partial MTTKRP: returns (T, R, S_mode) chunk-local blocks.

    factors_t  : tuple of (R, G_m·S_m) f32 — transposed, rows padded to a
                 whole number of chunks (ops.py does both).
    task_chunk : (T·N,) int32 flat task→chunk table.
    coords     : tuple of N (T, 1, P) int32; values: (T, 1, P) f32.
    T must be a multiple of `tasks_per_call`.
    """
    calls = values.shape[0] // tasks_per_call
    local = lax.map(
        lambda c: kernel_call(factors_t, task_chunk, coords, values, c,
                              mode=mode, chunk_shape=chunk_shape,
                              tasks_per_call=tasks_per_call,
                              interpret=interpret),
        jnp.arange(calls))
    return local.reshape(-1, *local.shape[2:])
