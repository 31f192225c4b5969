"""jit'd public wrappers around the Pallas MTTKRP kernel.

`kernel_tensor` moves a `ChunkedTensor` into the kernel's device layout
once (per-mode lane-major coordinates, task axis padded to whole calls);
`mttkrp_pallas` transposes and pads the factors, runs one kernel call per
SMEM-sized slice of the task axis, and sums each call's per-task partials
into the output (the paper's global sum reduction) before unpadding.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.chunking import ChunkedTensor
from .mttkrp_kernel import kernel_call, max_tasks_per_call

__all__ = ["KernelTensor", "call_split", "kernel_tensor", "mttkrp_pallas",
           "pad_factor"]


def pad_factor(f, chunk: int):
    """Pad rows to a whole number of chunks."""
    rpad = (-f.shape[0]) % chunk
    return jnp.pad(f, ((0, rpad), (0, 0))) if rpad else f


@dataclasses.dataclass(frozen=True)
class KernelTensor:
    """A chunked tensor in the Pallas kernel's device layout.

    task_chunk : (T·N,) int32 — task→chunk table, row-major (task, mode).
    coords     : N × (T, 1, P) int32 — chunk-relative coordinates per mode.
    values     : (T, 1, P) f32 — padded with 0.
    T is padded to `calls · tasks_per_call` with empty tasks on chunk 0.
    """

    task_chunk: jax.Array
    coords: tuple[jax.Array, ...]
    values: jax.Array
    chunk_shape: tuple[int, ...]
    tasks_per_call: int

    @property
    def calls(self) -> int:
        return self.values.shape[0] // self.tasks_per_call


def call_split(num_tasks: int, ndim: int,
               limit: int | None = None) -> tuple[int, int]:
    """(calls, tasks_per_call): the fewest kernel calls whose task tables
    fit SMEM (or `limit` tasks), balanced so padding is under one task per
    call."""
    limit = limit or max_tasks_per_call(ndim)
    calls = max(1, -(-num_tasks // limit))
    return calls, max(1, -(-num_tasks // calls))


def kernel_tensor(ct: ChunkedTensor, *,
                  tasks_per_call: int | None = None) -> KernelTensor:
    """Lay `ct` out for the kernel, its task axis padded to whole calls of
    `call_split` (at most `tasks_per_call` tasks each, when given)."""
    n = ct.ndim
    _calls, per = call_split(ct.num_tasks, n, tasks_per_call)
    ct = ct.pad_tasks(per)
    return KernelTensor(
        task_chunk=jnp.asarray(ct.task_chunk.reshape(-1)),
        coords=tuple(jnp.asarray(np.ascontiguousarray(ct.coords_rel[:, None, :, m]))
                     for m in range(n)),
        values=jnp.asarray(ct.values[:, None, :]),
        chunk_shape=ct.chunk_shape,
        tasks_per_call=per,
    )


@partial(jax.jit, static_argnames=("mode", "chunk_shape", "out_dim",
                                   "tasks_per_call", "interpret"))
def _mttkrp_pallas(factors, task_chunk, coords, values, *, mode: int,
                   chunk_shape: tuple[int, ...], out_dim: int,
                   tasks_per_call: int, interpret: bool):
    n = len(factors)
    rank = factors[0].shape[1]
    s_out = chunk_shape[mode]
    factors_t = tuple(pad_factor(f, chunk_shape[m]).T
                      for m, f in enumerate(factors))
    out_chunks = task_chunk.reshape(-1, n)[:, mode]

    def call(acc, c):
        local = kernel_call(factors_t, task_chunk, coords, values, c,
                            mode=mode, chunk_shape=chunk_shape,
                            tasks_per_call=tasks_per_call,
                            interpret=interpret)  # (per, R, S_out)
        rows = lax.dynamic_slice_in_dim(out_chunks, c * tasks_per_call,
                                        tasks_per_call)
        return acc.at[rows].add(local), None

    g = -(-out_dim // s_out)
    acc = jnp.zeros((g, rank, s_out), jnp.float32)
    acc, _ = lax.scan(call, acc, jnp.arange(values.shape[0] // tasks_per_call))
    return acc.transpose(0, 2, 1).reshape(g * s_out, rank)[:out_dim]


def mttkrp_pallas(factors, kt: KernelTensor, *, mode: int, out_dim: int,
                  interpret: bool = False):
    """Chunked spMTTKRP via the Pallas kernel.  Returns (out_dim, R) f32."""
    return _mttkrp_pallas(
        tuple(factors), kt.task_chunk, kt.coords, kt.values, mode=mode,
        chunk_shape=kt.chunk_shape, out_dim=out_dim,
        tasks_per_call=kt.tasks_per_call, interpret=interpret)
