"""Pure-jnp oracle for the Pallas kernel (no pallas imports).

It mirrors the kernel's *local* contract — per-task (T, S_mode, R) partial
blocks, before the global sum reduction — so allclose tests compare the
kernel body itself, not the surrounding scatter.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["mttkrp_local_ref"]


@partial(jax.jit, static_argnames=("mode", "chunk_shape"))
def mttkrp_local_ref(factors, task_chunk, coords_rel, values, *,
                     mode: int, chunk_shape: tuple[int, ...]):
    """(T, S_mode, R) f32 per-task partials, gather/scatter formulation."""
    n = len(factors)
    rank = factors[0].shape[1]
    offsets = task_chunk * jnp.asarray(chunk_shape, dtype=jnp.int32)
    part = values[..., None].astype(jnp.float32)  # (T, P, 1)
    for m in range(n):
        if m == mode:
            continue
        idx = offsets[:, m][:, None] + coords_rel[:, :, m]  # (T, P)
        idx = jnp.minimum(idx, factors[m].shape[0] - 1)
        part = part * factors[m][idx]
    s_out = chunk_shape[mode]
    local = jnp.zeros((task_chunk.shape[0], s_out, rank), jnp.float32)
    return jax.vmap(lambda l, c, p: l.at[c].add(p, mode="drop"))(
        local, coords_rel[:, :, mode], part)
