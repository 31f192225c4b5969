"""Shared partition-plan / chunked-tensor cache.

Chunking is the expensive, mode-agnostic preprocessing step (paper §IV-A:
one chunking serves every MTTKRP mode and every CP-ALS iteration).  The
cache lets every chunk-based backend — and the autotuner, which builds
several backends against the same tensor — share one `PartitionPlan`, one
`ChunkedTensor` and one set of device-resident arrays instead of re-chunking
per backend.  This is the software analogue of the paper's data-residency
argument: the tensor is placed once; only factors move.

Entries are keyed by tensor identity (`id`) and evicted when the tensor is
garbage collected, so the cache never outlives its tensors.
"""
from __future__ import annotations

import dataclasses
import time
import weakref

from ..core.chunking import ChunkedTensor, chunk_tensor, clamp_capacity
from ..core.partition import PartitionPlan, decide_partition
from ..core.sptensor import SparseTensor
from ..obs.metrics import default_registry
from ..obs.tracing import span

__all__ = ["PlanCache", "CacheStats", "default_plan_cache"]


@dataclasses.dataclass
class CacheStats:
    plan_hits: int = 0
    plan_misses: int = 0
    chunk_hits: int = 0
    chunk_misses: int = 0
    device_hits: int = 0
    device_misses: int = 0


class PlanCache:
    """Caches `decide_partition` plans, `chunk_tensor` results and the
    jnp device arrays derived from them, per live tensor."""

    def __init__(self):
        self._plans: dict = {}
        self._chunked: dict = {}
        self._device: dict = {}
        self._tracked: set[int] = set()
        self.stats = CacheStats()

    # -- keys -------------------------------------------------------------
    def _tensor_key(self, st: SparseTensor) -> int:
        key = id(st)
        # Evict every entry for this tensor once it is collected (id() values
        # are recycled by CPython, so stale entries would otherwise alias).
        # One finalizer per live tensor — not per lookup — and the finalizer
        # only weakly references this cache, so a short-lived cache stays
        # collectable while the tensor lives on.
        if key not in self._tracked:
            self._tracked.add(key)
            weakref.finalize(st, _evict_weak, weakref.ref(self), key)
        return key

    def _evict(self, tkey: int) -> None:
        self._tracked.discard(tkey)  # a recycled id() needs a new finalizer
        for cache in (self._plans, self._chunked, self._device):
            for k in [k for k in cache if k[0] == tkey]:
                del cache[k]

    # -- lookups ----------------------------------------------------------
    def plan(self, st: SparseTensor, rank: int, *, mem_bytes: int) -> PartitionPlan:
        k = (self._tensor_key(st), rank, mem_bytes)
        if k in self._plans:
            self.stats.plan_hits += 1
        else:
            self.stats.plan_misses += 1
            self._plans[k] = decide_partition(st, rank, mem_bytes=mem_bytes)
        return self._plans[k]

    def _capacity_key(self, st: SparseTensor, capacity: int | None):
        """Apply chunk_tensor's clamp so capacities that chunk identically
        share one cache entry."""
        if capacity is None:
            return None
        return clamp_capacity(st.nnz, capacity)

    def chunked(self, st: SparseTensor, chunk_shape: tuple[int, ...],
                capacity: int | None) -> ChunkedTensor:
        k = (self._tensor_key(st), tuple(chunk_shape),
             self._capacity_key(st, capacity))
        if k in self._chunked:
            self.stats.chunk_hits += 1
        else:
            self.stats.chunk_misses += 1
            with span("layout.chunk", chunk_shape=list(chunk_shape)) as sp:
                # Timed whether or not tracing is on.
                t0 = time.perf_counter()
                ct = chunk_tensor(st, tuple(chunk_shape), capacity)
                default_registry.histogram("layout.chunk_seconds").observe(
                    time.perf_counter() - t0)
                sp.set(tasks=ct.num_tasks, capacity=ct.capacity)
            self._chunked[k] = ct
        return self._chunked[k]

    def device_arrays(self, st: SparseTensor, chunk_shape: tuple[int, ...],
                      capacity: int | None) -> dict:
        """jnp copies of the chunked arrays (shipped to devices once)."""
        from ..core.mttkrp import chunked_device_arrays
        k = (self._tensor_key(st), tuple(chunk_shape),
             self._capacity_key(st, capacity))
        if k in self._device:
            self.stats.device_hits += 1
        else:
            self.stats.device_misses += 1
            self._device[k] = chunked_device_arrays(
                self.chunked(st, chunk_shape, capacity))
        return self._device[k]

    def clear(self) -> None:
        self._plans.clear()
        self._chunked.clear()
        self._device.clear()
        self._tracked.clear()
        self.stats = CacheStats()


def _evict_weak(cache_ref: "weakref.ref[PlanCache]", tkey: int) -> None:
    cache = cache_ref()
    if cache is not None:
        cache._evict(tkey)


#: Process-wide default used when callers don't thread their own cache.
default_plan_cache = PlanCache()
