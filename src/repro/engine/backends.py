"""The built-in execution strategies, as registry backends.

Each maps one of the paper's execution arms onto this host:

  ref          plain COO scatter (paper Fig. 1; the "GPU/BLCO" role)
  alto         ALTO linearized format: one bit-interleaved index serving
               every mode, de-interleaved at kernel time (the "CPU" role)
  csf          CSF fiber trees (repro.formats.csf): per-mode mode trees
               with fiber-level factor reuse
  chunked      PRISM chunked format, float (the "PIM" role)
  fixed        PRISM chunked + Alg.-2 fixed point (paper §IV-C)
  hetero       dense(MXU)/sparse split (paper §IV-D collaboration)
  pallas       the Pallas TPU kernel on its own VMEM-sized chunk plan
               (interpret mode on CPU hosts)
  distributed  shard_map over a (data, model) mesh (paper §IV-B on TPU)

All chunk-based builders pull their ChunkedTensor / device arrays from the
context's PlanCache, so building several backends against one tensor chunks
it exactly once; the format-based builders (`csf`, `alto`) likewise pull
their layouts from the context's FormatCache.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core import baselines, blocked, hetero, lockfree, mttkrp
from ..core.distributed import DistributedMTTKRP
from ..core.partition import decide_kernel_partition
from ..core.qformat import FIXED_PRESETS, value_qformat
from ..launch.mesh import make_local_mesh
from ..obs.metrics import default_registry
from ..obs.tracing import span
from .registry import EngineContext, register_backend

__all__ = []  # backends are reached through the registry, not by import


@register_backend(
    "ref",
    description="plain COO scatter-add reference (paper Fig. 1)")
def _build_ref(ctx: EngineContext):
    coords = jnp.asarray(ctx.st.coords)
    values = jnp.asarray(ctx.st.values)
    shape = ctx.st.shape

    def engine(factors, mode):
        return blocked.mttkrp_coo_blocked(tuple(factors), coords, values,
                                          mode=mode, out_dim=shape[mode])
    return engine


@register_backend(
    "alto",
    description="ALTO linearized index: one bit-interleaved copy serves all modes (CPU role)")
def _build_alto(ctx: EngineContext):
    from ..formats.alto import MAX_KEY_BITS, alto_key_bits
    shape = ctx.st.shape
    if alto_key_bits(shape) > MAX_KEY_BITS:
        # The packed linearization caps at 64 key bits (BLCO block splitting
        # is the ROADMAP lift); beyond it, degrade to the ALTO-*ordered* COO
        # baseline — same traversal order, explicit coordinates.
        order = baselines.alto_order(ctx.st.coords, shape)
        a_coords = jnp.asarray(ctx.st.coords[order])
        a_values = jnp.asarray(ctx.st.values[order])

        def engine(factors, mode):
            return baselines.mttkrp_alto(tuple(factors), a_coords, a_values,
                                         mode=mode, out_dim=shape[mode])
        return engine

    at = ctx.formats.alto(ctx.st)
    dev = ctx.formats.device_alto(ctx.st)
    positions = at.positions

    def engine(factors, mode):
        return blocked.mttkrp_alto_blocked(
            tuple(factors), dev["key_words"], dev["values"],
            mode=mode, positions=positions, out_dim=shape[mode])
    return engine


@register_backend(
    "csf",
    description="CSF fiber trees: interior factor rows fetched once per fiber")
def _build_csf(ctx: EngineContext):
    st, shape, formats = ctx.st, ctx.st.shape, ctx.formats

    def engine(factors, mode):
        # Trees build lazily per mode (the autotuner may only ever probe an
        # anchor mode) and come from the FormatCache, so CP-ALS and repeated
        # builds against one tensor construct each tree exactly once.
        tree = formats.csf(st, mode)
        dev = formats.device_csf(st, mode)
        return blocked.mttkrp_csf_blocked(
            tuple(factors), dev["inner_coord"], dev["values"],
            dev["fiber_ids"], dev["fiber_coords"],
            mode=mode, inner_mode=tree.inner_mode, mid_modes=tree.mid_modes,
            out_dim=shape[mode], n_fibers=tree.n_fibers)
    return engine


@register_backend(
    "chunked", needs_chunking=True,
    description="PRISM chunked format, float (PIM role)")
def _build_chunked(ctx: EngineContext):
    ct = ctx.chunked()
    dev = ctx.device_arrays()
    cs, shape = ct.chunk_shape, ctx.st.shape
    nnz_pt = jnp.asarray(ct.nnz_per_task) if ctx.lockfree_mode else None

    def engine(factors, mode):
        vals = dev["values"]
        if nnz_pt is not None:
            m = lockfree.wave_collision_mask(dev["coords_rel"][:, :, mode], nnz_pt)
            vals = vals * m
        return blocked.mttkrp_chunked_blocked(
            tuple(factors), dev["task_chunk"], dev["coords_rel"], vals,
            mode=mode, chunk_shape=cs, out_dim=shape[mode])
    return engine


@register_backend(
    "fixed", needs_chunking=True, supports_fixed_point=True, lossless=False,
    presets=tuple(FIXED_PRESETS),
    description="PRISM chunked + paper Alg. 2 fixed point (int7 / int15-12)")
def _build_fixed(ctx: EngineContext):
    ct = ctx.chunked()
    dev = ctx.device_arrays()
    cs, shape = ct.chunk_shape, ctx.st.shape
    qf, prec_shift = FIXED_PRESETS[ctx.fixed_preset]
    vq = value_qformat(ctx.st.values, storage_bits=16)
    qvalues = jnp.asarray(vq.quantize_np(ct.values))
    nnz_pt = jnp.asarray(ct.nnz_per_task) if ctx.lockfree_mode else None

    # One compiled program per mode: unlike the float backends (a single
    # pre-jitted kernel call), the fixed path wraps its kernel in factor
    # quantization and output dequantization — left eager, those ~4 ops per
    # factor of dispatch overhead swamp the narrow-int memory win this
    # backend exists for.  Fusing quantize → kernel → dequantize also lets
    # XLA keep the intermediates in int registers.
    @partial(jax.jit, static_argnums=1)
    def engine(factors, mode):
        qfactors = tuple(qf.quantize(f) for f in factors)
        qvals = qvalues
        if nnz_pt is not None:
            m = lockfree.wave_collision_mask(dev["coords_rel"][:, :, mode], nnz_pt)
            qvals = qvals * m.astype(qvals.dtype)
        qout = mttkrp.mttkrp_chunked_fixed(
            qfactors, dev["task_chunk"], dev["coords_rel"], qvals,
            mode=mode, chunk_shape=cs, out_dim=shape[mode],
            matrix_frac=qf.frac_bits, value_frac=vq.frac_bits,
            prec_shift=prec_shift)
        return mttkrp.dequantize_output(qout, qf.frac_bits, prec_shift)
    return engine


@register_backend(
    "hetero", needs_chunking=True,
    description="dense(MXU)/sparse split, cost-model scheduled (paper §IV-D)")
def _build_hetero(ctx: EngineContext):
    ct = ctx.chunked()
    split = hetero.split_tasks(ct, ctx.rank, dense_fraction=ctx.dense_fraction)
    dense_blocks = jnp.asarray(hetero.densify_tasks(ct, split.dense_idx))
    arrays = hetero.hetero_arrays(ct, split, full=ctx.device_arrays())
    shape = ctx.st.shape

    def engine(factors, mode):
        return hetero.mttkrp_hetero(
            tuple(factors), ct, split, dense_blocks,
            mode=mode, out_dim=shape[mode], arrays=arrays)
    return engine


@register_backend(
    "pallas", needs_chunking=True,
    description="Pallas TPU kernel, VMEM-sized chunk plan (interpret mode on CPU hosts)")
def _build_pallas(ctx: EngineContext):
    from ..kernels import ops as kops
    shape = ctx.st.shape
    # The kernel has its own plan: the decider's MRAM-sized chunks would
    # never fit VMEM.  An explicit chunk_shape/capacity still wins.
    plan_cs, plan_cap = decide_kernel_partition(shape, ctx.st.nnz)
    cs = ctx.chunk_shape or plan_cs
    cap = ctx.capacity if ctx.chunk_shape or ctx.capacity else plan_cap
    ct = ctx.plans.chunked(ctx.st, cs, cap)
    with span("layout.kernel") as sp:
        kt = kops.kernel_tensor(ct)
        tasks, _, slots = kt.values.shape
        sp.set(nnz=ctx.st.nnz, tasks=tasks, slots_per_task=slots, calls=kt.calls)
    # The layout's slot fill, nnz / (T·P), counted whether or not tracing is on.
    default_registry.counter("layout.kernel_nonzeros").inc(ctx.st.nnz)
    default_registry.counter("layout.kernel_slots").inc(tasks * slots)
    interpret = ctx.interpret

    def engine(factors, mode):
        return kops.mttkrp_pallas(tuple(factors), kt, mode=mode,
                                  out_dim=shape[mode], interpret=interpret)
    return engine


@register_backend(
    "distributed", needs_chunking=True, min_devices=2,
    description="shard_map mesh: rank partitioning on `model`, tasks on `data`")
def _build_distributed(ctx: EngineContext):
    # Default to a real model axis when the host allows it, so rank
    # partitioning (the paper's favored, replication-free partitioning)
    # is actually exercised — not just the data/task axis.
    mesh = (ctx.mesh if ctx.mesh is not None
            else make_local_mesh(n_model=2 if len(jax.devices()) >= 2 else 1))
    dmt = DistributedMTTKRP(mesh, ctx.chunked(), ctx.rank, reduce=ctx.reduce)
    shape = ctx.st.shape

    def engine(factors, mode):
        # Materialize + trim the task-padding rows so the engine contract
        # (exact (I_mode, R)) holds regardless of the reduction strategy.
        return jnp.asarray(dmt(factors, mode))[: shape[mode]]
    return engine
