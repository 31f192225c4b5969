"""Ahead-of-time compiles for a described TPU v5e, at FROSTT sizes.

Interpret mode cannot see what the chip's compiler refuses: blocks that are
not legal tiles, task tables that overflow SMEM, tiles that overflow VMEM,
programs that do not fit HBM.  These tests hand the TPU compiler the real
programs for a v5e 2x2 that is described, not attached, so a refusal fails
here instead of on the chip.  Nothing runs: shapes only.

The topology is described inside a module fixture (never at import), which
skips where it cannot be described.  All cases live in this one file so one
test worker loads the TPU library.
"""
import math
import os
import types

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import FROSTT
from repro.core.distributed import distributed_mttkrp_fn
from repro.core.partition import decide_kernel_partition, decide_partition
from repro.kernels import ops
from repro.kernels.mttkrp_kernel import mttkrp_pallas_local

#: v5e HBM per chip.
HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def kernel_plan(name: str):
    """Chunk shape, P and tasks per call of the `pallas` plan for a FROSTT
    tensor.  Tasks: one per occupied chunk, at most nnz of them."""
    spec = FROSTT[name]
    shape, nnz = spec["shape"], spec["nnz"]
    cs, cap = decide_kernel_partition(shape, nnz)
    tasks = min(math.prod(-(-d // s) for d, s in zip(shape, cs, strict=True)),
                nnz)
    calls, per = ops.call_split(tasks, len(shape))
    return shape, cs, cap, calls, per


def kernel_args(sharding, shape, cs, cap, tasks, rank):
    def sds(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=sharding)
    n = len(shape)
    factors_t = tuple(sds((rank, -(-d // s) * s), jnp.float32)
                      for d, s in zip(shape, cs, strict=True))
    return (factors_t, sds((tasks * n,), jnp.int32),
            tuple(sds((tasks, 1, cap), jnp.int32) for _ in range(n)),
            sds((tasks, 1, cap), jnp.float32))


@pytest.mark.parametrize("rank", [16, 128])
@pytest.mark.parametrize("mode", [0, 1, 2])
def test_kernel_compiles_nell2(one_chip, rank, mode):
    shape, cs, cap, calls, per = kernel_plan("nell2")
    assert calls > 1  # nell2's task table does not fit one call
    compiled = mttkrp_pallas_local.lower(
        *kernel_args(one_chip, shape, cs, cap, per, rank), mode=mode,
        chunk_shape=cs, tasks_per_call=per).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_pallas_op_compiles_nell2(one_chip, mode):
    """The whole op the backend runs: every call, the sum reduction."""
    shape, cs, cap, calls, per = kernel_plan("nell2")
    rank = 16
    _ft, table, coords, values = kernel_args(one_chip, shape, cs, cap,
                                             calls * per, rank)
    factors = tuple(jax.ShapeDtypeStruct((d, rank), jnp.float32,
                                         sharding=one_chip) for d in shape)
    compiled = ops._mttkrp_pallas.lower(
        factors, table, coords, values, mode=mode, chunk_shape=cs,
        out_dim=shape[mode], tasks_per_call=per, interpret=False).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode", [0, 4])
def test_kernel_compiles_5mode_lbnl(one_chip, mode):
    """Five modes: a wider task table, so fewer tasks per call."""
    shape, cs, cap, _calls, per = kernel_plan("lbnl")
    assert per <= ops.max_tasks_per_call(5) < ops.max_tasks_per_call(3)
    compiled = mttkrp_pallas_local.lower(
        *kernel_args(one_chip, shape, cs, cap, per, 16), mode=mode,
        chunk_shape=cs, tasks_per_call=per).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("reduce", ["psum", "psum_scatter"])
def test_distributed_mttkrp_compiles_2x2(topo, reduce):
    """The `distributed` backend's program on a 2x2 (data, model) mesh at
    nell2's MRAM chunk plan: tasks on `data`, rank on `model`.  The v5e
    compiler may lower psum_scatter to an all-reduce plus a slice."""
    spec = FROSTT["nell2"]
    shape, nnz, rank, mode = spec["shape"], spec["nnz"], 16, 2
    stats = types.SimpleNamespace(ndim=len(shape), shape=shape, nnz=nnz,
                                  density=nnz / math.prod(shape))
    plan = decide_partition(stats, rank)
    tasks = -(-plan.est_chunks // 2) * 2
    mesh = Mesh([[topo.devices[0], topo.devices[1]],
                 [topo.devices[2], topo.devices[3]]], ("data", "model"))

    def sds(s, dt, spec_):
        return jax.ShapeDtypeStruct(s, dt, sharding=NamedSharding(mesh, spec_))
    factors = tuple(sds((d, rank), jnp.float32, P(None, "model"))
                    for d in shape)
    fn, _ = distributed_mttkrp_fn(mesh, mode=mode,
                                  chunk_shape=plan.chunk_shape,
                                  out_dim=shape[mode], reduce=reduce)
    compiled = fn.lower(
        factors,
        sds((tasks, 3), jnp.int32, P("data", None)),
        sds((tasks, plan.capacity, 3), jnp.int32, P("data", None, None)),
        sds((tasks, plan.capacity), jnp.float32, P("data", None)),
    ).compile()
    hlo = compiled.as_text()
    assert "all-reduce" in hlo or "reduce-scatter" in hlo
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
