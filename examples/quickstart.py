"""Quickstart: decompose a sparse tensor with PRISM on this machine.

  PYTHONPATH=src python examples/quickstart.py

Walks the paper end-to-end in miniature: build a sparse tensor, let the
Fig. 5 decider pick a partition, run CP-ALS with the PRISM chunked engine
(float), the fixed-point engine (paper Alg. 2), and the Pallas TPU kernel
(interpret mode on CPU), and compare convergence.
"""
from pathlib import Path

import numpy as np

from repro.core import (cp_als, decide_partition, random_tensor)
from repro.launch.cache import enable_compile_cache


def main():
    enable_compile_cache(Path(__file__).resolve().parents[1])
    # A Nell-2-like synthetic tensor (see benchmarks/table1.py for the set).
    st = random_tensor((605, 460, 1440), nnz=50_000, seed=0)
    print(f"tensor: dims={st.shape} nnz={st.nnz} density={st.density:.2e}")

    rank = 10
    plan = decide_partition(st, rank, mem_bytes=256 * 1024, rank_axis=rank)
    print(f"partition plan (Fig. 5): chunk_shape={plan.chunk_shape} "
          f"capacity={plan.capacity} rank_block={plan.rank_block} "
          f"kernel_iterations={plan.kernel_iterations}")

    for engine, kw in [
        ("ref", {}),
        ("chunked", dict(chunk_shape=plan.chunk_shape, capacity=plan.capacity)),
        ("fixed", dict(chunk_shape=plan.chunk_shape, capacity=plan.capacity,
                       fixed_preset="int7")),
        # The kernel brings its own VMEM-sized chunk plan.
        ("pallas", {}),
    ]:
        res = cp_als(st, rank, n_iters=3, engine=engine, seed=0, **kw)
        print(f"engine={engine:8s} fit={res.fit_history[-1]:+.4f} "
              f"avg|X-X̂|={res.diff_history[-1]:.5f} "
              f"t/iter={np.mean(res.iter_times):.2f}s")


if __name__ == "__main__":
    main()
