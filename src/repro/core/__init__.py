"""PRISM core: chunked sparse tensor format, hierarchical partitioning,
fixed-point spMTTKRP, CP-ALS, heterogeneous + distributed execution."""
from .chunking import ChunkedTensor, chunk_tensor, replication_stats
from .cpals import CPResult, avg_abs_diff, cp_als, fit_value, init_factors, make_engine
from .distributed import DistributedMTTKRP, distributed_mttkrp_fn
from .hetero import HeteroSplit, mttkrp_hetero, split_tasks
from .mttkrp import (
    mttkrp_chunked,
    mttkrp_chunked_fixed,
    mttkrp_coo,
    mttkrp_coo_fixed,
)
from .partition import PartitionPlan, decide_partition
from .qformat import FIXED_PRESETS, Q17_15, Q5_3, Q9_7, QFormat, value_qformat
from .sptensor import FROSTT, TABLE1, SparseTensor, random_tensor, table1_tensor


def __getattr__(name):
    # Lazy (PEP 562): `repro.batch` itself imports from `repro.core.cpals`,
    # so an eager import here would be circular.
    if name == "cp_als_batched":
        from ..batch import cp_als_batched
        return cp_als_batched
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
