"""Operations, bytes and least time of one MTTKRP, whatever implements it.

One mode's MTTKRP of an N-mode tensor with `nnz` nonzeros at rank R needs:

    FLOPs = nnz · R · (N + 1)          N - 1 Hadamard products, the value
                                       product and the accumulation, per rank
    bytes = nnz · 4 · (N + 1)          every nonzero's N int32 coordinates and
          + Σ_k I_k · R · 4            f32 value once, each of the N - 1 input
                                       factors read once, the output written once

The counts come from the tensor's shape alone, so no layout's padding or
replication enters them: a layout that moves more bytes shows as a lower
share of the roofline, not as a different yardstick.  The least time is the
larger of FLOPs over the chip's peak rate and bytes over its HBM bandwidth,
from the peaks table beside this file, keyed by JAX's `device_kind`.
"""
from __future__ import annotations

import json
from pathlib import Path

__all__ = ["least_time", "mttkrp_bytes", "mttkrp_flops", "peaks_for"]

PEAKS = Path(__file__).with_name("peaks.json")


def peaks_for(device_kind: str) -> dict:
    """The published peaks of `device_kind`; a kind not in the table is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def mttkrp_flops(nnz: int, ndim: int, rank: int) -> int:
    return nnz * rank * (ndim + 1)


def mttkrp_bytes(shape, nnz: int, rank: int) -> int:
    return nnz * 4 * (len(shape) + 1) + sum(int(d) for d in shape) * rank * 4


def least_time(shape, nnz: int, rank: int, peaks: dict) -> tuple[float, str]:
    """(seconds, "memory" or "compute"): the bound of one mode's MTTKRP."""
    t_mem = mttkrp_bytes(shape, nnz, rank) / peaks["hbm_byte_per_s"]
    t_flop = mttkrp_flops(nnz, len(shape), rank) / peaks["bf16_flop_per_s"]
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")
