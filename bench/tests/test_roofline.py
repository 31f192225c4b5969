"""Roofline counts against hand counts; the peaks table."""
from __future__ import annotations

import pytest

from bench.roofline import least_time, mttkrp_bytes, mttkrp_flops, peaks_for

NELL2 = ((12092, 9184, 28818), 76_879_419)
LBNL = ((1605, 4198, 1631, 4209, 868131), 1_698_825)


def test_nell2_counts():
    shape, nnz = NELL2
    # 76,879,419 nonzeros x (3 int32 coordinates + 1 f32 value) x 4 bytes,
    # plus 50,094 factor rows x 16 ranks x 4 bytes.
    assert mttkrp_bytes(shape, nnz, 16) == 1_230_070_704 + 3_206_016
    assert mttkrp_flops(nnz, 3, 16) == 4_920_282_816


def test_lbnl_counts():
    shape, nnz = LBNL
    assert mttkrp_bytes(shape, nnz, 16) == 40_771_800 + 56_305_536
    assert mttkrp_flops(nnz, 5, 16) == 163_087_200


@pytest.mark.parametrize(("tensor", "expect_ms"), [(NELL2, 1.5058), (LBNL, 0.11853)])
def test_v5e_least_time_is_memory_bound(tensor, expect_ms):
    shape, nnz = tensor
    t, bound = least_time(shape, nnz, 16, peaks_for("TPU v5 lite"))
    assert bound == "memory"
    assert t * 1e3 == pytest.approx(expect_ms, rel=1e-4)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        peaks_for("TPU v9 imaginary")


def test_peaks_name_their_source():
    assert "Google Cloud" in peaks_for("TPU v5 lite")["source"]
