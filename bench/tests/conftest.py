"""Tests of the benchmark harness, on the CPU at tiny sizes:

    python -m pytest bench/tests
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

#: A tensor small enough for the Pallas interpreter: 3 modes, one chunk edge
#: of 256 rows in the first, skewed so the kernel plan splits chunks.
TINY = {
    "name": "tiny",
    "source": "test-local",
    "shape": [300, 40, 50],
    "nnz": 1500,
    "dtype": "float32",
    "coords": {"kind": "zipf", "a": 1.5, "seed": 3},
    "values": {"kind": "uniform", "low": -1.0, "high": 1.0},
}


@pytest.fixture
def tiny_bench(tmp_path):
    """A benchmark directory holding one cell per engine on TINY."""
    root = ROOT / "bench"
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (tmp_path / "traffic").mkdir()
    (tmp_path / "workloads").mkdir()
    cells = []
    for engine in ("ref", "pallas"):
        traffic = json.loads((root / "traffic" / f"r16.{engine}.json").read_text())
        traffic.update(rank=4, n_iters=2)
        (tmp_path / "traffic" / f"r4.{engine}.json").write_text(json.dumps(traffic))
        name = f"tiny.r4.{engine}"
        (tmp_path / "workloads" / f"{name}.json").write_text(
            (root / "workloads" / f"lbnl.r16.{engine}.json").read_text())
        cells.append({"name": name, "config": "tiny", "traffic": f"r4.{engine}", "chips": 1})
    spec["configs"] = [{"name": "tiny", "source": "test-local", "file": "configs/tiny.json",
                        "reduced": [], "why": "tiny"}]
    spec["workloads"] = cells
    for m in spec["per_layer"] + spec["end_to_end"]:
        m.pop("workloads", None)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path
