"""Shared pieces of the benchmark's own tests (run them by path:
``python -m pytest bench/tests``).  They run on the CPU at tiny sizes."""

from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
for _p in (BENCH, REPO / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

#: a 2-D scheme small enough for the CPU, with a bucket the Pallas
#: kernels take (in interpret mode off the chip)
TINY_CONFIG = {
    "name": "tiny_2d", "source": "test",
    "scheme": {"kind": "regular", "dim": 2, "level": 5},
    "tenants": 4,
    "field": {"kind": "poly_sine", "max_freq": 8},
    "exec": {"interpret": None, "dtype": "float32"},
    "engine": {"max_batch": 8, "deadline_ms": 5.0},
    "reference": "combination_multilinear",
    "reduced": [],
}
TINY_MIXES = {
    "tiny_refresh": {"refresh": {"clients": 2, "ring": 2}},
    "tiny_query": {"query": {"rate_per_s": 40, "points": 16,
                             "deadline_ms": 5}},
}
TINY_CELLS = ("tiny_2d.refresh", "tiny_2d.query")


#: the tiny cells' limit of ``answer_gap``, set from CPU readings of
#: ``test_control``: the program reads about 2e-7, the control 1.5e-5
TINY_LIMIT = 3e-6


def add_tiny_cells(root: Path, limit: float = TINY_LIMIT) -> None:
    """Add the tiny configuration, its mixes, cells and limits to the
    layout at ``root`` as new files and new entries only."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg_file = "bench/configs/tiny_2d.json"
    (root / cfg_file).write_text(json.dumps(TINY_CONFIG))
    for name, mix in TINY_MIXES.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    spec["configs"].append({"name": "tiny_2d", "source": "test",
                            "file": cfg_file, "reduced": [], "why": "test"})
    for cell, mix in zip(TINY_CELLS, TINY_MIXES):
        spec["workloads"].append({"name": cell, "config": "tiny_2d",
                                  "traffic": mix, "chips": 1, "why": "test"})
        (root / "bench" / "checks" / f"{cell}.json").write_text(
            json.dumps({"answer_gap": {"limit": limit}}))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" not in m:
            continue
        kinds = [w.split(".", 1)[1] for w in m["workloads"]]
        m["workloads"] += [c for c in TINY_CELLS
                           if c.split(".", 1)[1] in kinds]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def allow_cpu(root: Path) -> None:
    """Test scaffolding only: a peaks row for the CPU, so a run driven
    here past the chip check finds its device kind."""
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    peaks["cpu"] = {"hbm_bytes_per_s": 1e11, "bf16_flops_per_s": 1e12,
                    "hbm_bytes": 1e10, "source": "test"}
    (root / "bench" / "peaks.json").write_text(json.dumps(peaks))


def copy_layout(dst: Path) -> Path:
    """A checkout-like copy: ``BENCHMARK.json`` and ``bench/``, with the
    program's ``src`` linked in."""
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(REPO / "src", dst / "src")
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    root = copy_layout(tmp_path)
    add_tiny_cells(root)
    allow_cpu(root)
    return root
