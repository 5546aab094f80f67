"""Find every per-cell file by the name ``BENCHMARK.json`` gives it.

A configuration, a traffic mix, a per-layer metric and a reference are
each a file of their own, found by name under the benchmark directory:

    bench/configs/<config>.json     (the path ``configs[].file`` names)
    bench/traffic/<traffic>.json
    bench/metrics/<metric>.py       (defines ``read(ctx) -> float | None``)
    bench/references/<reference>.py (named by the configuration file)
    bench/checks/<workload>.json    (the limit of each number compared)
    bench/peaks.json                (keyed by ``device_kind``)

So a later change adds a cell, a mix or a metric with new files and new
entries in ``BENCHMARK.json`` and edits nothing that is there.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH_DIR = "bench"


class LayoutError(RuntimeError):
    """A name in ``BENCHMARK.json`` has no file, or a file is malformed."""


class Layout:
    """The benchmark files of one checkout, rooted at ``root``."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.bench = self.root / BENCH_DIR
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise LayoutError(f"no BENCHMARK.json at {self.root}")
        self.spec = json.loads(path.read_text())

    # -- entries of BENCHMARK.json -----------------------------------------

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise LayoutError(f"no workload {name!r} in BENCHMARK.json "
                          f"(have {[w['name'] for w in self.spec['workloads']]})")

    def config_entry(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return c
        raise LayoutError(f"no configuration {name!r} in BENCHMARK.json")

    def end_to_end(self, cell: str) -> List[dict]:
        """The end-to-end metrics this cell reports (``setup_s`` and every
        metric whose ``workloads`` lists it or that has no such list)."""
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[dict]:
        """The per-layer metrics read in this cell's traced run: those that
        list it, and those with no list whose ``moves`` it reports."""
        moves = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in moves)]

    # -- files found by name -----------------------------------------------

    def _json(self, path: Path, what: str) -> dict:
        if not path.is_file():
            raise LayoutError(f"{what}: no file {path}")
        return json.loads(path.read_text())

    def config(self, name: str) -> dict:
        return self._json(self.root / self.config_entry(name)["file"],
                          f"configuration {name!r}")

    def traffic(self, name: str) -> dict:
        return self._json(self.bench / "traffic" / f"{name}.json",
                          f"traffic mix {name!r}")

    def checks(self, workload: str) -> Dict[str, dict]:
        return self._json(self.bench / "checks" / f"{workload}.json",
                          f"limits of workload {workload!r}")

    def peaks(self) -> Dict[str, dict]:
        return self._json(self.bench / "peaks.json", "peak table")

    def metric_reader(self, name: str) -> ModuleType:
        return self._module(self.bench / "metrics" / f"{name}.py",
                            f"per-layer metric {name!r}", "read")

    def reference(self, name: str) -> ModuleType:
        return self._module(self.bench / "references" / f"{name}.py",
                            f"reference {name!r}", "reference_values")

    def _module(self, path: Path, what: str, attr: str) -> ModuleType:
        if not path.is_file():
            raise LayoutError(f"{what}: no file {path}")
        # a metric name may hold a '.', so load by path, not by import name
        spec = importlib.util.spec_from_file_location(
            f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if not hasattr(mod, attr):
            raise LayoutError(f"{what}: {path} defines no {attr}()")
        return mod
