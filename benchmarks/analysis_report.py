"""BENCH_analysis.json: static invariant findings + runtime lockdep
coverage in one artifact.

Runs the `repro.analysis` static pass over the whole package, then an
instrumented 4-thread engine workload with the runtime sanitizer
forced on, and emits the combined machine-readable report CI uploads
and gates on (``violations == 0`` and zero runtime cycles).

  PYTHONPATH=src python benchmarks/analysis_report.py
"""

from __future__ import annotations

import argparse
import json
import threading
import time

import jax
import numpy as np

jax.config.update("jax_enable_x64", True)

from repro.analysis import lockdep  # noqa: E402
from repro.analysis import locklint, report  # noqa: E402
from repro.core.engine import CTEngine  # noqa: E402
from repro.core.levels import CombinationScheme, grid_shape  # noqa: E402


def _lockdep_workload() -> dict:
    """4 threads x 4 tenants of instrumented engine traffic; returns
    the sanitizer's graph summary."""
    lockdep.enable()
    lockdep.reset()
    t0 = time.perf_counter()
    try:
        scheme = CombinationScheme(2, 3)
        eng = CTEngine()
        names = [f"t{i}" for i in range(4)]
        for i, name in enumerate(names):
            rng = np.random.default_rng(i)
            eng.register(name, scheme,
                         {ell: rng.standard_normal(grid_shape(ell))
                          for ell, _ in scheme.grids})
        eng.start()

        def work(name, i):
            rng = np.random.default_rng(100 + i)
            for _ in range(3):
                grids = {ell: rng.standard_normal(grid_shape(ell))
                         for ell, _ in scheme.grids}
                eng.submit_ingest(name, grids).result(30)
                eng.submit_query(
                    name, rng.random((16, 2))).result(30)

        threads = [threading.Thread(target=work, args=(n, i))
                   for i, n in enumerate(names)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        eng.stop()
        rep = lockdep.report()
        return {
            "workload": "4-thread engine ingest+query",
            "wall_s": round(time.perf_counter() - t0, 3),
            "edges": rep["edges"],
            "cycles": len(rep["cycles"]),
            "order_violations": len(rep["order_violations"]),
            "dispatch_under_lock": len(rep["dispatch_under_lock"]),
        }
    finally:
        lockdep.reset()
        lockdep.restore_default()


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--json", default="BENCH_analysis.json")
    args = parser.parse_args()

    findings, files = locklint.lint_paths()
    dep = _lockdep_workload()
    payload = report.build_report(findings, files, lockdep_report=dep)
    report.write_json(payload, args.json)
    print(json.dumps({k: payload[k] for k in
                      ("violations", "files_scanned", "per_rule")},
                     indent=2))
    print("lockdep:", json.dumps(dep))
    if payload["violations"] or dep["cycles"] \
            or dep["order_violations"] or dep["dispatch_under_lock"]:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
