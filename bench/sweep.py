"""Knee sweep of a query cell: offer several fixed rates in one process.

    python bench/sweep.py --workload fig6_2d.query --rates 200,300,400 --seconds 10

Sets the cell up once, then runs one window per rate (the cell's own
mix, with only ``rate_per_s`` replaced) and prints, per rate, what was
offered and completed, the latency quantiles, and whether a backlog
grew: the median latency of the window's last quarter against its first
quarter.  The knee is the highest rate that completes at the offered
rate with no growing backlog.  Needs a TPU, as ``run.py`` does.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

from run import NoAccelerator, compile_cache, find_devices  # noqa: E402
from harness.layout import Layout  # noqa: E402


def sweep_point(cell, rate: float, seed: int) -> dict:
    cell.mix["query"]["rate_per_s"] = rate
    cell.new_schedule(np.random.default_rng([seed, int(rate * 1000)]))
    w = cell.run_window()
    lat = np.asarray(w.latencies_s) * 1e3
    q = max(1, len(lat) // 4)
    return {"offered_per_s": rate,
            "attempted": w.attempted, "completed": w.completed,
            "failed": w.failed, "missing": w.missing,
            "completed_per_s": w.completed / w.span_s if w.span_s else 0.0,
            "p50_ms": float(np.percentile(lat, 50)) if lat.size else None,
            "p95_ms": float(np.percentile(lat, 95)) if lat.size else None,
            "first_quarter_p50_ms": float(np.median(lat[:q])) if lat.size else None,
            "last_quarter_p50_ms": float(np.median(lat[-q:])) if lat.size else None,
            "generator_late_p99_ms": float(np.percentile(
                np.asarray(w.lateness_s) * 1e3, 99)) if w.lateness_s else None,
            "batches": w.counters_close["eval.batches"] - w.counters_open["eval.batches"],
            "compiles_in_window": w.compiles}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    layout = Layout(ROOT)
    entry = layout.workload(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    compile_cache(ROOT)
    try:
        find_devices(int(entry["chips"]), require_tpu=True)
    except NoAccelerator as exc:
        print(f"sweep: {exc}", file=sys.stderr)
        return 3
    from harness.cell import Cell
    cell = Cell(layout, args.workload, args.seed, args.seconds)
    points = []
    try:
        cell.setup()
        for rate in [float(r) for r in args.rates.split(",")]:
            points.append(sweep_point(cell, rate, args.seed))
            print(json.dumps(points[-1]), flush=True)
    finally:
        cell.close()
    held = [p["offered_per_s"] for p in points if sustained(p)]
    print(json.dumps({"workload": args.workload,
                      "knee_per_s": max(held) if held else None}), flush=True)
    return 0


def sustained(p: dict) -> bool:
    """Completed at the offered rate, nothing failed, and no backlog
    grew: the last quarter's median latency within 1.5x (+5 ms) of the
    first quarter's."""
    return (p["failed"] == 0 and p["missing"] == 0
            and p["completed_per_s"] >= 0.95 * p["offered_per_s"]
            and p["last_quarter_p50_ms"] is not None
            and p["last_quarter_p50_ms"]
            <= 1.5 * p["first_quarter_p50_ms"] + 5.0)


if __name__ == "__main__":
    sys.exit(main())
