"""Readings that a cell's limits are set from, many seeds in one process.

    python bench/control.py --workload fig6_2d.refresh --seeds 11,12,13 --seconds 3

For each seed: set the cell up, run a short window at the cell's own
load, read the served answers back exactly as ``run.py`` does, and
compare them with the plain reference (the program's reading).  Then put
the reference's control in the program's place (the same contraction
with Precision.HIGH's three bfloat16 passes) at the same points and
compare that (the control's reading).  The limit of ``answer_gap`` lies
between the largest program reading and the smallest control reading.
The benchmark's own runs never run the control.  Needs a TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

from run import NoAccelerator, compile_cache, find_devices  # noqa: E402
from harness import report  # noqa: E402
from harness.layout import Layout  # noqa: E402


def readings(layout, workload: str, seed: int, seconds: float) -> dict:
    from harness.cell import Cell
    cell = Cell(layout, workload, seed, seconds)
    try:
        cell.setup()
        w = cell.run_window()
        readback = cell.readback()
    finally:
        cell.close()
        gc.collect()
    ref = layout.reference(cell.cfg["reference"])
    t0 = time.perf_counter()
    program = report.compare(cell, ref, readback)
    reference_s = time.perf_counter() - t0
    control = report.compare(cell, ref, readback, control=True)
    return {"seed": seed, "completed": w.completed, "failed": w.failed,
            "missing": w.missing + program["answers_missing"],
            "compared": program["compared"],
            "program_gap": program["answer_gap"],
            "control_gap": control["answer_gap"],
            "reference_s": reference_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    layout = Layout(ROOT)
    entry = layout.workload(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    compile_cache(ROOT)
    try:
        find_devices(int(entry["chips"]), require_tpu=True)
    except NoAccelerator as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 3
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        rows.append(readings(layout, args.workload, seed, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    lower = max(r["program_gap"] for r in rows)
    upper = min(r["control_gap"] for r in rows)
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "lower": lower, "upper": upper,
                      "upper_over_lower": upper / lower if lower else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
