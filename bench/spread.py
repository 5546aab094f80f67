"""Run a cell several times and report each metric's spread.

    python bench/spread.py --workload fig6_2d.refresh --seeds 1,2,3,4,5,6 --sets 2

Runs ``bench/run.py`` once per seed and set, one process after another
(this process never touches JAX, so each run has the chip to itself),
with the same seeds in every set.  For each end-to-end metric it prints
each set's median and spread (the distance between the first and third
quartile of ``statistics.quantiles(values, n=4)``, as a share of the
median), the widest spread, and five times it (never under 1%): the
bound that spread supports.  ``--trace 1`` runs the traced variant
instead.  Every result line is appended to ``--out`` when given.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def spread(values):
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else float("inf")


def one_run(workload, seed, seconds, trace):
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True, timeout=1500)
    lines = p.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        result = {"error": p.returncode, "stderr": p.stderr[-2000:]}
    result["seed"] = seed
    result["log"] = [ln for ln in lines[:-1] if ln.startswith("[")]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    sets = []
    for k in range(args.sets):
        rows = []
        for seed in seeds:
            r = one_run(args.workload, seed, args.seconds, args.trace)
            r["set"] = k
            rows.append(r)
            brief = {m: v["value"] for m, v in r.get("metrics", {}).items()}
            print(json.dumps({"set": k, "seed": seed,
                              "correct": r.get("correct"), **brief,
                              "checks": r.get("checks"),
                              "error": r.get("error")}), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(r) + "\n")
        sets.append(rows)
    names = sorted({m for rows in sets for r in rows for m in r.get("metrics", {})})
    for m in names:
        per_set = []
        for rows in sets:
            vals = [r["metrics"][m]["value"] for r in rows
                    if m in r.get("metrics", {})]
            if len(vals) >= 2:
                per_set.append((statistics.median(vals), spread(vals), vals))
        if not per_set:
            continue
        widest = max(s for _, s, _ in per_set)
        print(json.dumps({"metric": m,
                          "medians": [med for med, _, _ in per_set],
                          "spreads": [s for _, s, _ in per_set],
                          "widest": widest,
                          "bound_5x": max(0.01, 5 * widest)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
