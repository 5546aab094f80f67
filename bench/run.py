"""One benchmark run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload fig6_2d.refresh --seed 7 --seconds 10 --trace 0

Sets up the cell (inputs from ``--seed``, engine, every tenant
registered, every shape the window uses warmed), measures for
``--seconds``, checks the served answers against the plain reference,
prints per-phase lines and ends with one JSON line: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics read from a profiler trace
of a slice of the window), ``device``, ``breakdown`` (traced runs) and
``checks`` (each number compared, with its limit).

Runs on the machine it is started on.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
JAX's persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` if
set, else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "bench") not in sys.path:
    sys.path.insert(0, str(ROOT / "bench"))

from harness import report, roofline  # noqa: E402
from harness import trace as trc  # noqa: E402
from harness.layout import Layout  # noqa: E402


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def compile_cache(root: Path) -> str:
    """Keep every compiled program (however quick to compile) in the
    checkout's cache, so only a cell's first run in a checkout compiles."""
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def find_devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"the benchmark needs a TPU; JAX's first device "
                            f"is {devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chip(s), JAX finds "
                            f"{len(devs)}")
    return devs


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="with --trace 1: write one run of each traced "
                         "program, with its operations, to this JSON file")
    return ap.parse_args(argv)


def run(argv=None, *, root: Path = ROOT, require_tpu: bool = True,
        t_start: float = T_START) -> int:
    args = parse(argv)
    layout = Layout(root)
    entry = layout.workload(args.workload)
    limits = layout.checks(args.workload)
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    import jax
    cache = compile_cache(root)
    devices = find_devices(int(entry["chips"]), require_tpu)
    if jax.config.jax_enable_x64:
        raise RuntimeError("the served path runs in float32: x64 must be off")
    peaks = roofline.peak(layout.peaks(), devices[0].device_kind)
    from harness.cell import Cell

    cell = Cell(layout, args.workload, args.seed, args.seconds)
    report.log("setup", workload=args.workload, seed=args.seed,
               platform=devices[0].platform, device_kind=devices[0].device_kind,
               devices=len(devices), jax=jax.__version__, compile_cache=cache)
    try:
        phases = cell.setup()
        window = cell.run_window(trace=bool(args.trace))
        setup_s = window.t_open - t_start
        device = report.device_info(devices, int(entry["chips"]))
        report.log("setup", setup_s=repr(setup_s),
                   **{k: f"{v:.3f}" for k, v in phases.items()})
        _log_window(window)
        ctx = report.context(cell, window, peaks) if args.trace else None
        readback = cell.readback()
    finally:
        cell.close()
    reference = layout.reference(cell.cfg["reference"])
    t0 = time.perf_counter()
    cmp = report.compare(cell, reference, readback)
    report.log("check", compared=cmp["compared"],
               answer_gap=repr(cmp["answer_gap"]),
               answers_missing=cmp["answers_missing"],
               reference_s=f"{time.perf_counter() - t0:.3f}")

    breakdown = None
    if args.trace:
        if window.trace is not None and args.dump_trace:
            Path(args.dump_trace).write_text(json.dumps(
                {"device_kind": device["kind"], "sizes": ctx.sizes,
                 "counters": ctx.counters,
                 "counters_trace": ctx.counters_trace,
                 "programs": trc.program_samples(window.trace)}))
        metrics = report.per_layer(layout, args.workload, ctx)
        units = {m["name"]: m["unit"] for m in layout.per_layer(args.workload)}
        if window.trace is not None:
            device["busy_s"] = trc.busy_ns(window.trace) * 1e-9
            device["window_s"] = window.trace.window_ns * 1e-9
            breakdown = {"device_ops": trc.top_ops(window.trace),
                         "idle_gaps": trc.idle_gaps(window.trace)}
            report.log("trace", busy_s=device["busy_s"],
                       window_s=device["window_s"],
                       device_ops=len(window.trace.ops()),
                       programs=trc.top_programs(window.trace))
    else:
        values = report.end_to_end(window, setup_s)
        units = {m["name"]: m["unit"] for m in layout.end_to_end(args.workload)}
        metrics = {k: values[k] for k in units if k in values}
    checks = {"answer_gap": [cmp["answer_gap"],
                             float(limits["answer_gap"]["limit"])],
              "answers_missing": [cmp["answers_missing"] + window.missing, 0]}
    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": window.attempted,
              "failed": window.failed + window.missing,
              "metrics": {k: {"value": v, "unit": units[k]}
                          for k, v in metrics.items()},
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    report.emit(result, checks)
    return 0


def _log_window(w) -> None:
    import numpy as np
    lat = np.asarray(w.latencies_s) * 1e3
    late = np.asarray(w.lateness_s) * 1e3
    report.log("window", kind=w.kind, attempted=w.attempted,
               completed=w.completed, failed=w.failed, missing=w.missing,
               span_s=f"{w.span_s:.4f}", compiles_in_window=w.compiles,
               p50_ms=f"{np.percentile(lat, 50):.4f}" if lat.size else "-",
               p95_ms=f"{np.percentile(lat, 95):.4f}" if lat.size else "-",
               max_ms=f"{lat.max():.4f}" if lat.size else "-",
               generator_late_p50_ms=(f"{np.percentile(late, 50):.4f}"
                                      if late.size else "-"),
               generator_late_p99_ms=(f"{np.percentile(late, 99):.4f}"
                                      if late.size else "-"),
               counters=w.counters_close, errors=w.errors or "-")


def main() -> int:
    try:
        return run()
    except NoAccelerator as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3


if __name__ == "__main__":
    sys.exit(main())
