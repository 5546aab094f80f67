"""From a finished window to the numbers a run prints.

End-to-end metrics are taken here from the host clock; per-layer metrics
are read by the per-metric readers under ``bench/metrics`` from the
``Context`` built here; the check compares the served answers with the
plain reference.
"""

from __future__ import annotations

import json
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from . import scheme as sch
from . import trace as trc


@dataclass
class Context:
    """What a per-layer metric reader may read."""

    cell: str
    config: dict
    traffic: dict
    trace: Optional[trc.Trace]
    counters: Dict[str, int]          # engine counters, deltas over the window
    counters_trace: Dict[str, int]    # ... over the traced slice
    sizes: Dict[str, int]             # sizes from the benchmark's arithmetic
    peaks: dict                       # this device kind's row of peaks.json


def _delta(a: Dict[str, int], b: Dict[str, int]) -> Dict[str, int]:
    return {k: b[k] - a.get(k, 0) for k in b}


def p95_ms(latencies_s: List[float]) -> Optional[float]:
    if not latencies_s:
        return None
    return float(np.percentile(np.asarray(latencies_s) * 1e3, 95))


def end_to_end(window, setup_s: float) -> Dict[str, float]:
    """Every end-to-end value this window can give, by metric name."""
    out = {"setup_s": setup_s}
    rate = window.completed / window.span_s if window.span_s > 0 else None
    if window.kind == "refresh":
        out["ingest_ms_p95"] = p95_ms(window.latencies_s)
        out["ingests_per_s"] = rate
    if window.kind == "query":
        out["query_ms_p95"] = p95_ms(window.latencies_s)
        out["queries_per_s"] = rate
    return {k: v for k, v in out.items() if v is not None}


def sizes(cell) -> Dict[str, int]:
    itemsize = np.dtype(cell.cfg["exec"].get("dtype") or "float32").itemsize
    return {"fine_points": sch.num_points(sch.fine_levels(cell.grids)),
            "least_ingest_bytes": sch.least_ingest_bytes(cell.grids, itemsize),
            "hier_kernel_bytes": sch.hierarchize_bytes(
                cell.pallas_member_levels(), itemsize)}


def context(cell, window, peaks: dict) -> Context:
    before, after = window.counters_trace
    return Context(cell=cell.name, config=cell.cfg, traffic=cell.mix,
                   trace=window.trace,
                   counters=_delta(window.counters_open, window.counters_close),
                   counters_trace=_delta(before, after) if after else {},
                   sizes=sizes(cell), peaks=peaks)


class MetricMissing(RuntimeError):
    """A per-layer metric the cell lists found nothing to read."""


def per_layer(layout, cell_name: str, ctx: Context) -> Dict[str, float]:
    """Every per-layer metric the cell lists, read by its reader.

    A reader that finds nothing returns nothing; but a metric that the
    cell lists must be there to read: a counter always, a trace metric
    whenever the trace holds device operations (it holds none off the
    chip).  So a renamed program or kernel stops the run loudly, naming
    what the trace does hold, instead of leaving the metric out."""
    out, missing = {}, []
    on_device = ctx.trace is not None and bool(ctx.trace.ops())
    for m in layout.per_layer(cell_name):
        value = layout.metric_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = float(value)
        elif m["source"] != "device_trace" or on_device:
            missing.append(m["name"])
    if missing:
        seen = trc.top_programs(ctx.trace) if ctx.trace is not None else []
        raise MetricMissing(f"{cell_name}: nothing to read for {missing}; "
                            f"the traced programs were {seen}")
    return out


def compare(cell, reference_mod, readback, control: bool = False) -> Dict[str, float]:
    """Widest gap between served answers and the reference, relative to
    the largest reference value, over every compared point; and how many
    compared answers never came.  With ``control=True`` the reference's
    lower-precision control stands in the program's place."""
    by_tenant: Dict[int, List] = {}
    missing = 0
    for t, pts, ans in readback:
        if ans is None:
            missing += 1
            continue
        by_tenant.setdefault(t, []).append((pts, ans))
    num, den = 0.0, 0.0
    for t, items in by_tenant.items():
        pts = np.concatenate([p for p, _ in items])
        got = np.concatenate([np.asarray(a, np.float64).reshape(-1)
                              for _, a in items])
        grids = cell.tenant_grids(t)
        want = reference_mod.reference_values(grids, pts)
        if control:
            got = reference_mod.reference_values(grids, pts, control=True)
        if got.shape != want.shape or not np.all(np.isfinite(got)):
            num = float("inf")
            continue
        num = max(num, float(np.max(np.abs(got - want))))
        den = max(den, float(np.max(np.abs(want))))
    gap = num / den if den > 0 else float("inf")
    return {"answer_gap": gap, "answers_missing": missing,
            "compared": sum(len(v) for v in by_tenant.values())}


def device_info(devices, used: int) -> dict:
    peak = 0
    for d in devices[:used]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def emit(result: dict, checks: Dict[str, list]) -> None:
    """The numbers compared as the last lines of standard error, then the
    result as the last line of standard output with ``checks`` last."""
    for name, (value, limit) in checks.items():
        print(f"check {name} = {value!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    print(json.dumps(result), flush=True)
