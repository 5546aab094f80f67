"""Profiler trace capture and the reduction from trace to numbers.

``capture`` records a JAX profiler trace of a slice of the window;
``load`` reads the ``.xplane.pb`` it wrote into a plain ``Trace`` (lists
of events with start and duration in nanoseconds), which the per-layer
metric readers and the tests use.  Device planes are ``/device:TPU:<k>``;
their ``XLA Ops`` line holds one event per operation run on the chip
and their ``XLA Modules`` line one event per program run.  The traced
window is the host span ``bench.trace_window`` that the harness opens
around the slice.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.trace_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """``devices[plane][line]`` and ``host[line]`` are event lists;
    ``window`` is the traced slice ``(start_ns, end_ns)``."""

    devices: Dict[str, Dict[str, List[Event]]]
    host: Dict[str, List[Event]]
    window: Tuple[float, float]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def device_events(self, line: str) -> Dict[str, List[Event]]:
        """Per device plane, the events of ``line`` that overlap the window."""
        lo, hi = self.window
        return {p: [e for e in lines.get(line, [])
                    if e.end_ns > lo and e.start_ns < hi]
                for p, lines in self.devices.items()}

    def ops(self) -> List[Event]:
        return [e for evs in self.device_events(OPS_LINE).values()
                for e in evs]

    def modules(self) -> List[Event]:
        return [e for evs in self.device_events(MODULES_LINE).values()
                for e in evs]


# -- capture and load --------------------------------------------------------

@contextlib.contextmanager
def capture(parent: Optional[str] = None):
    """Trace JAX into a fresh directory; yields a dict that holds
    ``path`` (the ``.xplane.pb``) once the block exits.  The caller
    deletes the directory with ``discard``."""
    import jax
    out: Dict[str, str] = {}
    d = tempfile.mkdtemp(prefix="bench_trace_", dir=parent)
    out["dir"] = d
    # no Python function tracer: it would record every call the host
    # makes and slow the very host path being measured
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if found:
            out["path"] = found[0]


def discard(captured: Dict[str, str]) -> None:
    shutil.rmtree(captured.get("dir", ""), ignore_errors=True)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into a ``Trace``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = {
                line.name: [Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
                for line in plane.lines
                if line.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.setdefault(line.name, []).extend(
                    Event(e.name, e.start_ns, e.duration_ns)
                    for e in line.events)
    spans = [e for evs in host.values() for e in evs
             if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace {path} holds no {WINDOW_SPAN!r} span")
    w = max(spans, key=lambda e: e.dur_ns)
    return Trace(devices=devices, host=host, window=(w.start_ns, w.end_ns))


# -- reduction ---------------------------------------------------------------

def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of half-open intervals as a sorted disjoint list."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def union_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    """Length of the union of the events' intervals inside [lo, hi]."""
    return sum(b - a for a, b in
               merge(clip(((e.start_ns, e.end_ns) for e in events), lo, hi)))


def busy_ns(trace: Trace) -> float:
    """Device-busy time in the window, averaged over the device planes:
    the union of the intervals in which an operation ran."""
    per = trace.device_events(OPS_LINE)
    if not per:
        return 0.0
    lo, hi = trace.window
    return sum(union_ns(evs, lo, hi) for evs in per.values()) / len(per)


def idle_share(trace: Trace) -> Optional[float]:
    """100 * (1 - busy / window), in percent; None without device ops."""
    if not trace.devices or not trace.ops() or trace.window_ns <= 0:
        return None
    return 100.0 * (1.0 - busy_ns(trace) / trace.window_ns)


def select(events: Iterable[Event], pattern: str) -> List[Event]:
    """Events whose name matches the regular expression ``pattern``."""
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name)]


def inside(events: Iterable[Event], spans: Sequence[Event]) -> List[Event]:
    """Events whose midpoint lies inside one of ``spans``."""
    iv = merge((s.start_ns, s.end_ns) for s in spans)
    starts = [a for a, _ in iv]
    out = []
    for e in events:
        mid = e.start_ns + e.dur_ns / 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid < iv[i][1]:
            out.append(e)
    return out


def executions(trace: Trace, pattern: str) -> List[Event]:
    """Runs of the programs whose module name matches ``pattern`` and
    whose midpoint lies in the window: each run counted once, whole."""
    lo, hi = trace.window
    return [m for m in select(trace.modules(), pattern)
            if lo <= m.start_ns + m.dur_ns / 2 < hi]


def top_ops(trace: Trace, k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` device operations (by module and op name) that took most
    time in the window, with their summed seconds."""
    mods = trace.modules()
    tot: Dict[str, float] = {}
    mod_iv = sorted(((m.start_ns, m.end_ns, m.name) for m in mods))
    starts = [a for a, _, _ in mod_iv]
    lo, hi = trace.window
    for e in trace.ops():
        mid = e.start_ns + e.dur_ns / 2
        i = bisect.bisect_right(starts, mid) - 1
        mod = mod_iv[i][2] if i >= 0 and mid < mod_iv[i][1] else "?"
        key = f"{_short(mod)}/{e.name}"
        tot[key] = tot.get(key, 0.0) + max(
            0.0, min(e.end_ns, hi) - max(e.start_ns, lo))
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in ranked]


def top_programs(trace: Trace, k: int = 12) -> List[Tuple[str, int, float]]:
    """The ``k`` programs that ran longest on the device in the window:
    ``(name, runs, seconds)``; what a reader selects programs by."""
    tot: Dict[str, List[float]] = {}
    for m in trace.modules():
        acc = tot.setdefault(_short(m.name), [0, 0.0])
        acc[0] += 1
        acc[1] += m.dur_ns * 1e-9
    return sorted(((n, int(c), t) for n, (c, t) in tot.items()),
                  key=lambda r: -r[2])[:k]


def program_samples(trace: Trace) -> List[dict]:
    """One whole run of each program in the window (first device plane),
    with the operations inside it: ``{"module", "dur_ns", "ops":
    [[name, start_ns after the run's start, dur_ns], ...]}``.  What the
    readers' tests are built from, as a record of real event names."""
    per_ops = trace.device_events(OPS_LINE)
    per_mod = trace.device_events(MODULES_LINE)
    if not per_mod:
        return []
    plane = sorted(per_mod)[0]
    lo, hi = trace.window
    out, seen = [], set()
    for m in sorted(per_mod[plane], key=lambda e: e.start_ns):
        key = _short(m.name)
        if key in seen or m.start_ns < lo or m.end_ns > hi:
            continue
        seen.add(key)
        ops = inside(per_ops.get(plane, []), [m])
        out.append({"module": m.name, "dur_ns": m.dur_ns,
                    "ops": [[e.name, e.start_ns - m.start_ns, e.dur_ns]
                            for e in sorted(ops, key=lambda e: e.start_ns)]})
    return out


def _short(module: str) -> str:
    return module.split("(")[0]


def idle_gaps(trace: Trace, k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` longest device-idle gaps in the window (first device
    plane), each named by what the host was doing at its midpoint: the
    shortest host span that covers it, with its thread."""
    per = trace.device_events(OPS_LINE)
    if not per:
        return []
    lo, hi = trace.window
    evs = per[sorted(per)[0]]
    busy = merge(clip(((e.start_ns, e.end_ns) for e in evs), lo, hi))
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:k]:
        out.append([_host_at((a + b) / 2, trace), (b - a) * 1e-9])
    return out


def _host_at(t: float, trace: Trace) -> str:
    best: Optional[Tuple[float, str]] = None
    for line, evs in trace.host.items():
        for e in evs:
            if e.name == WINDOW_SPAN or not (e.start_ns <= t < e.end_ns):
                continue
            if best is None or e.dur_ns < best[0]:
                best = (e.dur_ns, f"{line}: {e.name}")
    return best[1] if best else "host idle"
