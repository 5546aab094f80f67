"""The engine's own host spans in a trace, reduced to numbers.

The engine opens named ``jax.profiler`` spans (the ``SPAN_*`` constants
of ``repro.core.engine``) around its scheduler passes and sleeps, each
ingest, each eval batch and their phases.  They land on the host lines
of the same trace as the device operations, on the same clock.  Every
Python thread's line has the same name, and ``trace.load`` merges lines
by name, so nothing here asks which thread a span ran on: a span is
found by name on any host line, and a parent's self time is where more
parent spans are open than child spans inside them (children nest on
their parent's thread, one at a time).

Functions take the engine's constant (``"SPAN_INGEST"``), not the span's
name.  Where the program defines no such constant, or the trace holds no
such span, they return ``None``.
"""

from __future__ import annotations

import bisect
import itertools
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import trace as trc

Interval = Tuple[float, float]

#: spans in which a host thread feeds the device: host-to-device copies,
#: stacking, and the calls that enqueue a program
FEED = ("SPAN_INGEST_TRANSFER", "SPAN_INGEST_LAUNCH", "SPAN_QUERY_STACK",
        "SPAN_QUERY_POINTS", "SPAN_QUERY_LAUNCH")
#: engine spans whose self time counts as engine work, with their children
ENGINE = {
    "SPAN_INGEST": ("SPAN_INGEST_TRANSFER", "SPAN_INGEST_LAUNCH",
                    "SPAN_INGEST_WAIT", "SPAN_INGEST_CHECK",
                    "SPAN_INGEST_COMMIT"),
    "SPAN_QUERY_BATCH": ("SPAN_QUERY_STACK", "SPAN_QUERY_POINTS",
                         "SPAN_QUERY_LAUNCH", "SPAN_QUERY_WAIT"),
    "SPAN_SCHED_PASS": ("SPAN_QUERY_BATCH", "SPAN_INGEST"),
    "SPAN_QUERY_FETCH": (),
}
ASLEEP = "SPAN_SCHED_SLEEP"
#: the parts of device-idle time, in the order an idle instant is given
#: to the first that holds
PARTS = ("feed", "engine", "asleep", "unattributed")


def span_name(const: str) -> Optional[str]:
    """The program's name for the span ``const``; ``None`` where the
    program defines no such constant."""
    from repro.core import engine
    return getattr(engine, const, None)


def events(trace: Optional[trc.Trace], const: str) -> List[trc.Event]:
    """Every event of the span ``const`` on any host line."""
    name = span_name(const)
    if trace is None or name is None:
        return []
    return [e for evs in trace.host.values() for e in evs if e.name == name]


def in_slice(trace: trc.Trace, evs: Iterable[trc.Event]) -> List[trc.Event]:
    """The events whose midpoint lies in the traced slice."""
    lo, hi = trace.window
    return [e for e in evs if lo <= e.start_ns + e.dur_ns / 2 < hi]


def mean_ms(trace: Optional[trc.Trace], const: str) -> Optional[float]:
    """Mean duration of the span ``const`` in the slice, milliseconds."""
    evs = in_slice(trace, events(trace, const)) if trace is not None else []
    if not evs:
        return None
    return sum(e.dur_ns for e in evs) * 1e-6 / len(evs)


def slice_share(trace: Optional[trc.Trace], const: str,
                present: Sequence[str] = ()) -> Optional[float]:
    """Share of the slice covered by the span ``const``, in percent.
    ``None`` unless ``const`` or one of the ``present`` spans is in the
    trace at all (so a span that was never open reads 0)."""
    if trace is None or trace.window_ns <= 0 or not any(
            events(trace, c) for c in (const, *present)):
        return None
    lo, hi = trace.window
    return 100.0 * trc.union_ns(events(trace, const), lo, hi) / trace.window_ns


# -- interval arithmetic on sorted disjoint lists -------------------------

def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def _length(iv: Iterable[Interval]) -> float:
    return sum(b - a for a, b in iv)


def _union(evs: Iterable[trc.Event]) -> List[Interval]:
    return trc.merge((e.start_ns, e.end_ns) for e in evs)


def self_time(parents: Sequence[trc.Event],
              children: Iterable[trc.Event]) -> List[Interval]:
    """Where some thread is inside a parent span and in none of its
    children: where more parents than children inside them are open.
    A child counts only inside a parent span."""
    spans = sorted((p.start_ns, p.end_ns) for p in parents)
    if not spans:
        return []
    starts = [s for s, _ in spans]
    reach = list(itertools.accumulate((e for _, e in spans), max))
    edges = []
    for s, e in spans:
        edges += [(s, 1), (e, -1)]
    for c in children:
        i = bisect.bisect_right(starts, c.start_ns) - 1
        if i >= 0 and reach[i] >= c.end_ns:
            edges += [(c.start_ns, -1), (c.end_ns, 1)]
    out, open_, start = [], 0, None
    # at one instant, apply closings before openings: a span that ends
    # where the next begins leaves no gap and no overlap
    for t, d in sorted(edges, key=lambda x: (x[0], x[1])):
        was = open_ > 0
        open_ += d
        if not was and open_ > 0:
            start = t
        elif was and open_ <= 0 and t > start:
            out.append((start, t))
    return trc.merge(out)


def idle_intervals(trace: trc.Trace) -> Dict[str, List[Interval]]:
    """Per device plane, the stretches of the slice with no operation
    running; empty without device operations."""
    per = trace.device_events(trc.OPS_LINE)
    if not any(per.values()):
        return {}
    lo, hi = trace.window
    return {plane: _subtract([(lo, hi)], trc.merge(trc.clip(
                ((e.start_ns, e.end_ns) for e in evs), lo, hi)))
            for plane, evs in per.items()}


def idle_parts(trace: Optional[trc.Trace]) -> Optional[Dict[str, float]]:
    """Device-idle time of the slice in nanoseconds (averaged over device
    planes, as ``trace.busy_ns``), split into ``PARTS``: an idle instant
    is ``feed`` if some thread is inside a feed span, else ``engine`` if
    some thread is in the self time of an engine span, else ``asleep``
    if the scheduler is in its sleep, else ``unattributed``.  ``None``
    without device operations or without any engine span."""
    if trace is None:
        return None
    idle = idle_intervals(trace)
    host = {c: events(trace, c) for c in
            set(FEED) | set(ENGINE) | {c for k in ENGINE.values() for c in k}
            | {ASLEEP}}
    if not idle or not any(host.values()):
        return None
    feed = _union(e for c in FEED for e in host[c])
    engine = trc.merge(iv for p, kids in ENGINE.items() for iv in self_time(
        host[p], (e for c in kids for e in host[c])))
    asleep = _union(host[ASLEEP])
    parts = dict.fromkeys(PARTS, 0.0)
    for gaps in idle.values():
        left = gaps
        for part, cover in (("feed", feed), ("engine", engine),
                            ("asleep", asleep)):
            parts[part] += _length(_intersect(left, cover))
            left = _subtract(left, cover)
        parts["unattributed"] += _length(left)
    return {k: v / len(idle) for k, v in parts.items()}


def idle_part_share(trace: Optional[trc.Trace], part: str,
                    needs: Sequence[str]) -> Optional[float]:
    """Share of the device-idle time (not of the slice) given to
    ``part``, in percent; ``None`` unless one of the ``needs`` spans is
    in the trace."""
    if not any(events(trace, c) for c in needs):
        return None
    parts = idle_parts(trace)
    if parts is None:
        return None
    total = sum(parts.values())
    return 100.0 * parts[part] / total if total > 0 else None
