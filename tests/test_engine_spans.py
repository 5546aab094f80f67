"""The engine's host spans, read back from a real profiler trace.

A small engine serves ingests and queries through its scheduler thread
while ``jax.profiler`` traces it; the ``.xplane.pb`` is read with
``jax.profiler.ProfileData``.  Every span the engine names must be
there, nested in its parent on the parent's thread, and tracing must not
change a single answer.
"""

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import engine as E
from repro.core.engine import CTEngine
from repro.core.levels import CombinationScheme, grid_shape

TIMEOUT_S = 60.0

#: child span -> the span it nests in, on the same thread
PARENT = {
    E.SPAN_INGEST_TRANSFER: E.SPAN_INGEST,
    E.SPAN_INGEST_LAUNCH: E.SPAN_INGEST,
    E.SPAN_INGEST_WAIT: E.SPAN_INGEST,
    E.SPAN_INGEST_CHECK: E.SPAN_INGEST,
    E.SPAN_INGEST_COMMIT: E.SPAN_INGEST,
    E.SPAN_QUERY_EVAL: E.SPAN_QUERY_BATCH,
    E.SPAN_QUERY_POINTS: E.SPAN_QUERY_EVAL,
    E.SPAN_QUERY_LAUNCH: E.SPAN_QUERY_EVAL,
    E.SPAN_QUERY_WAIT: E.SPAN_QUERY_BATCH,
    E.SPAN_QUERY_BATCH: E.SPAN_SCHED_PASS,
}
ALL_SPANS = {v for k, v in vars(E).items() if k.startswith("SPAN_")}


def _grids(scheme, seed):
    rng = np.random.default_rng(seed)
    return {ell: rng.standard_normal(grid_shape(ell)) for ell, _ in scheme.grids}


def _serve(check_finite: bool, trace_dir=None):
    """Register two tenants, then through the started scheduler: one
    refresh each, then queries of both; returns the surpluses and the
    answers (and leaves a trace in ``trace_dir`` if given)."""
    scheme = CombinationScheme(2, 4)
    eng = CTEngine(deadline_ms=2.0)
    names = ("a", "b")
    for k, name in enumerate(names):
        eng.register(name, scheme, _grids(scheme, k))
    points = np.random.default_rng(9).random((5, 16, 2))
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    try:
        eng.start()
        ingests = [eng.submit_ingest(name, _grids(scheme, 10 + k),
                                     check_finite=check_finite)
                   for k, name in enumerate(names)]
        queries = [eng.submit_query(names[i % 2], points[i])
                   for i in range(len(points))]
        # wait() never drives the engine: everything runs on the
        # scheduler thread and the ingest pool
        assert all(f.wait(TIMEOUT_S) for f in ingests + queries)
        answers = [f.result() for f in queries]
        eng.stop(drain=True)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    surpluses = [np.asarray(eng.surplus(n)) for n in names]
    eng.close()
    return surpluses, answers


def _host_lines(trace_dir):
    found = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1, found
    pd = ProfileData.from_file(found[0])
    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for e in line.events if e.name in ALL_SPANS]
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines]


@pytest.mark.parametrize("check_finite", [False, True])
def test_engine_spans_nest_and_leave_the_answers_alone(tmp_path, check_finite):
    plain = _serve(check_finite)
    traced = _serve(check_finite, trace_dir=tmp_path)

    for want, got in zip(plain[0] + plain[1], traced[0] + traced[1]):
        np.testing.assert_array_equal(got, want)

    lines = _host_lines(tmp_path)
    seen = {name for line in lines for name, _, _ in line}
    expected = ALL_SPANS - (set() if check_finite else {E.SPAN_INGEST_CHECK})
    assert seen == expected

    for line in lines:
        for name, start, end in line:
            parent = PARENT.get(name)
            if parent is None:
                continue
            assert any(p == parent and ps <= start and end <= pe
                       for p, ps, pe in line), (name, parent)
        sched = sorted((s, e) for n, s, e in line
                       if n in (E.SPAN_SCHED_PASS, E.SPAN_SCHED_SLEEP))
        assert all(a_end <= b_start
                   for (_, a_end), (b_start, _) in zip(sched, sched[1:]))



def test_packed_transfer_nests_in_its_ingest(tmp_path):
    """Host grids take the packed feed; its one copy is still the
    ``ct.ingest.transfer`` span, inside its ``ct.ingest`` on the pool
    thread."""
    scheme = CombinationScheme(2, 4)
    eng = CTEngine(deadline_ms=2.0)
    eng.register("a", scheme, _grids(scheme, 0))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.start()
        ingests = [eng.submit_ingest("a", _grids(scheme, 20 + k))
                   for k in range(3)]
        assert all(f.wait(TIMEOUT_S) for f in ingests)
        eng.stop(drain=True)
    finally:
        jax.profiler.stop_trace()
    assert eng.stats()["ingest_feed"] == {"packed": 4, "per_part": 0}
    eng.close()

    transfers = 0
    for line in _host_lines(tmp_path):
        for name, start, end in line:
            if name != E.SPAN_INGEST_TRANSFER:
                continue
            transfers += 1
            assert any(p == E.SPAN_INGEST and ps <= start and end <= pe
                       for p, ps, pe in line)
    assert transfers == 3
