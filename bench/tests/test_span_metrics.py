"""The reduction of the engine's host spans, on small synthetic traces."""

from types import SimpleNamespace

import pytest

from conftest import REPO
from harness import spans
from harness import trace as trc
from harness.layout import Layout
from repro.core import engine as eng

E = trc.Event
US = 1000.0   # ns
READERS = ("ingest_span_ms", "ingest_transfer_ms", "idle_host_feed.refresh",
           "sched_busy_share.query", "query_host_ms_per_batch",
           "query_fetch_ms", "idle_sched_sleep.query")


def _trace(host_events, ops=(), window=(0.0, 100 * US)):
    """All host spans on one line named ``python``, as every Python
    thread's line is named in a real trace."""
    devices = {"/device:TPU:0": {trc.OPS_LINE: list(ops),
                                 trc.MODULES_LINE: []}}
    return trc.Trace(devices=devices, host={"python": list(host_events)},
                     window=window)


def _read(name, trace):
    return Layout(REPO).metric_reader(name).read(SimpleNamespace(trace=trace))


def test_self_time_leaves_out_nested_children():
    parents = [E(eng.SPAN_INGEST, 0, 50 * US)]
    kids = [E(eng.SPAN_INGEST_TRANSFER, 5 * US, 10 * US),
            E(eng.SPAN_INGEST_WAIT, 15 * US, 20 * US),     # back to back
            E(eng.SPAN_INGEST_COMMIT, 40 * US, 10 * US),   # ends with it
            E(eng.SPAN_INGEST_WAIT, 70 * US, 5 * US)]      # outside: ignored
    assert spans.self_time(parents, kids) == [(0, 5 * US), (35 * US, 40 * US)]


def test_self_time_on_several_threads_is_where_any_thread_is_in_it():
    # two ingests on two threads share one line; while one waits the
    # other is still in its own self time
    a = [E(eng.SPAN_INGEST, 0, 40 * US),
         E(eng.SPAN_INGEST_WAIT, 10 * US, 30 * US)]
    b = [E(eng.SPAN_INGEST, 20 * US, 40 * US),
         E(eng.SPAN_INGEST_WAIT, 50 * US, 10 * US)]
    parents = [a[0], b[0]]
    kids = [a[1], b[1]]
    assert spans.self_time(parents, kids) == [(0, 10 * US), (20 * US, 50 * US)]


def test_idle_parts_partition_the_idle_time():
    ops = [E("fusion", 0, 10 * US), E("fusion", 60 * US, 10 * US)]
    host = [
        # thread 1: an ingest feeding the device, then waiting on it
        E(eng.SPAN_INGEST, 5 * US, 50 * US),
        E(eng.SPAN_INGEST_TRANSFER, 12 * US, 8 * US),        # feed 12-20
        E(eng.SPAN_INGEST_WAIT, 30 * US, 25 * US),           # 30-55
        # thread 2: the scheduler, a pass, then asleep
        E(eng.SPAN_SCHED_PASS, 18 * US, 6 * US),             # 18-24
        E(eng.SPAN_SCHED_SLEEP, 24 * US, 46 * US),           # 24-70
        E(eng.SPAN_SCHED_SLEEP, 75 * US, 25 * US),           # 75-100
    ]
    t = _trace(host, ops)
    parts = spans.idle_parts(t)
    # idle: 10-60 and 70-100
    #   feed 12-20 = 8; engine (ingest self 10-12, 20-30; pass 20-24) = 12;
    #   asleep 30-60, 75-100 = 55; unattributed 70-75 = 5
    assert parts == {"feed": pytest.approx(8 * US),
                     "engine": pytest.approx(12 * US),
                     "asleep": pytest.approx(55 * US),
                     "unattributed": pytest.approx(5 * US)}
    idle = t.window_ns - trc.busy_ns(t)
    assert sum(parts.values()) == pytest.approx(idle)
    assert _read("idle_host_feed.refresh", t) == pytest.approx(100 * 8 / 80)
    assert _read("idle_sched_sleep.query", t) == pytest.approx(100 * 55 / 80)


def test_spans_are_clipped_at_the_slice_edges():
    ops = [E("fusion", 40 * US, 10 * US)]
    host = [E(eng.SPAN_SCHED_PASS, -20 * US, 30 * US),     # 10 us inside
            E(eng.SPAN_SCHED_PASS, 90 * US, 30 * US),      # 10 us inside
            E(eng.SPAN_SCHED_SLEEP, 10 * US, 80 * US),
            E(eng.SPAN_INGEST, -30 * US, 50 * US),         # midpoint out
            E(eng.SPAN_INGEST, 80 * US, 30 * US)]          # midpoint in
    t = _trace(host, ops)
    assert _read("sched_busy_share.query", t) == pytest.approx(20.0)
    assert _read("ingest_span_ms", t) == pytest.approx(30e-3)
    parts = spans.idle_parts(t)
    assert sum(parts.values()) == pytest.approx(90 * US)
    # the passes and ingests (no children) are engine self time: 0-20
    # and 80-100; the rest of the idle time, 20-40 and 50-80, asleep
    assert parts["engine"] == pytest.approx(40 * US)
    assert parts["asleep"] == pytest.approx(50 * US)
    assert parts["feed"] == parts["unattributed"] == 0


def test_per_ingest_and_per_batch_readers():
    host = [E(eng.SPAN_INGEST, 0, 40 * US),
            E(eng.SPAN_INGEST_TRANSFER, 2 * US, 6 * US),
            E(eng.SPAN_INGEST, 10 * US, 40 * US),
            E(eng.SPAN_INGEST_TRANSFER, 12 * US, 4 * US),
            E(eng.SPAN_INGEST_TRANSFER, -50 * US, 4 * US),   # not an ingest's
            E(eng.SPAN_QUERY_BATCH, 60 * US, 10 * US),
            E(eng.SPAN_QUERY_WAIT, 66 * US, 3 * US),
            E(eng.SPAN_QUERY_BATCH, 80 * US, 6 * US),
            E(eng.SPAN_QUERY_WAIT, 83 * US, 1 * US),
            E(eng.SPAN_QUERY_FETCH, 90 * US, 2 * US),
            E(eng.SPAN_QUERY_FETCH, 92 * US, 4 * US)]
    t = _trace(host, [E("fusion", 0, 1 * US)])
    assert _read("ingest_transfer_ms", t) == pytest.approx(5e-3)
    assert _read("query_host_ms_per_batch", t) == pytest.approx(6e-3)
    assert _read("query_fetch_ms", t) == pytest.approx(3e-3)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_without_its_span(name):
    other = [E("PjitFunction(ingest)", 0, 50 * US)]
    assert _read(name, _trace(other, [E("fusion", 0, 10 * US)])) is None
    assert _read(name, None) is None


def test_the_idle_parts_need_device_operations():
    host = [E(eng.SPAN_SCHED_SLEEP, 0, 100 * US)]
    assert spans.idle_parts(_trace(host)) is None
    assert _read("idle_sched_sleep.query", _trace(host)) is None
