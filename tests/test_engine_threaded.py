"""Concurrency stress tier for the thread-safe CTEngine (PR 6).

Every test here hammers the engine (or the process-global caches) from
many threads and asserts the serving contract holds: no dropped or hung
futures, exact cache accounting, bit-identical results to a
single-threaded replay.  The tier runs in its own CI job
(``pytest -m threaded``) with ``PYTHONFAULTHANDLER=1`` so a deadlock
dumps stacks instead of timing out silently.
"""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as E
from repro.core.engine import CTEngine, clear_compile_cache, plan_signature
from repro.core.executor import build_plan, clear_plan_cache
from repro.core.levels import CombinationScheme, GeneralScheme, grid_shape

pytestmark = pytest.mark.threaded

N_THREADS = 8
RESULT_TIMEOUT = 120.0


@pytest.fixture(autouse=True)
def _fresh_caches():
    clear_compile_cache()
    clear_plan_cache()
    yield


def _random_grids(scheme, rng, dtype=np.float64):
    return {ell: jnp.asarray(rng.standard_normal(grid_shape(ell)), dtype)
            for ell, _ in scheme.grids}


def _run_threads(fns):
    """Run one callable per thread; re-raise the first worker error."""
    errors = []
    barrier = threading.Barrier(len(fns))

    def wrap(fn):
        try:
            barrier.wait(timeout=30)
            fn()
        except BaseException as exc:           # noqa: BLE001 — reported below
            errors.append(exc)

    threads = [threading.Thread(target=wrap, args=(fn,), daemon=True)
               for fn in fns]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=RESULT_TIMEOUT)
        assert not t.is_alive(), "worker thread hung"
    if errors:
        raise errors[0]


# ---------------------------------------------------------------------------
# Acceptance: 8 submitter threads x 9 tenants == single-threaded replay
# ---------------------------------------------------------------------------

def test_threaded_mixed_load_bit_identical_to_serial_replay():
    """8 submitter threads drive 9 tenants (3 schemes x 3 tenants) with
    mixed closed-loop ingest/query traffic against ONE started engine;
    every per-tenant result sequence is bit-identical to the same
    workload replayed single-threaded, with zero dropped/hung futures."""
    schemes = [CombinationScheme(2, 3), CombinationScheme(2, 4),
               CombinationScheme(3, 3)]
    tenants = [(f"t{s}_{k}", schemes[s]) for s in range(3) for k in range(3)]
    rounds = 4

    def tenant_workload(name, scheme):
        """Deterministic per-tenant op sequence: (grids_r, points_r)."""
        seed = abs(hash(name)) % (2 ** 31)
        rng = np.random.default_rng(seed)
        ops = []
        for r in range(rounds):
            grids = _random_grids(scheme, rng)
            pts = rng.random((8, scheme.dim))
            ops.append((grids, pts))
        return ops

    workloads = {name: tenant_workload(name, scheme)
                 for name, scheme in tenants}

    def drive(engine, results, my_tenants):
        """Closed-loop per tenant: ingest_r -> query_r -> wait, so the
        result sequence is deterministic regardless of scheduling."""
        cursors = {name: 0 for name in my_tenants}
        while cursors:
            for name in list(cursors):
                r = cursors[name]
                grids, pts = workloads[name][r]
                fi = engine.submit_ingest(name, grids)
                fq = engine.submit_query(name, pts)
                val = fq.result(timeout=RESULT_TIMEOUT)
                fi.result(timeout=RESULT_TIMEOUT)
                results[name].append(np.asarray(val).copy())
                cursors[name] = r + 1
                if cursors[name] == rounds:
                    del cursors[name]

    # -- concurrent run: 8 threads, tenants round-robin across them ------
    eng = CTEngine(deadline_ms=5.0)
    for name, scheme in tenants:
        eng.register(name, scheme, workloads[name][0][0])
    got = {name: [] for name, _ in tenants}
    shards = [[] for _ in range(N_THREADS)]
    for i, (name, _) in enumerate(tenants):
        shards[i % N_THREADS].append(name)
    with eng:
        _run_threads([
            (lambda names=names: drive(eng, got, names))
            for names in shards if names])
    eng.close()

    # -- serial replay ---------------------------------------------------
    ref_eng = CTEngine()
    for name, scheme in tenants:
        ref_eng.register(name, scheme, workloads[name][0][0])
    ref = {name: [] for name, _ in tenants}
    for name, _ in tenants:
        drive(ref_eng, ref, [name])

    for name, _ in tenants:
        assert len(got[name]) == rounds, f"{name}: dropped results"
        for r in range(rounds):
            np.testing.assert_array_equal(
                got[name][r], ref[name][r],
                err_msg=f"{name} round {r} diverged from serial replay")

    st = eng.stats()
    assert st["scheduler"]["pending"] == 0          # nothing left behind
    assert st["ingests"] >= 9 * rounds
    # every dispatch counted once, on its feed, by 8 racing pool threads
    assert sum(st["ingest_feed"].values()) == st["ingests"]


# ---------------------------------------------------------------------------
# Satellite: _INGEST_EXECUTABLES lock — no lost executables, exact counts
# ---------------------------------------------------------------------------

def test_ingest_cache_accounting_two_engines_eight_threads():
    """8 threads bind tenants across 2 engines concurrently: afterwards
    every distinct signature is present exactly once in the shared cache
    (no lost executables, no duplicate builds) and hits+misses across
    both engines account for EVERY bind exactly — one miss per
    signature, hits for all the rest."""
    schemes = [CombinationScheme(2, 2), CombinationScheme(2, 3),
               CombinationScheme(3, 2), CombinationScheme(2, 4)]
    engines = [CTEngine(), CTEngine()]
    binds_per_thread = 8

    def worker(tid):
        rng = np.random.default_rng(tid)
        for j in range(binds_per_thread):
            eng = engines[(tid + j) % 2]
            scheme = schemes[(tid * binds_per_thread + j) % len(schemes)]
            eng.register(f"w{tid}_{j}", scheme, _random_grids(scheme, rng))

    _run_threads([lambda tid=t: worker(tid) for t in range(N_THREADS)])

    sigs = {plan_signature(build_plan(s), E.ExecSpec()) for s in schemes}
    with E._INGEST_CACHE_LOCK:
        cached = set(E._INGEST_EXECUTABLES)
    assert sigs <= cached, "lost executables under concurrent binding"

    hits = sum(e._counters["cache_hits"] for e in engines)
    misses = sum(e._counters["cache_misses"] for e in engines)
    total_binds = N_THREADS * binds_per_thread
    assert hits + misses == total_binds, "double- or under-counted binds"
    assert misses == len(schemes), \
        f"expected exactly one miss per signature, got {misses}"

    # every tenant actually serves
    pts2 = np.random.default_rng(1).random((4, 2))
    pts3 = np.random.default_rng(2).random((4, 3))
    for eng in engines:
        for name in eng.names():
            dim = eng.scheme(name).dim
            assert eng.query(name, pts3 if dim == 3 else pts2).shape == (4,)


# ---------------------------------------------------------------------------
# Flush swap: concurrent submitters never lose a request
# ---------------------------------------------------------------------------

def test_concurrent_flush_never_drops_submissions():
    """Submitters race a dedicated flusher loop: every submitted future
    resolves (the queue swap is atomic; nothing enqueued during a
    concurrent flush is dropped)."""
    scheme = CombinationScheme(2, 3)
    eng = CTEngine(max_pending=10_000)
    eng.register("t", scheme, _random_grids(scheme, np.random.default_rng(3)))
    pts = np.random.default_rng(30).random((4, 2))
    per_thread = 50
    all_futs = [[] for _ in range(N_THREADS)]
    stop = threading.Event()

    def flusher():
        while not stop.is_set():
            eng.flush()
        eng.flush()

    def submitter(tid):
        for _ in range(per_thread):
            all_futs[tid].append(eng.submit_query("t", pts))

    fl = threading.Thread(target=flusher, daemon=True)
    fl.start()
    try:
        _run_threads([lambda tid=t: submitter(tid) for t in range(N_THREADS)])
    finally:
        stop.set()
        fl.join(timeout=30)
    assert not fl.is_alive()

    want = eng.query("t", pts)
    for futs in all_futs:
        assert len(futs) == per_thread
        for f in futs:
            np.testing.assert_array_equal(f.result(timeout=RESULT_TIMEOUT),
                                          want)
    assert eng.stats()["scheduler"]["pending"] == 0


# ---------------------------------------------------------------------------
# Satellite: lifecycle races — unregister/refit vs queued work, no hangs
# ---------------------------------------------------------------------------

def test_unregister_racing_queued_work_resolves_every_future():
    """unregister/re-register churns while submitters enqueue: every
    future resolves — with a value or a NAMED KeyError — and none hang."""
    scheme = CombinationScheme(2, 3)
    rng = np.random.default_rng(4)
    grids = _random_grids(scheme, rng)
    eng = CTEngine(max_pending=10_000)
    eng.register("t", scheme, grids)
    pts = np.random.default_rng(40).random((4, 2))
    rounds = 30
    futs_lock = threading.Lock()
    futs = []

    def submitter():
        for _ in range(rounds):
            batch = []
            try:
                batch.append(eng.submit_ingest("t", grids))
                batch.append(eng.submit_query("t", pts))
            except KeyError:
                pass                       # raced the unregister window
            with futs_lock:
                futs.extend(batch)
            eng.flush()

    def churner():
        for _ in range(rounds):
            eng.unregister("t")
            eng.register("t", scheme, grids)
            # dwell registered: register's insert lands only after its
            # initial ingest, so a zero-dwell churn keeps the tenant
            # missing nearly all the time and no traffic would land
            time.sleep(0.002)

    _run_threads([submitter] * (N_THREADS - 1) + [churner])
    eng.flush()
    # post-churn traffic: the engine must still serve after the storm
    # (also pins outcomes["ok"] > 0 deterministically — the concurrent
    # rounds above can legitimately all land in unregister windows)
    futs.append(eng.submit_ingest("t", grids))
    futs.append(eng.submit_query("t", pts))
    eng.flush()

    outcomes = {"ok": 0, "keyerror": 0}
    for f in futs:
        try:
            f.result(timeout=RESULT_TIMEOUT)
            outcomes["ok"] += 1
        except KeyError as exc:
            assert "unregistered" in str(exc)
            outcomes["keyerror"] += 1
    assert outcomes["ok"] + outcomes["keyerror"] == len(futs)
    assert outcomes["ok"] > 0              # some traffic really served
    assert eng.stats()["scheduler"]["pending"] == 0


def test_refit_racing_queued_ingests_commits_consistently():
    """refit swaps the tenant record while queued ingests are in flight:
    the CAS commit retries, no future hangs, and the tenant ends serving
    a consistent (scheme, surplus) pair."""
    gs = GeneralScheme.regular(2, 2)
    grown = gs.with_levels([(3, 1)])
    rng = np.random.default_rng(5)
    grids_small = _random_grids(gs, rng)
    grids_big = {ell: jnp.asarray(rng.standard_normal(grid_shape(ell)))
                 for ell, _ in grown.grids}
    eng = CTEngine(max_pending=10_000)
    rounds = 20
    futs_lock = threading.Lock()
    futs = []

    eng.register("t", gs, grids_small)

    def submitter():
        for _ in range(rounds):
            try:
                f = eng.submit_ingest("t", grids_big)   # valid on BOTH plans
            except KeyError:
                continue
            with futs_lock:
                futs.append(f)
            eng.flush()

    def refitter():
        for i in range(rounds):
            try:
                if i % 2 == 0:
                    eng.refit("t", grown, grids_big)
                else:
                    eng.unregister("t")
                    eng.register("t", gs, grids_small)
            except KeyError:
                pass                       # raced another lifecycle op
            eng.flush()

    _run_threads([submitter] * (N_THREADS - 1) + [refitter])
    eng.flush()

    for f in futs:
        try:
            f.result(timeout=RESULT_TIMEOUT)
        except (KeyError, ValueError):
            # unregistered mid-flight, or grids_big vs the small plan —
            # named failure is fine; hanging is not
            pass
    surp = eng.surplus("t")
    assert np.all(np.isfinite(np.asarray(surp)))


# ---------------------------------------------------------------------------
# Satellite: plan cache under threads + explicit clear
# ---------------------------------------------------------------------------

def test_plan_cache_identity_stable_under_threads():
    """Concurrent ``build_plan`` of the same scheme returns ONE plan
    object (first insert wins — ``extend_plan`` relies on bucket
    identity), and ``clear_plan_cache`` is safe against racing builds."""
    scheme = CombinationScheme(2, 4)
    plans = [None] * N_THREADS

    def worker(tid):
        plans[tid] = build_plan(scheme)

    _run_threads([lambda tid=t: worker(tid) for t in range(N_THREADS)])
    assert all(p is plans[0] for p in plans), \
        "concurrent builders observed different cached plan objects"

    stop = threading.Event()

    def clearer():
        while not stop.is_set():
            clear_plan_cache()

    def builder():
        for _ in range(200):
            p = build_plan(scheme)
            assert p.fine_shape == plans[0].fine_shape

    cl = threading.Thread(target=clearer, daemon=True)
    cl.start()
    try:
        _run_threads([builder for _ in range(4)])
    finally:
        stop.set()
        cl.join(timeout=30)
    assert not cl.is_alive()


# ---------------------------------------------------------------------------
# Started-scheduler end-to-end under submitter threads
# ---------------------------------------------------------------------------

def test_started_engine_sustains_threaded_submitters_without_flush():
    """With the scheduler thread running, submitter threads never call
    flush (we wait on the raw events): deadlines alone drain the queue."""
    scheme = CombinationScheme(2, 3)
    eng = CTEngine(deadline_ms=2.0, max_pending=10_000)
    eng.register("t", scheme, _random_grids(scheme, np.random.default_rng(6)))
    pts = np.random.default_rng(60).random((4, 2))
    want = eng.query("t", pts)
    per_thread = 25

    def submitter():
        for _ in range(per_thread):
            f = eng.submit_query("t", pts)
            assert f._event.wait(timeout=RESULT_TIMEOUT), "future hung"
            np.testing.assert_array_equal(f.result(), want)

    with eng:
        _run_threads([submitter for _ in range(N_THREADS)])
    st = eng.stats()
    assert st["scheduler"]["pending"] == 0
    assert st["eval"]["queries"] >= N_THREADS * per_thread
