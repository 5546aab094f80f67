"""CTCluster serving under a mid-run host kill + restart: the failover
and durability SLO bench.

The PR-7 claim priced here: a 4-host `CTCluster` absorbs the loss of a
host in the middle of an open-loop serving load with ZERO dropped
futures — every request submitted before, during, and after the kill
resolves to a value or to the named ``HostFailed`` (unreplicated
in-flight ingests only; queries are transparently retried on the new
owner) — and the post-recovery tail stays within 3x of the pre-failover
tail at equal offered load.

The PR-9 claim stacked on top: with per-host durable stores (WAL +
surplus snapshots) the victim is RESTARTED mid-load — fresh engine over
the same store, restore -> rejoin -> WAL replay — after which placement
returns EXACTLY to the pre-kill assignment and every tenant's answers
are BIT-IDENTICAL to a never-crashed single-engine oracle fed the same
acked ingests (``lost_acked_ingests == 0``, the chaos CI bar).  The
recovery time is split into its three phases (snapshot restore, ring
re-placement, WAL replay).

The harness replays ``benchmarks/serve_engine.py``'s open-loop schedule
(fixed-QPS queries + periodic ingest bursts) against the cluster front
door, kills the primary of a live tenant at the half-way mark via the
``FaultInjector``, lets the health monitor (heartbeat + probe query)
detect and fail it over, then calls ``restart_host`` at the 3/4 mark
WITHOUT pausing the load, and records

  * ``recovery_ms`` — injected kill to failover complete (victim out of
    the ring, every tenant re-owned): detection latency + migration,
  * ``restart`` — the restore / replace (re-placement) / replay split
    of the rejoin, in ms,
  * ``dropped_futures`` — hung (never resolved) or resolved with an
    UNNAMED error; the CI bar is exactly 0,
  * ``lost_acked_ingests`` — tenants whose post-restart answers differ
    from the oracle fed their newest acked payload; the CI bar is 0,
  * ``p99_pre_ms`` / ``p99_post_ms`` — query tail latency for arrivals
    before the kill vs after the restart completed, same offered QPS.

  PYTHONPATH=src python benchmarks/serve_cluster.py
"""

from __future__ import annotations

import argparse
import json
import tempfile
import threading
import time

import jax
import numpy as np

jax.config.update("jax_enable_x64", True)

from repro.core.engine import CTEngine, EngineSaturated  # noqa: E402
from repro.core.levels import CombinationScheme, grid_shape  # noqa: E402
from repro.runtime.cluster import CTCluster, HostFailed  # noqa: E402
from repro.runtime.fault_tolerance import HostHealthConfig  # noqa: E402

#: tenant fleet: M tenants per scheme — deliberate signature sharing, so
#: migrated tenants re-bind from the process-global executable cache
#: (failover compiles nothing)
SCHEMES = [CombinationScheme(2, 5), CombinationScheme(3, 4),
           CombinationScheme(4, 3)]
TENANTS_PER_SCHEME = 3
QUERY_POINTS = 64
N_HOSTS = 4

#: errors that count as RESOLVED, not dropped: the named failover error
#: plus the engine's own per-request validation/NaN errors
NAMED_ERRORS = (HostFailed, EngineSaturated, FloatingPointError, KeyError,
                ValueError)


def _fleet(rng):
    tenants = []
    for scheme in SCHEMES:
        for m in range(TENANTS_PER_SCHEME):
            grids = {ell: rng.standard_normal(grid_shape(ell))
                     for ell, _ in scheme.grids}
            tenants.append((f"d{scheme.dim}n{scheme.level}_t{m}", scheme,
                            grids))
    return tenants


def _schedule(n_queries, qps, ingest_every, burst):
    """Open-loop arrivals: queries at fixed ``qps`` spacing, a burst of
    ``burst`` ingests every ``ingest_every`` queries."""
    events = []
    for i in range(n_queries):
        events.append((i / qps, "query", i))
        if ingest_every and i % ingest_every == ingest_every - 1:
            events.extend([(i / qps, "ingest", i + j) for j in range(burst)])
    return events


def _warmup(cluster, tenants, points):
    """Compile every dispatch shape before timing: each signature's
    ingest executable (registration did that) plus the batched eval at
    the power-of-two T-pad buckets the per-host scheduler can form."""
    for group_size in (1, 5, 9, 17):
        futs = []
        for name, _, _ in tenants:
            futs.extend(cluster.submit_query(name, points[name])
                        for _ in range(group_size))
        for f in futs:
            f.result(120.0)


def _oracle_mismatches(tenants, points, initial, ingest_log, got):
    """Never-crashed oracle: one fresh engine per tenant fed the same
    acked ingests (full-dict last-writer-wins -> the newest acked
    payload IS the final state).  Returns the tenants whose cluster
    answers are not bit-identical to the oracle's."""
    bad = []
    for name, scheme, _ in tenants:
        acked = [(seq, payload) for seq, payload, ok in ingest_log[name]
                 if ok]
        final = max(acked, key=lambda x: x[0])[1] if acked \
            else initial[name]
        oracle = CTEngine(host_id="oracle")
        oracle.register(name, scheme, final)
        want = oracle.query(name, points[name])
        if not np.array_equal(np.asarray(got[name]), np.asarray(want)):
            bad.append(name)
    return bad


def bench(n_queries, qps, ingest_every, burst, deadline_ms,
          durability_dir=None):
    rng = np.random.default_rng(0)
    tenants = _fleet(rng)
    names = [name for name, _, _ in tenants]
    points = {name: rng.random((QUERY_POINTS, scheme.dim))
              for name, scheme, _ in tenants}
    initial = {name: grids for name, _, grids in tenants}
    base_refresh = {name: {ell: rng.standard_normal(grid_shape(ell))
                           for ell, _ in scheme.grids}
                    for name, scheme, _ in tenants}

    durability_dir = durability_dir or tempfile.mkdtemp(
        prefix="ct-durability-")
    cluster = CTCluster(
        N_HOSTS, replication=1, seed=7,
        health=HostHealthConfig(heartbeat_timeout_s=1.0,
                                probe_deadline_s=0.5, max_strikes=2),
        monitor_interval_s=0.05,
        durability_dir=durability_dir, snapshot_interval=8,
        engine_kwargs={"deadline_ms": deadline_ms,
                       "max_pending": 1_000_000})
    for name, scheme, grids in tenants:
        cluster.register(name, scheme, grids)
    placement = {n: list(cluster.owners_of(n)) for n in names}

    events = _schedule(n_queries, qps, ingest_every, burst)
    kill_at = events[len(events) // 2][0]      # half-way arrival time
    restart_at = events[(3 * len(events)) // 4][0]
    victim = cluster.owners_of(names[0])[0]
    victim_tenants = [n for n in names if cluster.owners_of(n)[0] == victim]

    #: per-tenant ingest payload log: (cluster submit order, payload,
    #: acked) — distinct payloads per submission so the oracle check is
    #: sensitive to a LOST acked ingest, not just a lost tenant
    ingest_log = {n: [] for n in names}
    ingest_counter = {n: 0 for n in names}

    with cluster:                              # start hosts + monitor
        _warmup(cluster, tenants, points)

        def _recovered():
            return victim not in cluster.live_hosts() and all(
                victim not in cluster.owners_of(n) for n in names)

        restart_result = {}

        def _do_restart():
            t = time.monotonic()
            restart_result["outcomes"] = cluster.restart_host(victim)
            restart_result["wall_ms"] = (time.monotonic() - t) * 1e3

        futs, killed_t, recovered_t = [], None, None
        restart_thread = None
        t0 = time.monotonic()
        for dt, kind, i in events:
            target = t0 + dt
            now = time.monotonic()
            while now < target:
                time.sleep(min(0.0005, target - now))
                now = time.monotonic()
            if killed_t is None and now - t0 >= kill_at:
                cluster.injector.kill(victim)  # mid-run host loss
                killed_t = time.monotonic()
            if killed_t is not None and recovered_t is None \
                    and _recovered():
                recovered_t = time.monotonic()
            if restart_thread is None and now - t0 >= restart_at \
                    and recovered_t is not None:
                # rejoin the victim at full load: restore + re-place +
                # WAL replay race the open-loop arrivals below
                restart_thread = threading.Thread(target=_do_restart,
                                                  daemon=True)
                restart_thread.start()
            name = names[i % len(names)]
            sub = time.monotonic()
            if kind == "query":
                futs.append((sub, "query", None,
                             cluster.submit_query(name, points[name])))
            else:
                k = ingest_counter[name] = ingest_counter[name] + 1
                payload = {ell: g * (1.0 + 0.01 * k)
                           for ell, g in base_refresh[name].items()}
                f = cluster.submit_ingest(name, payload)
                ingest_log[name].append([k, payload, f])
                futs.append((sub, "ingest", name, f))
        if killed_t is None:                   # load ended early: kill now
            cluster.injector.kill(victim)
            killed_t = time.monotonic()

        # failover complete = victim out of the ring and un-owned
        deadline = time.monotonic() + 60.0
        while recovered_t is None and time.monotonic() < deadline:
            if _recovered():
                recovered_t = time.monotonic()
                break
            time.sleep(0.001)
        assert recovered_t is not None, "failover never completed"
        recovery_ms = (recovered_t - killed_t) * 1e3

        # the restart must run even if the schedule ended before 3/4
        if restart_thread is None:
            restart_thread = threading.Thread(target=_do_restart,
                                              daemon=True)
            restart_thread.start()
        restart_thread.join(timeout=120.0)
        assert not restart_thread.is_alive(), "restart_host hung"
        restart_done_t = time.monotonic()

        # a post-restart tail at the same offered spacing, so the
        # recovered steady state has its own latency samples
        tail = max(50, len(events) // 4)
        for i in range(tail):
            target = restart_done_t + i / qps
            now = time.monotonic()
            while now < target:
                time.sleep(min(0.0005, target - now))
                now = time.monotonic()
            name = names[i % len(names)]
            futs.append((time.monotonic(), "query", None,
                         cluster.submit_query(name, points[name])))

        hung = unnamed = host_failed = retried = 0
        q_lat = []                             # (submit_t, latency_ms)
        for sub, kind, _, f in futs:
            if not f.wait(120.0):
                hung += 1
                continue
            err = f.error()
            if err is not None:
                if isinstance(err, HostFailed):
                    host_failed += 1
                elif not isinstance(err, NAMED_ERRORS):
                    unnamed += 1
                continue
            retried += f.retargeted
            if kind == "query":
                q_lat.append((sub, (f.done_at - sub) * 1e3))
        dropped = hung + unnamed
        # resolve the ingest log to (seq, payload, acked) triples
        for n in names:
            ingest_log[n] = [(k, payload,
                              f.done() and f.error() is None)
                             for k, payload, f in ingest_log[n]]

        pre = np.asarray([ms for sub, ms in q_lat if sub < killed_t])
        post = np.asarray([ms for sub, ms in q_lat
                           if sub > restart_done_t])
        stats = cluster.stats()

        # post-restart: placement returned to the PRE-KILL assignment
        # (same seeded vnodes), and every tenant answers
        placement_after = {n: list(cluster.owners_of(n)) for n in names}
        got = {n: cluster.query(n, points[n]) for n in names}
        for n in names:
            assert np.all(np.isfinite(got[n]))

    lost = _oracle_mismatches(tenants, points, initial, ingest_log, got)

    p99_pre = float(np.percentile(pre, 99)) if len(pre) else None
    p99_post = float(np.percentile(post, 99)) if len(post) else None
    failover = stats["failovers"][0] if stats["failovers"] else {}
    restart = stats["restarts"][-1] if stats["restarts"] else {}

    payload = {
        "bench": "serve_cluster",
        "backend": jax.default_backend(),
        "hosts": N_HOSTS,
        "tenants": len(tenants),
        "distinct_schemes": len(SCHEMES),
        "replication": 1,
        "qps_offered": qps,
        "queries": int(sum(1 for _, k, _, _ in futs if k == "query")),
        "ingests": int(sum(1 for _, k, _, _ in futs if k == "ingest")),
        "placement": placement,
        "victim": victim,
        "victim_tenants": victim_tenants,
        # --- the CI contract (top-level, non-null) ---
        "recovery_ms": recovery_ms,
        "dropped_futures": dropped,
        "lost_acked_ingests": len(lost),
        "p99_pre_ms": p99_pre,
        "p99_post_ms": p99_post,
        # --- durability / restart detail ---
        "durability_dir": durability_dir,
        "restart": {
            "outcomes": restart.get("outcomes", {}),
            "restore_ms": restart.get("restore_ms"),
            "replace_ms": restart.get("replace_ms"),
            "replay_ms": restart.get("replay_ms"),
            "total_ms": restart.get("total_ms"),
            "replayed_entries": restart.get("replayed"),
            "wall_ms": restart_result.get("wall_ms"),
        },
        "placement_restored": placement_after == placement,
        "lost_tenants": lost,
        # --- failover detail ---
        "hung_futures": hung,
        "unnamed_errors": unnamed,
        "host_failed_resolutions": host_failed,
        "transparent_retries": retried,
        "migration_ms": failover.get("recovery_ms"),
        "failover_outcomes": failover.get("outcomes", {}),
        "failover_log": stats["failovers"],
        "restart_log": stats["restarts"],
        "retried_queries": stats["retried_queries"],
        "promoted_ingests": stats["promoted_ingests"],
        "replayed_ingests": stats["replayed_ingests"],
        "p50_pre_ms": float(np.percentile(pre, 50)) if len(pre) else None,
        "p50_post_ms": float(np.percentile(post, 50)) if len(post) else None,
        "pre_samples": int(len(pre)),
        "post_samples": int(len(post)),
    }

    print(f"{'':>26} {'pre-failover':>14} {'post-restart':>14}")
    print(f"{'query p50 (ms)':>26} {payload['p50_pre_ms']:>14.2f} "
          f"{payload['p50_post_ms']:>14.2f}")
    print(f"{'query p99 (ms)':>26} {p99_pre:>14.2f} {p99_post:>14.2f}")
    print(f"\nkilled {victim} (primary of {len(victim_tenants)} tenants) "
          f"mid-replay: failed over in {recovery_ms:.1f} ms "
          f"(migration {failover.get('recovery_ms', 0):.1f} ms), "
          f"{stats['retried_queries']} queries retried transparently, "
          f"{host_failed} ingests resolved HostFailed, "
          f"{stats['replayed_ingests']} replayed from the WAL, "
          f"{dropped} dropped futures")
    print(f"restarted {victim} mid-load: restore "
          f"{restart.get('restore_ms', 0):.1f} ms + re-place "
          f"{restart.get('replace_ms', 0):.1f} ms + WAL replay "
          f"{restart.get('replay_ms', 0):.1f} ms "
          f"({restart.get('replayed', 0)} entries); placement restored: "
          f"{payload['placement_restored']}; lost acked ingests: "
          f"{len(lost)}")

    # --- acceptance bars (also asserted from CI on the JSON) ---
    assert dropped == 0, (
        f"{hung} hung + {unnamed} unnamed-error futures: the failover "
        f"path dropped requests")
    assert recovery_ms is not None and recovery_ms > 0
    assert not lost, (
        f"tenants {lost} diverged from the never-crashed oracle: acked "
        f"ingests were lost across the kill/restart")
    assert payload["placement_restored"], (
        "restart did not return placement to the pre-kill assignment")
    # equal offered load before/after: the tail may grow briefly but the
    # recovered steady state stays within 3x + a small CPU-noise floor
    assert p99_pre is not None and p99_post is not None
    assert p99_post <= 3.0 * p99_pre + 5.0, (
        f"post-restart p99 {p99_post:.2f}ms vs pre {p99_pre:.2f}ms: "
        f"exceeds the 3x bar")
    return payload


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--queries", type=int, default=400)
    ap.add_argument("--qps", type=float, default=150.0)
    ap.add_argument("--ingest-every", type=int, default=50,
                    help="one ingest burst per this many queries")
    ap.add_argument("--ingest-burst", type=int, default=3,
                    help="tenant refresh ingests per burst")
    ap.add_argument("--deadline-ms", type=float, default=5.0)
    ap.add_argument("--durability-dir", default=None,
                    help="durable store root (default: fresh temp dir)")
    ap.add_argument("--json-out", default="BENCH_serve_cluster.json")
    args = ap.parse_args(argv)
    payload = bench(args.queries, args.qps, args.ingest_every,
                    args.ingest_burst, args.deadline_ms,
                    durability_dir=args.durability_dir)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json_out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
