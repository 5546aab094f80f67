"""Machine-readable registry of the concurrency/bit-identity invariants.

This module is the single source of truth that both the static pass
(`repro.analysis.locklint`) and the runtime sanitizer
(`repro.analysis.lockdep`) consume.  The prose versions that used to
live only in the `core/engine.py` and `runtime/cluster.py` docstrings
are anchored here; the docstrings now point at this file.

Everything here is plain data (tuples/dicts/frozensets) so the
analysis package imports nothing heavier than the stdlib.

Lock classes and ranks
----------------------
A lock may only be acquired while holding locks of *strictly lower*
rank (except a reentrant re-acquire of the same class).  The ranks
encode the documented order:

    cluster(10) -> engine(20) -> future(30) -> store(40)
        -> plan-cache(50) -> ingest-cache(60) -> shared-pool(61)
        -> ingest-feed(63)

i.e. the cluster lock is the outermost lock in the system and the
module-leaf cache locks are leaves: nothing else may be acquired
while one of them is held.

Rule identifiers
----------------
``lock-order``            nested ``with`` acquiring a lock of rank <=
                          a held lock's rank (wrong direction).
``lock-order-call``       call whose (transitive or registered
                          external) summary acquires a lock of rank <=
                          a held lock's rank.
``block-under-lock``      blocking primitive (``block_until_ready``,
                          ``Future.result``, ``join``, ``sleep``,
                          fsync-backed store IO, synchronous engine
                          control-plane methods, ...) executed while
                          any instrumented lock is held.
``dispatch-under-lock``   device dispatch (donating ingest
                          executable, batched eval, jit call) while
                          any instrumented lock is held.
``wait-wrong-lock``       ``Condition.wait``/``wait_for`` without
                          holding the condition's owning lock.
``notify-outside-lock``   ``Condition.notify``/``notify_all`` without
                          holding the owning lock.
``blocking-submit-under-lock``  ``submit_ingest``/``submit_query``/
                          ``submit_probe`` under the cluster lock
                          without an explicit ``block=False``.
``donate-reuse``          a donating dispatch that can run more than
                          once for the same payload (retry wrapper or
                          loop whose payload does not derive from the
                          loop variable) without a preceding
                          donation guard (``_check_not_donated`` /
                          ``is_deleted``).
``bit-identity-reassoc``  reassociating reduction (``jnp.sum``,
                          ``lax.psum``, ``segment_sum``, ...) inside a
                          function on the left-fold scatter path,
                          which must stay bit-identical across
                          sharded/unsharded runs.

Pragmas
-------
``# ctlint: ok(rule[,rule2...])[: justification]`` on the offending
line (or the line directly above it) suppresses the named rules at
that site.  ``# ctlint: holds(lockname)`` on a ``def`` line declares
that the function is only ever called with that lock already held
(the `_locked` helper convention), so the intra-procedural pass
starts with it in the held set.
"""

from __future__ import annotations

# --------------------------------------------------------------------
# Lock classes.
# --------------------------------------------------------------------

#: lock class -> rank.  Acquire order must be strictly increasing.
LOCK_RANKS = {
    "cluster": 10,       # runtime/cluster.py CTCluster._lock (RLock)
    "engine": 20,        # core/engine.py CTEngine._lock/_work/_space
    "future": 30,        # runtime/cluster.py ClusterFuture._flock
    "store": 40,         # runtime/durability.py DurableStore._lock
    "plan-cache": 50,    # core/executor.py _PlanCache._lock
    "ingest-cache": 60,  # core/engine.py _INGEST_CACHE_LOCK
    "shared-pool": 61,   # core/engine.py _SHARED_POOL_LOCK
    "ingest-feed": 63,   # core/engine.py CTEngine._feed_lock
}

#: lock classes backed by an RLock (same-class re-acquire is legal).
REENTRANT_LOCKS = frozenset({"cluster", "engine", "store"})

#: Classification of source expressions to lock classes, per file.
#: Entries are (path_suffix, expr_suffix, lock_class, is_condition).
#: An expression matches when the file path ends with ``path_suffix``
#: and the unparsed ``with``-item expression equals or ends with
#: ``expr_suffix``.  Order matters: first match wins (so the engine
#: conditions are listed before the generic ``._lock``).
LOCK_PATTERNS = (
    ("core/engine.py", "._work", "engine", True),
    ("core/engine.py", "._space", "engine", True),
    ("core/engine.py", "._feed_lock", "ingest-feed", False),
    ("core/engine.py", "._lock", "engine", False),
    ("core/engine.py", "_INGEST_CACHE_LOCK", "ingest-cache", False),
    ("core/engine.py", "_SHARED_POOL_LOCK", "shared-pool", False),
    ("core/executor.py", "._lock", "plan-cache", False),
    ("runtime/cluster.py", "._flock", "future", False),
    ("runtime/cluster.py", "._lock", "cluster", False),
    ("runtime/durability.py", "._lock", "store", False),
)


def classify_lock(path: str, expr: str):
    """Map an unparsed ``with``-item expression to a lock class.

    Returns ``(lock_class, is_condition)`` or ``None`` when the
    expression is not a known lock.  ``path`` uses forward slashes.
    """
    for suffix, tail, name, is_cond in LOCK_PATTERNS:
        if path.endswith(suffix) and (expr == tail or expr.endswith(tail)):
            return name, is_cond
    return None


# --------------------------------------------------------------------
# External call summaries.
# --------------------------------------------------------------------
# The static pass is intra-module; cross-module effects are declared
# here.  A call is matched by (receiver suffix, method name): the
# unparsed receiver expression must end with the suffix.

#: CTEngine public/entry methods that take the engine lock.  Matched
#: on receivers ending in "engine" (``host.engine.X``, ``engine.X``,
#: ``self._engine.X``).
ENGINE_LOCKING_METHODS = frozenset({
    "submit_ingest", "submit_query", "submit_probe",
    "register", "unregister", "refit", "extend", "drop_grid",
    "rebind", "update", "query", "flush", "pump", "start", "stop",
    "close", "heartbeat", "stats", "surplus", "restore", "replay",
    "snapshot_tenant",
})

#: CTEngine methods that can block (drain queues, run device work,
#: join worker threads, or do disk IO) in addition to locking.
ENGINE_BLOCKING_METHODS = frozenset({
    "register",        # synchronous initial ingest when grids given
    "refit", "extend", "drop_grid", "rebind",   # drain + re-dispatch
    "update", "query", "surplus",               # synchronous device work
    "flush", "stop", "close",                   # drain / join workers
    "restore", "replay",                        # WAL read + re-dispatch
    "snapshot_tenant", "unregister",            # device->host copy / IO
})

#: DurableStore methods (receivers ending in "store" / "_store").
STORE_LOCKING_METHODS = frozenset({
    "register", "discard", "append", "flush", "snapshot", "load",
    "pending_after", "tenants", "stats", "close",
})

#: DurableStore methods that hit the disk (fsync / rmtree / read).
STORE_BLOCKING_METHODS = frozenset({
    "append", "flush", "snapshot", "load", "pending_after",
    "discard", "close",
})

#: ClusterFuture leaf-lock helpers callable on any receiver.
FUTURE_LOCKING_METHODS = frozenset({
    "_finalize_locked", "_retarget_locked",
})


def external_call_effects(receiver: str, method: str):
    """Summarize a cross-object call ``receiver.method(...)``.

    Returns ``(acquires, blocks)`` where ``acquires`` is a lock class
    or ``None`` and ``blocks`` is a bool.  Matching is by receiver
    suffix so ``host.engine``, ``self._engine`` and a bare ``engine``
    local all resolve the same way.
    """
    if method in FUTURE_LOCKING_METHODS:
        return "future", False
    if receiver.endswith("engine") and method in ENGINE_LOCKING_METHODS:
        return "engine", method in ENGINE_BLOCKING_METHODS
    if receiver.endswith("store") and method in STORE_LOCKING_METHODS:
        return "store", method in STORE_BLOCKING_METHODS
    return None, False


# --------------------------------------------------------------------
# Blocking / dispatch primitives (direct calls).
# --------------------------------------------------------------------

#: Attribute or function names that block the calling thread.
BLOCKING_CALL_NAMES = frozenset({
    "block_until_ready",   # jax device sync
    "result",              # concurrent.futures / ClusterFuture
    "join",                # thread join
    "sleep",               # time.sleep
    "shutdown",            # executor shutdown(wait=True)
})

#: Attribute/function names that launch device work.  ``locklint``
#: flags these under ANY held lock; ``lockdep.note_dispatch`` is the
#: runtime twin.
DISPATCH_CALL_NAMES = frozenset({
    "_dispatch_ingest",        # donating ingest executable (engine)
    "_dispatch_query_groups",  # batched eval + block_until_ready
    "_EVAL_BATCHED",           # jit'd eval of one surplus (ct.query.eval)
    "hierarchize_batched",
    "interpolate_hierarchical",
})

#: Cluster submit entry points that must pass block=False when
#: invoked under the cluster lock (rule blocking-submit-under-lock).
CLUSTER_SUBMIT_METHODS = frozenset({
    "submit_ingest", "submit_query", "submit_probe",
})

# --------------------------------------------------------------------
# Donation safety (PR 8).
# --------------------------------------------------------------------

#: Calls that hand buffers to a donate_argnums executable.  The
#: donated payload is the *second* positional argument
#: (``self._dispatch_ingest(tenant, nodal_grids)``).
DONATING_CALLS = frozenset({"_dispatch_ingest"})

#: Index of the donated-payload argument in a donating call.
DONATED_ARG_INDEX = 1

#: Guard calls that make a repeated donating dispatch safe.
DONATION_GUARDS = frozenset({"_check_not_donated", "is_deleted"})

# --------------------------------------------------------------------
# Bit-identity (left-fold scatter order, PR 3/4/8).
# --------------------------------------------------------------------

#: Function-name prefixes on the bit-identical scatter path: every
#: gather a plan runs through.
BIT_CRITICAL_FUNC_PREFIXES = (
    "gather_slab_scatter",   # core/distributed.py slab scatter family
    "_finish_slab_gather",
    "_gather_one_bucket",
    "_gather_compact",       # core/executor.py one-device compact gather
    "_scatter_surplus",
)

#: Reassociating reductions forbidden inside bit-critical functions.
FORBIDDEN_REASSOC_NAMES = frozenset({
    "sum", "nansum", "psum", "segment_sum", "cumsum", "einsum",
    "logsumexp", "mean",
})

# --------------------------------------------------------------------
# Invariant catalogue (rule -> provenance).  Rendered in reports and
# in analysis/README.md; keep in sync with the rule implementations.
# --------------------------------------------------------------------

INVARIANTS = {
    "lock-order": (
        "Locks are acquired in strictly increasing rank order: "
        "cluster -> engine -> future -> store -> plan-cache -> "
        "ingest-cache/shared-pool/ingest-feed.  Module-leaf cache locks "
        "are leaves; nothing may be acquired while one is held. "
        "(PR 6 engine lock redesign; PR 7 cluster->engine order.)"
    ),
    "lock-order-call": (
        "A call made under a lock must not (transitively) acquire a "
        "lock of lower or equal rank.  (PR 7: cluster methods call "
        "into engines, never the reverse while locked.)"
    ),
    "block-under-lock": (
        "No blocking primitive under an instrumented lock: "
        "block_until_ready, Future.result, Thread.join, time.sleep, "
        "synchronous engine control-plane calls, fsync-backed store "
        "IO.  Exception (pragma'd): WAL append at admission runs "
        "under the engine lock so journal order equals admission "
        "order (PR 9)."
    ),
    "dispatch-under-lock": (
        "Device dispatch never runs under any lock; workers drop the "
        "engine lock before _dispatch_ingest/_EVAL_BATCHED (the "
        "one-surplus eval each ct.query.eval span launches) and "
        "reacquire it only to commit (PR 6)."
    ),
    "wait-wrong-lock": (
        "Condition.wait/wait_for only with the owning lock held "
        "(the _work/_space conditions share the engine RLock; helpers "
        "called with it held carry a '# ctlint: holds(engine)' "
        "annotation).  (PR 6.)"
    ),
    "notify-outside-lock": (
        "Condition.notify/notify_all only with the owning lock held; "
        "an unlocked notify races the waiter's predicate check. "
        "(PR 6.)"
    ),
    "blocking-submit-under-lock": (
        "Every engine submit made while holding the cluster lock "
        "passes block=False; a full engine queue must surface as "
        "EngineSaturated to the failover path, not wedge the cluster "
        "(PR 7)."
    ),
    "donate-reuse": (
        "A buffer handed to the donate_argnums ingest executable is "
        "dead after dispatch; any path that can dispatch the same "
        "payload twice (retry wrapper, replay loop with a hoisted "
        "payload) must guard with _check_not_donated/is_deleted "
        "first (PR 8 IngestBuffersDonated)."
    ),
    "bit-identity-reassoc": (
        "Surplus scatter is a left fold; reassociating reductions "
        "(jnp.sum, lax.psum, segment_sum, ...) are forbidden on the "
        "scatter path so sharded and single-device runs stay "
        "bit-identical (PR 3/4/8)."
    ),
}

#: Rank lookup helper used by lockdep at acquire time.
def rank_of(lock_class):
    return LOCK_RANKS.get(lock_class)
