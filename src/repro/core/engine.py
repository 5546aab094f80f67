"""Unified CT execution front door: ``ExecSpec`` + multi-tenant ``CTEngine``.

Two objects:

* ``ExecSpec`` — ONE frozen, hashable dataclass carrying every execution
  policy (bucket merging, mesh/slab sharding, interpret mode, dtype).
  Every entry point (``build_plan``, ``extend_plan``, ``shard_plan``,
  ``ct_transform*``, ``ct_scatter``, ``ct_transform_sharded``,
  ``recombine_after_fault``, ``AdaptiveDriver``, ``make_ct_step``,
  ``CTSurrogate``) takes it as ``spec=``; there is no second spelling.
* ``CTEngine`` — a THREAD-SAFE multi-tenant registry serving N named
  surrogates (scheme + plan + spec each) behind a deadline-aware
  continuous-batching queue, with jitted ingest executables DEDUPED
  across tenants by plan shape-signature.

ExecSpec precedence rules
-------------------------

1. **Conflicts raise.**  Fields that can disagree (``n_slabs`` against
   the mesh axis extent, ``member_axis`` against ``axis_name``) raise
   ``ValueError`` at construction instead of guessing which one the
   caller meant.
2. **A spec is an ExecSpec.**  ``spec=`` given anything else raises a
   named ``TypeError``.  ``spec=None`` means ``ExecSpec()`` at a free
   function and the engine's own spec at ``CTEngine.register``.
3. **Field-level defaults resolve as late as possible.**
   ``n_slabs=None`` means "the mesh axis extent" (``spec.slabs``);
   ``interpret=None`` means "ask ``repro.kernels.hierarchize.
   interpret_default`` at execution time" (never frozen into the spec);
   ``dtype=None`` means "promote the input dtypes".
4. **A meshed spec routes multi-device.**  ``mesh=`` makes the front
   doors (``ct_transform``, ``CTEngine``, ``CTSurrogate``) run the
   slab-sharded gather over ``mesh.shape[axis_name]`` device groups;
   everything else (merge, interpret) composes orthogonally.

CTEngine threading contract
---------------------------

``submit_ingest`` / ``submit_query`` may be called from ANY thread; they
enqueue work and return ``CTFuture``s backed by ``threading.Event``
(``result(timeout=)`` blocks, auto-flushing the queue while it waits).
The queue drains through three equivalent paths:

* ``flush()`` — drain EVERYTHING now (synchronous; safe to call
  concurrently — the pending-queue swap is atomic under the engine
  lock, so requests enqueued during a concurrent flush are never
  dropped, they simply ride the next drain);
* ``pump()`` — one scheduler step: dispatch only what is DUE
  (deadline expired or per-tenant batch full);
* ``start()`` / ``stop()`` — a background scheduler thread calling the
  pump loop, waking on submissions and deadline expiry.

**Ingest pool.**  Pending ingests are dispatched on a background thread
pool (shared across engines by default; ``ingest_workers=N`` gives an
engine a private pool, ``ingest_workers=0`` forces inline execution).
Each tenant's ingests form an ordered chain; chains of different
tenants overlap each other AND the query batching on the main thread —
jax dispatch releases the GIL inside XLA, so host-side plan work and
device compute pipeline.  ``jax.block_until_ready`` runs inside the
chain worker: a device-side failure resolves the OWNING request's
future and never poisons siblings or escapes ``flush()``.

**Ordering.**  Per tenant, queries observe every ingest of the same
tenant submitted before them (a monotonic per-tenant watermark pairs
each query with the ingest generation it must wait for); ingests of one
tenant apply in submission order.  Across tenants there is no implied
order — that is what makes the coalesced batching legal.

**Deadlines / priority / backpressure.**  Each query carries an
absolute deadline (explicit ``deadline_ms=``, else the tenant default,
else the engine default) and an integer priority (higher first).  The
scheduler dispatches a tenant's queries when its batch reaches
``max_batch`` OR the earliest deadline in the group expires —
flush-on-deadline-or-batch-full, not flush-everything.  The queue is
bounded by ``max_pending``: ``submit_*(block=False)`` raises
``EngineSaturated`` when full, blocking submits wait for space (with
optional ``timeout=``).

**Lock order.**  One engine lock (an ``RLock`` shared by the ``_work``
and ``_space`` conditions) guards the registry, the queue, the
watermarks and the counters.  The full rank order, the lock-class
registry and every enforced rule live in
``repro.analysis.invariants`` (checked statically by
``python -m repro.analysis`` and at runtime under ``REPRO_LOCKDEP=1``);
the short version: engine(20) sits between cluster(10) and the
module-level cache locks (ingest-executable cache, ``build_plan``
cache), which are LEAVES — never held while
taking an engine lock — and no device dispatch ever runs under ANY
lock.

CTEngine serving model
----------------------

``register(name, scheme, grids, spec=...)`` admits a tenant; ingest
executables are cached in a process-global table keyed by the plan's
SHAPE SIGNATURE (canonical bucket levels + axis permutations + fine
grid + the execution-relevant spec fields).  The per-tenant embed index
maps and combination coefficients are passed to the jitted executable as
ARGUMENTS rather than baked in as constants, so two schemes with equal
bucket signatures — same canonical grid shapes, different coefficients
or different data — compile ONCE and the results stay bit-identical to
the constants-baked ``ct_transform`` (both spellings trace the same
ops; pinned by ``tests/test_engine.py``).

Queries coalesce BY SIGNATURE (surplus shape/dtype + padded point
extent) into chunks of up to ``max_batch``; a chunk runs one eval per
distinct surplus in it, over the points of every row that reads it, and
never copies a surplus.  Per-request results match a per-tenant
dispatch because each query point's hat-basis contraction is
independent of the batching.  ``refit``
/ ``extend`` / ``drop_grid`` route through the incremental plan paths
(``extend_plan`` / ``recombine_after_fault``) per tenant; ``rebind``
re-shards a tenant onto a new mesh/slab layout WITHOUT recomputing its
surplus (the elastic-rebalance fast lane); ``stats()`` reports the
compile-cache, eval and scheduler counters.

**Host spans.**  The scheduler pass and sleep, each ingest and each
eval batch, and their phases open named ``jax.profiler`` spans (the
``SPAN_*`` constants): under a profiler session they land on the
device trace's clock, so a device-idle gap can be put down to the
engine step that was running; with no session they record nothing.

``repro.launch.serve.CTSurrogate`` is a thin single-tenant view over a
private engine.

One engine is one HOST
----------------------

``repro.runtime.cluster.CTCluster`` serves N engines as a multi-host
front end: consistent-hash tenant placement routes every ``register`` /
``submit_*`` to an owner engine, a health monitor watches each engine's
pump liveness and probe latency, and failover migrates a dead host's
tenants to survivors.  The engine-side plumbing the cluster relies on:

* ``host_id=`` names the engine in errors and ``stats()`` (so
  ``EngineSaturated`` / ``KeyError`` messages in cluster logs say WHICH
  host rejected the work);
* ``heartbeat()`` is the pump-liveness signal: the monotonic timestamp
  of the last scheduler pass (``pump`` / ``flush`` / the scheduler
  loop), plus queue depth and whether the scheduler thread is alive — a
  stalled dispatch shows up as a growing ``age_s`` with an alive
  thread, a dead one as a dead thread;
* ``submit_probe()`` round-trips a no-op request through the full
  queue/scheduler path; the cluster waits on it with
  ``CTFuture.wait()`` (which, unlike ``result()``, NEVER drives the
  engine from the waiting thread — a probe that only resolves because
  the prober flushed proves nothing about the host's own liveness);
* ``register(..., plan=, surplus=)`` is the failover fast lane: adopt a
  tenant from a retained plan and an already-computed surplus without
  re-ingesting — combined with the process-global executable cache, a
  signature-preserving migration recompiles NOTHING.

Ownership across hosts is the CLUSTER's job: an engine never calls into
the cluster (lock order is strictly cluster -> engine), and a tenant
name is only ever served by the engines the cluster placed it on.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.analysis import lockdep as _lockdep

from repro.core.executor import (ExecutorPlan, MergeConfig, ShardedPlan,
                                 _assemble_members, _check_nodal_grids,
                                 _gather_compact, build_plan, extend_plan,
                                 shard_plan)
from repro.core.interpolation import interpolate_hierarchical
from repro.core.levels import SchemeLike, grid_shape
from repro.kernels.hierarchize import hierarchize_batched, interpret_default
from repro.runtime.durability import DurableStore, RetryPolicy

__all__ = ["ExecSpec", "CTEngine", "CTFuture", "EngineSaturated",
           "IngestBuffersDonated", "RestoreInfo", "clear_compile_cache"]


class EngineSaturated(RuntimeError):
    """The engine's bounded request queue is full (admission control)."""


class _RebindRace(RuntimeError):
    """Internal: an ingest commit lost the CAS against a concurrent
    refit/rebind record swap — retried under the engine's RetryPolicy."""


@dataclass(frozen=True)
class RestoreInfo:
    """What ``CTEngine.restore`` recovered for one tenant."""

    name: str
    snapshot_seq: int           # watermark of the adopted snapshot (0 none)
    base_seq: int               # highest journaled seq (snapshot + WAL)
    tag: int                    # newest caller ordering tag recovered; -1
    snapshot_tag: int           # caller tag of the adopted snapshot; -1
    pending: int                # WAL entries newer than the snapshot
    replayed: int               # entries already applied (replay=True)
    restore_s: float
    replay_s: float
    events: Tuple[str, ...]     # tolerated anomalies (torn tails, ...)


class IngestBuffersDonated(RuntimeError):
    """An ingest under ``ExecSpec(donate=True)`` failed (or lost a rebind
    race) AFTER its input buffers were donated to the executable: the
    device buffers are deleted, so the ingest can neither be retried
    in-place nor resubmitted elsewhere.  The owning future resolves with
    this error instead of redispatching dead buffers — resubmit from
    host copies (``np.asarray`` snapshots, as ``CTCluster`` takes at
    admission) to recover."""


@dataclass(frozen=True)
class ExecSpec:
    """One frozen config for the whole CT execution stack.

    Hashable (meshes hash by device assignment, ``MergeConfig`` is a
    frozen dataclass, ``dtype`` is canonicalized to its name), so a spec
    can sit in plan caches and executable-cache keys.  See the module
    docstring for the precedence rules.
    """

    #: bucket-merging cost model (``None`` = one bucket per canonical
    #: shape) — part of the PLAN, so two specs differing only here
    #: produce different plans, not different executables
    merge: Optional[MergeConfig] = None
    #: jax device mesh for the slab-sharded multi-device gather
    mesh: Optional[Any] = None
    #: mesh axis the fine grid's leading axis is slab-sharded over
    axis_name: str = "slab"
    #: slab count override; ``None`` = ``mesh.shape[axis_name]`` (1 off-mesh)
    n_slabs: Optional[int] = None
    #: Pallas interpret mode: ``None`` = backend default at execution time
    interpret: Optional[bool] = None
    #: accumulation dtype of engine ingest (name, e.g. ``"float64"``);
    #: ``None`` = promote the input grid dtypes
    dtype: Optional[str] = None
    #: zero-copy ingest hand-off: donate the staged nodal-grid buffers
    #: into the jitted ingest (``donate_argnums``, like
    #: ``launch/train.py`` donates the train state) so XLA may reuse
    #: their memory for the transform's intermediates instead of
    #: holding inputs + intermediates live together.  OPT-IN: with
    #: ``donate=True`` a caller that passes device arrays relinquishes
    #: them (numpy inputs are staged to fresh buffers per call and are
    #: always safe); backends that cannot use a donation silently keep
    #: the copying behavior (jax warns once at compile time).
    donate: bool = False
    #: SECOND mesh axis of the 2-D (member x slab) ingest: when set (and
    #: the mesh carries it), the hierarchization itself is compute-
    #: sharded over ``members * slabs`` groups and ingest routes through
    #: ``repro.core.distributed.gather_slab_scatter_2d`` (bit-identical).
    #: ``None`` = classic slab-only sharding
    #: with replicated compute.  Inert without a mesh (so
    #: ``dataclasses.replace(spec, mesh=None)`` de-meshings stay valid).
    member_axis: Optional[str] = None

    def __post_init__(self):
        if self.dtype is not None:
            object.__setattr__(self, "dtype", jnp.dtype(self.dtype).name)
        if self.n_slabs is not None and self.n_slabs < 1:
            raise ValueError(f"n_slabs must be >= 1, got {self.n_slabs}")
        if self.mesh is not None:
            if self.axis_name not in self.mesh.shape:
                raise ValueError(
                    f"axis_name {self.axis_name!r} is not an axis of the "
                    f"mesh (axes: {tuple(self.mesh.shape)})")
            extent = int(self.mesh.shape[self.axis_name])
            if self.n_slabs is not None and self.n_slabs != extent:
                raise ValueError(
                    f"n_slabs={self.n_slabs} conflicts with mesh axis "
                    f"{self.axis_name!r} of {extent} device(s); set ONE of "
                    f"them (precedence rule 1: conflicts raise)")
            if self.member_axis is not None:
                if self.member_axis == self.axis_name:
                    raise ValueError(
                        f"member_axis and axis_name must differ, both "
                        f"{self.axis_name!r}")
                if self.member_axis not in self.mesh.shape:
                    raise ValueError(
                        f"member_axis {self.member_axis!r} is not an axis "
                        f"of the mesh (axes: {tuple(self.mesh.shape)})")

    @property
    def slabs(self) -> int:
        """Effective slab count: explicit ``n_slabs``, else the mesh axis
        extent, else 1 (unsharded)."""
        if self.n_slabs is not None:
            return self.n_slabs
        if self.mesh is not None:
            return int(self.mesh.shape[self.axis_name])
        return 1

    @property
    def members(self) -> int:
        """Member-axis extent of the 2-D mesh (1 when not member-meshed)."""
        if self.member_axis is not None and self.mesh is not None \
                and self.member_axis in self.mesh.shape:
            return int(self.mesh.shape[self.member_axis])
        return 1

    @property
    def groups(self) -> int:
        """Compute-shard group count of the 2-D ingest:
        ``members * slabs`` when a member axis is meshed, else 1
        (hierarchization replicated)."""
        if self.member_axis is not None and self.mesh is not None \
                and self.member_axis in self.mesh.shape:
            return self.members * self.slabs
        return 1

    def resolve_interpret(self) -> bool:
        """The concrete interpret flag this spec means RIGHT NOW (the
        shared backend-default helper; late so the spec stays portable)."""
        if self.interpret is not None:
            return self.interpret
        return interpret_default()

    def result_dtype(self, *input_dtypes):
        """Accumulation dtype under this spec's dtype policy."""
        if self.dtype is not None:
            return jnp.dtype(self.dtype)
        return jnp.result_type(*input_dtypes)

    def plan(self, scheme: SchemeLike, full_levels=None):
        """Build the (possibly slab-sharded, possibly merged) executor
        plan this spec prescribes for ``scheme``."""
        return build_plan(scheme, full_levels, spec=self)


# ---------------------------------------------------------------------------
# Signature-shared ingest executables
# ---------------------------------------------------------------------------

def plan_signature(plan, spec: ExecSpec) -> Tuple:
    """Hashable shape signature of (plan, spec): everything the jitted
    ingest executable's TRACE depends on — canonical bucket member levels
    and axis permutations (these determine every array shape, operator
    and index-map layout), the fine grid, the slab split, and the
    execution-relevant spec fields.  NOT included: the member level
    vectors' original order (``ells``), coefficients and index-map
    VALUES — those are runtime arguments, which is exactly what lets
    same-signature tenants share one compilation."""
    sharded = isinstance(plan, ShardedPlan)
    base = plan.plan if sharded else plan
    buckets = tuple((b.levels, b.perms) for b in base.buckets)
    shard = (plan.n_slabs, plan.n_groups) if sharded else None
    return (base.full_levels, buckets, shard,
            spec.interpret, spec.dtype, spec.donate,
            spec.mesh if sharded else None,
            spec.axis_name if sharded else None,
            spec.member_axis if sharded else None)


#: Process-global executable cache: signature -> jitted ingest fn.  Shared
#: across every CTEngine (and so across every CTSurrogate) in the process.
#: LRU-bounded like ``build_plan``'s plan cache: each entry retains its
#: jit cache AND (sharded signatures) the representative plan's slab
#: metadata in the closure, so retired signatures — a long refit/extend
#: trajectory produces one per scheme shape — must not accumulate
#: unboundedly.  Live tenants keep their executable reachable through
#: ``_Tenant.executable`` even after eviction; eviction only forces a
#: recompile for the NEXT tenant of that signature.
#:
#: Every get/insert/evict runs under ``_INGEST_CACHE_LOCK`` — building
#: the executable inside the lock is fine because ``jax.jit`` is lazy
#: (tracing/compilation happen at FIRST CALL, outside any lock).  The
#: lock is a LEAF: never held while taking an engine lock.
_INGEST_EXECUTABLES: "collections.OrderedDict[Tuple, _IngestExecutable]" = \
    collections.OrderedDict()
_INGEST_CACHE_MAX = 64
_INGEST_CACHE_LOCK = _lockdep.make_lock("ingest-cache")


def clear_compile_cache() -> None:
    """Drop the shared ingest-executable cache (tests / benchmarks)."""
    with _INGEST_CACHE_LOCK:
        _INGEST_EXECUTABLES.clear()


def _ingest_body(plan, spec: ExecSpec) -> Callable:
    """Un-jitted ``(grid_parts, idxs, coeffs) -> surplus`` for one plan
    signature.  ``plan`` is a REPRESENTATIVE realization of the
    signature: only signature-determined structure (bucket levels/perms/
    shapes, fine grid, slab metadata) is closed over; index maps and
    coefficients arrive as traced arguments.  On one device ``idxs`` is
    ``(compact maps, fine map)`` and the body is the compact gather
    (``ExecutorPlan.compact``)."""
    sharded = isinstance(plan, ShardedPlan)
    base = plan.plan if sharded else plan
    metas = [(b.levels, b.perms, b.shape) for b in base.buckets]
    fine_shape = base.fine_shape
    interpret, dtype_policy = spec.interpret, spec.dtype

    def _acc_dtype(parts):
        if dtype_policy is not None:
            return jnp.dtype(dtype_policy)
        return jnp.result_type(*(p.dtype for p in parts))

    def _assembled(parts):
        off, xs = 0, []
        for levels, perms, shape in metas:
            xs.append(_assemble_members(parts[off:off + len(levels)],
                                        perms, shape))
            off += len(levels)
        return xs

    if not sharded:
        def ingest(parts, idxs, coeffs):
            dtype = _acc_dtype(parts)
            maps, fine_map = idxs
            return _gather_compact(
                _assembled(parts), [levels for levels, _, _ in metas], maps,
                [cs.astype(dtype) for cs in coeffs], fine_map, fine_shape,
                dtype, interpret=interpret)

        return ingest

    if spec.mesh is None:
        raise ValueError(
            "a slab-sharded plan needs a meshed spec (ExecSpec(mesh=...)) "
            "to execute; n_slabs alone only shapes the plan")
    mesh, axis_name = spec.mesh, spec.axis_name
    splan = plan

    if spec.member_axis is not None and splan.n_groups > 1:
        member_axis = spec.member_axis

        def ingest_2d(parts, idxs, coeffs):
            # 2-D (member x slab) compute-sharded ingest: assembly only
            # here; hierarchization runs per member group INSIDE the
            # gather's shard_map.  ``idxs`` carries per-bucket
            # (ship_src, ship_idx) pairs (see _tenant_arrays).
            from repro.core.distributed import gather_slab_scatter_2d
            dtype = _acc_dtype(parts)
            stacks = [x.reshape(x.shape[0], -1) for x in _assembled(parts)]
            cs = [c.astype(dtype) for c in coeffs]
            return gather_slab_scatter_2d(
                stacks, splan, mesh, member_axis, axis_name,
                interpret=interpret, idx_arrays=idxs, coeff_arrays=cs,
                dtype=dtype)

        return ingest_2d

    def ingest_sharded(parts, idxs, coeffs):
        from repro.core.distributed import gather_slab_scatter
        dtype = _acc_dtype(parts)
        xs = _assembled(parts)
        cs = [c.astype(dtype) for c in coeffs]
        alphas = [hierarchize_batched(x, levels, interpret=interpret)
                  .reshape(len(levels), -1)
                  for x, (levels, _, _) in zip(xs, metas)]
        return gather_slab_scatter(alphas, splan, mesh, axis_name,
                                   idx_arrays=idxs, coeff_arrays=cs)

    return ingest_sharded


def _part_shapes(plan) -> Tuple[Tuple[int, ...], ...]:
    """Each grid part's shape, in the executable's part order.  A
    member's shape follows from its canonical levels and perm, so every
    plan of one signature gives the same shapes."""
    base = plan.plan if isinstance(plan, ShardedPlan) else plan
    return tuple(grid_shape(ell) for b in base.buckets for ell in b.ells)


def _build_ingest_executable(plan, spec: ExecSpec, *,
                             packed: bool = False) -> Callable:
    """Jitted ``(grid_parts, idxs, coeffs) -> surplus`` for one plan
    signature (see ``_ingest_body``).  ``packed=True`` takes ``(flat,
    idxs, coeffs)`` instead: every part raveled, in part order, into one
    1-D buffer, cut back into the parts at static offsets before the
    same body."""
    body = _ingest_body(plan, spec)
    if packed:
        shapes, unpacked = _part_shapes(plan), body

        def body(flat, idxs, coeffs):
            parts, off = [], 0
            for shape in shapes:
                n = math.prod(shape)
                parts.append(flat[off:off + n].reshape(shape))
                off += n
            return unpacked(tuple(parts), idxs, coeffs)

        # the trace names the module ``jit_<name>``: keep ``jit_ingest*``
        body.__name__ = f"{unpacked.__name__}_packed"
    # zero-copy hand-off: the staged grid parts or the packed buffer
    # (argument 0) are donated so the backend may retire them into the
    # transform's intermediates; index maps / coefficients are NOT
    # donated — they are the tenant's long-lived runtime identity,
    # reused every ingest
    return jax.jit(body, donate_argnums=(0,) if spec.donate else ())


class _IngestExecutable:
    """One plan signature's ingest, in both feeds: ``per_part`` takes the
    grid parts as they come (device arrays stay where they are),
    ``packed`` one host-packed buffer (one host-to-device copy, no
    per-part padding).  ``jax.jit`` compiles only what is called."""

    def __init__(self, plan, spec: ExecSpec):
        self.per_part = _build_ingest_executable(plan, spec)
        self.packed = _build_ingest_executable(plan, spec, packed=True)
        self.part_shapes = _part_shapes(plan)
        self.packed_size = sum(map(math.prod, self.part_shapes))

    def _cache_size(self) -> int:
        return self.per_part._cache_size() + self.packed._cache_size()

    def pack(self, grids) -> Optional[np.ndarray]:
        """``grids`` (in part order) in one host buffer, or ``None`` where
        they must go part by part: a device array among them (it never
        makes a host round trip), canonical dtypes that differ, or a
        shape the executable does not take (the per-part path raises as
        it always has).  The buffer holds each grid's canonical dtype,
        the one ``jnp.asarray`` would give it, so the device receives
        the same values either way."""
        hosts = []
        for g, shape in zip(grids, self.part_shapes):
            if isinstance(g, jax.Array):
                return None
            a = np.asarray(g)
            if a.shape != shape:
                return None
            hosts.append(a)
        dtypes = {jax.dtypes.canonicalize_dtype(a.dtype) for a in hosts}
        if len(dtypes) != 1:
            return None
        flat, off = np.empty(self.packed_size, dtypes.pop()), 0
        for a in hosts:
            np.copyto(flat[off:off + a.size].reshape(a.shape), a)
            off += a.size
        return flat


def _ingest_executable(signature: Tuple, plan,
                       spec: ExecSpec) -> Tuple[_IngestExecutable, bool]:
    """Fetch-or-build the shared executable; returns ``(fn, was_hit)``.

    The whole get/build/insert/evict sequence runs under ONE lock, so
    concurrent binders of the same signature observe exactly one miss
    and the LRU order never corrupts (building is cheap: ``jax.jit``
    only wraps — tracing happens at first call, outside the lock)."""
    with _INGEST_CACHE_LOCK:
        fn = _INGEST_EXECUTABLES.get(signature)
        if fn is not None:
            _INGEST_EXECUTABLES.move_to_end(signature)
            return fn, True
        fn = _IngestExecutable(plan, spec)
        _INGEST_EXECUTABLES[signature] = fn
        while len(_INGEST_EXECUTABLES) > _INGEST_CACHE_MAX:
            _INGEST_EXECUTABLES.popitem(last=False)
        return fn, False


#: One process-global jitted eval: one surplus at a batch of points (the
#: points of every row of a chunk that reads that surplus).  jit caches
#: one executable per (surplus shape and sharding, point count, dtypes);
#: each point's hat-basis contraction is independent of the others, so a
#: row's answers do not depend on what it is batched with.
_EVAL_BATCHED = jax.jit(interpolate_hierarchical)

#: Jitted device-side finiteness probe for ``check_finite`` ingests.
_FINITE_CHECK = jax.jit(lambda x: jnp.all(jnp.isfinite(x)))

# Host spans: ``jax.profiler`` TraceMe events, recorded on the profiler's
# clock (the device trace's) only while a profiler session runs, and a
# no-op otherwise.  Children nest on their parent's thread.
#: a scheduler-thread pass that took work: ``_run`` of what it took
SPAN_SCHED_PASS = "ct.sched.pass"
#: the scheduler thread waiting on the work condition
SPAN_SCHED_SLEEP = "ct.sched.sleep"
#: one ingest on its chain's thread, dispatch through watermark (``seq``)
SPAN_INGEST = "ct.ingest"
#: host to device: host grids packed into one buffer and copied by one
#: ``jnp.asarray``; else (a device array among them, or mixed dtypes)
#: ``jnp.asarray`` of every component grid
SPAN_INGEST_TRANSFER = "ct.ingest.transfer"
#: the call of the ingest executable
SPAN_INGEST_LAUNCH = "ct.ingest.launch"
#: ``block_until_ready`` on the new surplus
SPAN_INGEST_WAIT = "ct.ingest.wait"
#: the ``check_finite`` round trip
SPAN_INGEST_CHECK = "ct.ingest.check"
#: the commit's lock and compare-and-swap
SPAN_INGEST_COMMIT = "ct.ingest.commit"
#: one eval chunk through its futures (``rows``)
SPAN_QUERY_BATCH = "ct.query.batch"
#: the eval of one distinct surplus of a chunk, over the points of all
#: its rows (``rows``, ``ppad``: the padded point count)
SPAN_QUERY_EVAL = "ct.query.eval"
#: the padded point array and its ``jnp.asarray``
SPAN_QUERY_POINTS = "ct.query.points"
#: the call of the eval
SPAN_QUERY_LAUNCH = "ct.query.launch"
#: ``block_until_ready`` on the chunk's answers
SPAN_QUERY_WAIT = "ct.query.wait"
#: one answer, on the thread that reads it: its eval's answers copied to
#: the host (once an eval: the array keeps the copy) and sliced there
SPAN_QUERY_FETCH = "ct.query.fetch"


def _fetch_answer(out, start: int, q: int) -> np.ndarray:
    """Points ``start .. start + q`` of an eval's answers, on the host."""
    with TraceAnnotation(SPAN_QUERY_FETCH):
        return np.array(np.asarray(out)[start:start + q])

#: How long a draining flush waits for another thread's in-flight ingest
#: before failing the dependent query futures with TimeoutError.
_DRAIN_TIMEOUT_S = 120.0


# ---------------------------------------------------------------------------
# Shared ingest pool
# ---------------------------------------------------------------------------

_SHARED_POOL: Optional[ThreadPoolExecutor] = None
_SHARED_POOL_LOCK = _lockdep.make_lock("shared-pool")


def _shared_pool() -> ThreadPoolExecutor:
    """Lazy process-wide ingest pool (daemon threads), shared by every
    engine constructed with ``ingest_workers=None``."""
    global _SHARED_POOL
    with _SHARED_POOL_LOCK:
        if _SHARED_POOL is None:
            _SHARED_POOL = ThreadPoolExecutor(
                max_workers=min(8, (os.cpu_count() or 1) + 2),
                thread_name_prefix="ct-ingest")
        return _SHARED_POOL


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class CTFuture:
    """Result handle of ``submit_ingest`` / ``submit_query``, safe to
    wait on from any thread.  Completion is a ``threading.Event``;
    ``result(timeout=)`` blocks until the request resolves, flushing the
    owning engine's queue while it waits (so a bare ``submit → result``
    still makes progress without a scheduler thread).  A request that
    FAILED stores its exception here and re-raises it from ``result()``
    — one bad request never drops the other queued requests."""

    __slots__ = ("_engine", "_event", "_payload", "_error", "done_at")

    def __init__(self, engine: "CTEngine"):
        self._engine = engine
        self._event = threading.Event()
        self._payload = None
        self._error: Optional[BaseException] = None
        #: ``time.monotonic()`` at resolution (latency accounting)
        self.done_at: Optional[float] = None

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the request resolves WITHOUT driving the engine
        (no auto-flush) and return ``done()``.  This is the wait health
        probes must use: a probe that only resolves because the prober
        flushed the queue itself proves nothing about the host's own
        scheduler liveness.  ``error()``/``result()`` read the outcome."""
        return self._event.wait(timeout)

    def error(self) -> Optional[BaseException]:
        """The stored failure of a resolved request (``None`` while
        pending or on success) — a peek that never raises or blocks."""
        return self._error

    def _set(self, payload) -> None:
        self._payload = payload
        self.done_at = time.monotonic()
        self._event.set()

    def _set_error(self, exc: BaseException) -> None:
        self._error = exc
        self.done_at = time.monotonic()
        self._event.set()

    def result(self, timeout: Optional[float] = None):
        deadline = None if timeout is None else time.monotonic() + timeout
        while not self._event.is_set():
            self._engine.flush()
            if self._event.wait(0.02):
                break
            if deadline is not None and time.monotonic() >= deadline:
                raise TimeoutError(
                    f"CTFuture.result: request still pending after "
                    f"{timeout:.3f}s")
        if self._error is not None:
            raise self._error
        return self._payload() if callable(self._payload) else self._payload


@dataclass
class _Tenant:
    """One named surrogate: scheme + plan + spec, plus the per-tenant
    runtime arguments of the shared executable and its scheduling
    defaults."""

    name: str
    scheme: SchemeLike
    spec: ExecSpec
    plan: Any                       # ExecutorPlan | ShardedPlan
    signature: Tuple
    executable: _IngestExecutable
    idxs: Tuple[jnp.ndarray, ...]
    coeffs: Tuple[jnp.ndarray, ...]
    surplus: Optional[jnp.ndarray] = None
    surplus_seq: int = 0            # ingest_seq of the committed surplus
    deadline_ms: Optional[float] = None   # None = engine default
    priority: int = 0

    @property
    def base_plan(self) -> ExecutorPlan:
        return self.plan.plan if isinstance(self.plan, ShardedPlan) \
            else self.plan


@dataclass
class _Request:
    """One queued unit of work.  Holds the tenant NAME, not the tenant
    object: refit/extend/drop_grid atomically replace the ``_Tenant``
    record, and unregister removes it — resolving by name at dispatch
    time makes queued work apply to the tenant the engine serves THEN
    (or fail its future if the name is gone), never to a stale orphan.

    ``ingest_seq`` is the per-tenant ingest watermark: for an ingest,
    its own generation number; for a query, the generation it must wait
    for (every same-tenant ingest submitted before it)."""

    kind: str                       # "ingest" | "query"
    name: str
    payload: Any                    # (grids, check_finite) | (points, q, qpad)
    future: CTFuture
    ingest_seq: int = 0
    priority: int = 0
    deadline: Optional[float] = None      # absolute time.monotonic(); None
    #                                       = only batch-full/flush dispatch


def _tenant_arrays(plan) -> Tuple[Tuple[jnp.ndarray, ...],
                                  Tuple[jnp.ndarray, ...]]:
    """Upload a plan's index maps + coefficients once per (re)bind — the
    runtime arguments that distinguish tenants sharing one executable.
    One device: the compact gather's bucket maps and its fine map."""
    if isinstance(plan, ShardedPlan):
        if plan.n_groups > 1:
            # 2-D compute-sharded plan: the executable consumes the
            # shipping maps, not the per-slab scatter maps
            idxs = tuple((jnp.asarray(sb.ship_src), jnp.asarray(sb.ship_idx))
                         for sb in plan.slab_buckets)
        else:
            idxs = tuple(jnp.asarray(sb.index) for sb in plan.slab_buckets)
        buckets = plan.plan.buckets
    else:
        compact = plan.compact
        idxs = (tuple(jnp.asarray(m) for m in compact.buckets),
                jnp.asarray(compact.fine))
        buckets = plan.buckets
    coeffs = tuple(jnp.asarray(b.coeffs) for b in buckets)
    return idxs, coeffs


def _validate_points(points, dim: int, name: str) -> np.ndarray:
    """Named errors for malformed query points — instead of a shape or
    dtype failure deep inside the jitted eval."""
    points = np.asarray(points)
    if points.ndim == 1:
        points = points[None, :]
    if points.ndim != 2 or points.shape[1] != dim:
        raise ValueError(
            f"query points for tenant {name!r} must have shape (Q, {dim}) "
            f"— the scheme is {dim}-dimensional — got {points.shape}")
    if not np.issubdtype(points.dtype, np.floating):
        raise TypeError(
            f"query points for tenant {name!r} must be a floating dtype "
            f"(coordinates in [0,1]^{dim}), got {points.dtype}")
    return points


def _qpad(q: int) -> int:
    """Pad the batch extent to a power of two (>= 16) so varying batch
    sizes compile once per bucket, not once per Q."""
    return max(16, 1 << max(0, q - 1).bit_length())


_UNSET = object()


class CTEngine:
    """Thread-safe multi-tenant CT surrogate server (see the module
    docstring for the full threading / scheduling contract).

    ``submit_*`` enqueue from any thread; the queue drains via
    ``flush()`` (everything), ``pump()`` (one deadline/batch-full
    scheduler step) or the ``start()``-ed background scheduler thread.
    Ingests run on a background pool, ordered per tenant by a watermark
    that queries of the same tenant wait on; queries coalesce into
    chunks per signature group, each evaluated once per distinct
    surplus.  The ingest-executable
    cache is process-global (lock-guarded); hit/miss counters are per
    engine.  The queue is bounded (``max_pending``): non-blocking
    submits raise ``EngineSaturated`` when full.
    """

    def __init__(self, spec: Optional[ExecSpec] = None, *,
                 max_batch: int = 32, max_pending: int = 1024,
                 deadline_ms: float = 10.0,
                 ingest_workers: Optional[int] = None,
                 check_finite: bool = False,
                 host_id: Optional[str] = None,
                 store: Optional[DurableStore] = None,
                 snapshot_interval: int = 16,
                 retry: Optional[RetryPolicy] = None):
        if spec is not None and not isinstance(spec, ExecSpec):
            raise TypeError(f"CTEngine: spec must be an ExecSpec, got "
                            f"{type(spec).__name__}")
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        self._default_spec = spec or ExecSpec()
        self._max_batch = max_batch
        self._max_pending = max_pending
        self._deadline_ms = deadline_ms
        self._check_finite = check_finite
        #: durable tenant store (``repro.runtime.durability``): admitted
        #: ingests are journaled BEFORE they enqueue, the served surplus
        #: is snapshotted every ``snapshot_interval`` acked ingests, and
        #: ``restore()`` rebuilds every tenant after a crash.  ``None``
        #: keeps the engine pure in-memory (the default).
        self._store = store
        self._snapshot_interval = snapshot_interval
        self._retry = retry or RetryPolicy(attempts=5, base_delay_s=0.0)
        self._snap_seq: Dict[str, int] = {}     # last snapshotted watermark
        self._last_tag: Dict[str, int] = {}     # newest caller ordering tag
        self._replay_pending: Dict[str, List[Any]] = {}
        #: name of this engine in a multi-host deployment (cluster logs,
        #: error messages, stats); None = a standalone engine
        self.host_id = host_id
        self._last_pump = time.monotonic()
        self._lock = _lockdep.make_rlock("engine")
        self._work = threading.Condition(self._lock)    # new work / progress
        self._space = threading.Condition(self._lock)   # queue has room
        self._work_seq = 0          # bumped on every submit/progress event
        self._tenants: Dict[str, _Tenant] = {}
        self._pending: List[_Request] = []
        self._ingest_submitted: Dict[str, int] = {}
        self._ingest_done: Dict[str, int] = {}
        self._counters = {"ingests": 0, "queries": 0, "eval_batches": 0,
                          "surplus_evals": 0, "coalesced_queries": 0,
                          "cache_hits": 0, "cache_misses": 0}
        #: (surplus shape, dtype, sharding, qpad, point dtype) of every
        #: eval this engine has warmed at all its row paddings (under the
        #: engine lock)
        self._eval_warm: set = set()
        #: how ``_dispatch_ingest`` fed each ingest to the device, under a
        #: leaf lock of its own: the engine lock is the ingests' contended
        #: commit lock
        self._feed = {"packed": 0, "per_part": 0}
        self._feed_lock = _lockdep.make_lock("ingest-feed")
        self._sched = {"dispatch_deadline": 0, "dispatch_batch_full": 0,
                       "flushes": 0, "rejected": 0, "requeued": 0,
                       "ingest_retries": 0, "promoted": 0}
        if ingest_workers is None:
            self._private_pool = None
            self._inline_ingest = False
        elif ingest_workers == 0:
            self._private_pool = None
            self._inline_ingest = True
        else:
            self._private_pool = ThreadPoolExecutor(
                max_workers=ingest_workers, thread_name_prefix="ct-ingest")
            self._inline_ingest = False
        self._sched_thread: Optional[threading.Thread] = None
        self._stop_evt: Optional[threading.Event] = None

    # -- registry -----------------------------------------------------------

    def register(self, name: str, scheme: SchemeLike, nodal_grids=None, *,
                 spec: Optional[ExecSpec] = None,
                 deadline_ms: Optional[float] = None,
                 priority: int = 0, plan=None, surplus=None,
                 tag: Optional[int] = None,
                 durable: bool = True) -> "CTEngine":
        """Admit tenant ``name``: build its plan under ``spec`` (engine
        default when omitted), bind the signature-shared executable, and
        — when ``nodal_grids`` is given — ingest immediately.
        ``deadline_ms`` / ``priority`` set the tenant's scheduling
        defaults (queries may override per call).

        ``plan=`` / ``surplus=`` are the failover ADOPTION fast lane
        (``repro.runtime.cluster`` host migration): a retained plan
        skips ``build_plan`` and — signature unchanged — re-binds the
        already-compiled executable from the process-global cache; a
        retained surplus installs the served state directly, skipping
        the ingest entirely.  The caller owns the consistency of an
        adopted (scheme, plan, surplus) triple.  ``surplus=`` and
        ``nodal_grids=`` are mutually exclusive.

        With a durable store attached (and ``durable=True``) the tenant's
        identity is registered in the store, an initial ``nodal_grids``
        ingest is journaled at admission, and an adopted ``surplus`` is
        snapshotted immediately — so a host crash right after a failover
        adoption still restores the adopted state.  ``tag`` is the
        caller's own ordering tag (the cluster's per-tenant seq)
        journaled alongside the engine watermark; ``durable=False`` is
        for tenants that must never persist (probes) and for
        ``restore()`` itself (whose state is already on disk)."""
        if spec is not None and not isinstance(spec, ExecSpec):
            raise TypeError(f"register: spec must be an ExecSpec, got "
                            f"{type(spec).__name__}")
        if surplus is not None and nodal_grids is not None:
            raise ValueError(
                "register: pass nodal_grids= (ingest now) or surplus= "
                "(adopt precomputed state), not both")
        with self._lock:
            if name in self._tenants:
                raise ValueError(f"tenant {name!r} already registered "
                                 f"(unregister first, or refit)")
        spec = spec or self._default_spec
        if plan is None:
            plan = build_plan(scheme, spec=spec)      # outside the lock
        tenant = self._bind(name, scheme, spec, plan)
        tenant.deadline_ms, tenant.priority = deadline_ms, priority
        if surplus is not None:
            tenant.surplus = surplus
        durable = durable and self._store is not None
        if durable:
            # identity first (atomic meta.json), so a crash between here
            # and the first journal append restores an EMPTY tenant, not
            # an unknown one
            self._store.register(
                name, scheme, full_levels=tenant.base_plan.full_levels,
                deadline_ms=deadline_ms, priority=priority)
        with self._work:
            if name in self._tenants:
                raise ValueError(f"tenant {name!r} already registered "
                                 f"(unregister first, or refit)")
            self._tenants[name] = tenant
            if nodal_grids is not None:
                # count the initial ingest in the per-name watermark so a
                # query submitted between this insert and the surplus
                # commit below WAITS for it instead of observing the
                # still-empty tenant ("no ingested state to query")
                seq0 = self._ingest_submitted.get(name, 0) + 1
                self._ingest_submitted[name] = seq0
                if durable:
                    try:
                        # journal at admission: a crash after this append
                        # replays the initial ingest; a crash during it
                        # fails the registration (nothing was admitted)
                        # ctlint: ok(block-under-lock): journal order must equal admission order (PR 9)
                        self._store.append(name, seq0, nodal_grids,
                                           tag=tag)
                    except Exception:
                        del self._tenants[name]
                        self._ingest_submitted[name] = seq0 - 1
                        raise
                if tag is not None:
                    self._last_tag[name] = tag
            self._work_seq += 1
            self._work.notify_all()
        if durable and surplus is not None:
            # adopted state never flows through submit_ingest, so make it
            # durable NOW via an immediate snapshot (also rotates away
            # any stale journal of a previous incarnation of the name)
            seq0 = self._ingest_submitted.get(name, 0)
            if tag is not None:
                self._last_tag[name] = tag
            self._snapshot_now(name, seq0, tag, surplus,
                               scheme=scheme,
                               full_levels=tenant.base_plan.full_levels)
        if nodal_grids is not None:
            try:
                surplus = self._dispatch_ingest(tenant, nodal_grids)
                with self._lock:
                    tenant.surplus = surplus
                    self._counters["ingests"] += 1
            except Exception:
                with self._lock:
                    if self._tenants.get(name) is tenant:
                        del self._tenants[name]
                raise
            finally:
                # advance even on failure: waiters re-check and fail fast
                # against the rolled-back registry instead of hanging
                with self._work:
                    self._ingest_done[name] = \
                        self._ingest_done.get(name, 0) + 1
                    self._work_seq += 1
                    self._work.notify_all()
        return self

    def unregister(self, name: str) -> None:
        """Remove tenant ``name``.  Work already queued for the name
        fails its future with a named ``KeyError`` at dispatch time
        (never hangs); the per-name ingest watermark stays monotonic so
        a later re-register is race-free against stragglers.  Durable
        state is discarded: an unregister is a deliberate handoff (or
        retirement), not a crash — a later ``restore()`` must not
        resurrect a tenant this host no longer owns."""
        with self._work:
            del self._tenants[name]
            self._replay_pending.pop(name, None)
            self._work_seq += 1
            self._work.notify_all()
        if self._store is not None:
            self._store.discard(name)

    def __contains__(self, name: str) -> bool:
        return name in self._tenants

    def names(self) -> Tuple[str, ...]:
        with self._lock:
            return tuple(self._tenants)

    def _tenant(self, name: str) -> _Tenant:
        with self._lock:
            try:
                return self._tenants[name]
            except KeyError:
                raise KeyError(f"no tenant {name!r} (registered: "
                               f"{sorted(self._tenants)})") from None

    def scheme(self, name: str) -> SchemeLike:
        return self._tenant(name).scheme

    def plan(self, name: str):
        return self._tenant(name).plan

    def spec(self, name: str) -> ExecSpec:
        return self._tenant(name).spec

    def surplus(self, name: str) -> jnp.ndarray:
        """The tenant's served sparse-grid surplus (flushes and waits if
        an ingest for it is still queued or in flight)."""
        t = self._tenant(name)
        with self._lock:
            target = self._ingest_submitted.get(name, 0)
            behind = self._ingest_done.get(name, 0) < target
        if behind:
            self.flush()
            deadline = time.monotonic() + _DRAIN_TIMEOUT_S
            with self._work:
                while self._ingest_done.get(name, 0) < target:
                    if name not in self._tenants:
                        break
                    if not self._work.wait(1.0) \
                            and time.monotonic() >= deadline:
                        raise TimeoutError(
                            f"surplus({name!r}): in-flight ingest did not "
                            f"complete within {_DRAIN_TIMEOUT_S:.0f}s")
            t = self._tenant(name)
        if t.surplus is None:
            raise RuntimeError(f"tenant {name!r} has no ingested state yet")
        return t.surplus

    # -- executable binding -------------------------------------------------

    def _bind(self, name: str, scheme: SchemeLike, spec: ExecSpec,
              plan) -> _Tenant:
        signature = plan_signature(plan, spec)
        executable, hit = _ingest_executable(signature, plan, spec)
        with self._lock:
            self._counters["cache_hits" if hit else "cache_misses"] += 1
        idxs, coeffs = _tenant_arrays(plan)
        return _Tenant(name=name, scheme=scheme, spec=spec, plan=plan,
                       signature=signature, executable=executable,
                       idxs=idxs, coeffs=coeffs)

    def _check_not_donated(self, name: str, nodal_grids) -> None:
        """Raise the named ``IngestBuffersDonated`` error if any grid in
        the payload is a jax array whose buffer has already been deleted
        (i.e. donated to a previous dispatch of this same request)."""
        dead = [ell for ell, v in nodal_grids.items()
                if isinstance(v, jax.Array) and v.is_deleted()]
        if dead:
            raise IngestBuffersDonated(
                f"{self._host()}: ingest for tenant {name!r} cannot be "
                f"redispatched: {len(dead)} input grid(s) (first: "
                f"{dead[0]}) were donated to a previous attempt and "
                f"their device buffers are deleted — resubmit from host "
                f"copies")

    def _dispatch_ingest(self, tenant: _Tenant, nodal_grids) -> jnp.ndarray:
        _lockdep.note_dispatch("engine._dispatch_ingest")
        base = tenant.base_plan
        _check_nodal_grids(nodal_grids, base)
        exe = tenant.executable
        grids = [nodal_grids[ell] for b in base.buckets for ell in b.ells]
        with TraceAnnotation(SPAN_INGEST_TRANSFER):
            flat = exe.pack(grids)
            if flat is None:
                fn, feed = exe.per_part, tuple(jnp.asarray(g) for g in grids)
            else:
                fn, feed = exe.packed, jnp.asarray(flat)
        with self._feed_lock:
            self._feed["per_part" if flat is None else "packed"] += 1
        with TraceAnnotation(SPAN_INGEST_LAUNCH):
            return fn(feed, tenant.idxs, tenant.coeffs)

    # -- thread-safe submission ---------------------------------------------

    def _host(self) -> str:
        """Prefix naming this engine in error messages."""
        return f"engine[{self.host_id}]" if self.host_id else "engine"

    def _admit(self, block: bool, timeout: Optional[float],
               name: str) -> None:  # ctlint: holds(engine)
        """Bounded-queue admission control; caller holds the lock.  The
        rejection names the tenant and the live queue state — the
        actionable line a cluster operator greps for."""
        if len(self._pending) < self._max_pending:
            return
        if not block:
            self._sched["rejected"] += 1
            raise EngineSaturated(
                f"{self._host()}: rejecting request for tenant {name!r}: "
                f"queue depth {len(self._pending)} >= max_pending="
                f"{self._max_pending}; flush(), start() the scheduler, "
                f"or raise max_pending")
        deadline = None if timeout is None else time.monotonic() + timeout
        while len(self._pending) >= self._max_pending:
            if deadline is None:
                self._space.wait(0.1)
            else:
                left = deadline - time.monotonic()
                if left <= 0 or not self._space.wait(left):
                    if len(self._pending) < self._max_pending:
                        break
                    self._sched["rejected"] += 1
                    raise EngineSaturated(
                        f"{self._host()}: request for tenant {name!r} "
                        f"still blocked after {timeout:.3f}s: queue depth "
                        f"{len(self._pending)} >= max_pending="
                        f"{self._max_pending}")

    def submit_ingest(self, name: str, nodal_grids, *, priority: int = 0,
                      check_finite: Optional[bool] = None, block: bool = True,
                      timeout: Optional[float] = None,
                      tag: Optional[int] = None) -> CTFuture:
        """Enqueue new solver output for ``name`` (callable from any
        thread); the future resolves to the new surplus buffer once the
        ingest pool commits it.  Ingests of one tenant apply in
        submission order; queries of the same tenant submitted later
        observe this ingest.

        With a durable store attached the payload is JOURNALED here, at
        admission, keyed by the per-tenant watermark seq — before the
        request can be acknowledged, so every acked ingest is on disk.
        A failed append (e.g. a crash torn mid-record) fails the
        admission itself: the caller sees the error, nothing was acked,
        and replay stops cleanly before the torn tail.  ``tag`` is the
        caller's own ordering tag (the cluster's per-tenant seq) stored
        alongside the engine seq — what ``restart_host`` compares
        against the cluster's committed seq to arbitrate freshness."""
        self._tenant(name)                      # raise early on a bad name
        check = self._check_finite if check_finite is None else check_finite
        fut = CTFuture(self)
        with self._work:
            self._admit(block, timeout, name)
            if name not in self._tenants:
                raise KeyError(f"no tenant {name!r} (registered: "
                               f"{sorted(self._tenants)})")
            seq = self._ingest_submitted.get(name, 0) + 1
            self._ingest_submitted[name] = seq
            if self._store is not None:
                try:
                    # an append outside the lock could ack seq N+1
                    # before N is on disk, so this one stays under it
                    # ctlint: ok(block-under-lock): journal order must equal admission order (PR 9)
                    self._store.append(name, seq, nodal_grids, tag=tag)
                except Exception:
                    self._ingest_submitted[name] = seq - 1
                    raise
            if tag is not None:
                self._last_tag[name] = tag
            self._pending.append(
                _Request("ingest", name, (nodal_grids, check, tag), fut,
                         ingest_seq=seq, priority=priority,
                         deadline=time.monotonic()))
            self._work_seq += 1
            self._work.notify_all()
        return fut

    def submit_query(self, name: str, points, *,
                     deadline_ms: Optional[float] = None,
                     priority: Optional[int] = None, block: bool = True,
                     timeout: Optional[float] = None,
                     stale_ok: bool = False) -> CTFuture:
        """Enqueue a point-evaluation batch against ``name``'s surplus
        (callable from any thread); the future resolves to the (Q,)
        values once the scheduler dispatches its signature group —
        batch-full, deadline expiry, or any ``flush``.  Same-signature
        queries across tenants coalesce into one chunk, evaluated once
        per distinct surplus.

        ``stale_ok=True`` waits only for the ingests already COMMITTED
        (the done watermark), not for every ingest already admitted —
        the graceful-degradation mode a cluster uses against a tenant
        mid-recovery: the query serves the restored-snapshot state
        immediately instead of blocking behind the WAL replay."""
        tenant = self._tenant(name)
        points = _validate_points(points, tenant.base_plan.dim, name)
        q = points.shape[0]
        if deadline_ms is None:
            deadline_ms = tenant.deadline_ms if tenant.deadline_ms \
                is not None else self._deadline_ms
        prio = tenant.priority if priority is None else priority
        fut = CTFuture(self)
        dl = (time.monotonic() + deadline_ms / 1000.0
              if deadline_ms is not None and math.isfinite(deadline_ms)
              else None)
        with self._work:
            self._admit(block, timeout, name)
            if name not in self._tenants:
                raise KeyError(f"no tenant {name!r} (registered: "
                               f"{sorted(self._tenants)})")
            watermark = (self._ingest_done if stale_ok
                         else self._ingest_submitted).get(name, 0)
            self._pending.append(
                _Request("query", name, (points, q, _qpad(q)), fut,
                         ingest_seq=watermark,
                         priority=prio, deadline=dl))
            self._work_seq += 1
            self._work.notify_all()
        return fut

    def submit_probe(self, *, block: bool = False,
                     timeout: Optional[float] = None) -> CTFuture:
        """Liveness probe: enqueue a no-op request that rides the full
        queue/scheduler path and resolves (to ``True``) when a pump,
        flush, or the scheduler thread reaches it.  Health monitors
        pair this with ``CTFuture.wait(deadline)`` — NOT ``result()``,
        whose auto-flush would mask a dead scheduler.  Probes are
        always due and never coalesce with tenant work."""
        fut = CTFuture(self)
        with self._work:
            self._admit(block, timeout, "__probe__")
            self._pending.append(
                _Request("probe", "__probe__", None, fut,
                         deadline=time.monotonic()))
            self._work_seq += 1
            self._work.notify_all()
        return fut

    def heartbeat(self) -> Dict[str, Any]:
        """Pump-liveness snapshot: monotonic time of the last scheduler
        pass (``pump``/``flush``/scheduler-loop iteration), its age,
        queue depth, and whether the scheduler thread is alive.  A
        cluster health monitor reads stalls from a growing ``age_s``."""
        now = time.monotonic()
        with self._lock:
            alive = (self._sched_thread is not None
                     and self._sched_thread.is_alive())
            return {"host_id": self.host_id,
                    "last_pump": self._last_pump,
                    "age_s": now - self._last_pump,
                    "pending": len(self._pending),
                    "scheduler_alive": alive}

    # -- draining: flush / pump / scheduler ---------------------------------

    def flush(self) -> None:
        """Drain the WHOLE queue now: dispatch every pending ingest on
        the pool (per-tenant chains, submission order), coalesce every
        pending query into one batched eval per signature group, and
        return once all of it completed.  The queue swap is atomic under
        the engine lock — a ``submit_*`` racing this flush lands either
        in this drain or intact in the queue for the next one, never
        dropped.  A failing request resolves ITS OWN future with the
        exception (re-raised by ``result()``); siblings proceed."""
        with self._work:
            self._last_pump = time.monotonic()
            pending, self._pending = self._pending, []
            if pending:
                self._sched["flushes"] += 1
                self._space.notify_all()
        if not pending:
            return
        self._run(pending, drain=True)

    def pump(self, now: Optional[float] = None) -> int:
        """One scheduler step: dispatch only the DUE work (ingests
        always; queries on batch-full or deadline expiry).  Returns the
        number of requests resolved or handed to the pool."""
        with self._work:
            self._last_pump = time.monotonic()
            take, _ = self._take_due(time.monotonic() if now is None
                                     else now)
        if not take:
            return 0
        return self._run(take, drain=False)

    def start(self) -> "CTEngine":
        """Start the background scheduler thread (idempotent)."""
        with self._lock:
            if self._sched_thread is not None \
                    and self._sched_thread.is_alive():
                return self
            stop_evt = threading.Event()
            t = threading.Thread(target=self._scheduler_loop,
                                 args=(stop_evt,), name="ct-scheduler",
                                 daemon=True)
            self._stop_evt, self._sched_thread = stop_evt, t
        t.start()
        return self

    def stop(self, drain: bool = True) -> None:
        """Stop the scheduler thread; ``drain=True`` flushes what is
        left in the queue after it exits."""
        with self._lock:
            t, evt = self._sched_thread, self._stop_evt
            self._sched_thread = self._stop_evt = None
        if evt is not None:
            evt.set()
            with self._work:
                self._work.notify_all()
        if t is not None:
            t.join(timeout=30.0)
        if drain:
            self.flush()

    def close(self) -> None:
        """Stop the scheduler, drain the queue, shut down a private
        ingest pool.  The shared pool stays up for other engines; an
        attached durable store gets a final fsync (the store itself
        belongs to the host, so it is flushed, not closed)."""
        self.stop(drain=True)
        if self._private_pool is not None:
            self._private_pool.shutdown(wait=True)
        if self._store is not None:
            try:
                self._store.flush()
            except OSError:
                pass        # a closed/unlinked store at shutdown is moot

    def __enter__(self) -> "CTEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    def _scheduler_loop(self, stop_evt: threading.Event) -> None:
        while not stop_evt.is_set():
            now = time.monotonic()
            with self._work:
                self._last_pump = now
                seq = self._work_seq
                take, next_wake = self._take_due(now)
            if take:
                with TraceAnnotation(SPAN_SCHED_PASS):
                    did = self._run(take, drain=False)
                if did == 0:
                    # everything requeued (queries waiting on in-flight
                    # ingests): block briefly instead of spinning
                    with self._work:
                        if self._work_seq == seq:
                            with TraceAnnotation(SPAN_SCHED_SLEEP):
                                self._work.wait(0.01)
                continue
            with self._work:
                if self._work_seq != seq:
                    continue                    # raced a submit: rescan
                delay = 0.05
                if next_wake is not None:
                    delay = min(delay, next_wake - time.monotonic())
                with TraceAnnotation(SPAN_SCHED_SLEEP):
                    self._work.wait(max(delay, 0.001))

    def _take_due(self, now: float) -> Tuple[List[_Request],  # ctlint: holds(engine)
                                             Optional[float]]:
        """Pull the due requests off the queue; caller holds the lock.
        Ingests and probes are always due (the pool overlaps ingests
        with everything else); a query is due when its tenant's pending
        batch is full, its deadline expired, or its tenant is gone
        (fail fast).  Returns ``(due, next_deadline)``.

        Two anti-head-of-line rules (a large low-priority eval batch
        must not delay a high-priority query past its budget):

        * **cap** — a batch-full tenant contributes at most
          ``max_batch`` queries per pump (highest priority first,
          submission order within a priority), so one oversized
          low-priority backlog drains across pumps instead of
          monopolizing a single pump while other deadlines expire;
        * **promote** — when this pump dispatches any query work,
          every pending query of STRICTLY higher priority than the due
          set is taken along (even if its own deadline has not
          expired): the dispatch path orders by priority, so the
          high-priority group runs FIRST within the same pump at the
          cost of a slightly earlier (never later) dispatch for it.
        """
        pending = self._pending
        counts: Dict[str, int] = {}
        for r in pending:
            if r.kind == "query":
                counts[r.name] = counts.get(r.name, 0) + 1
        full = {n for n, c in counts.items() if c >= self._max_batch}
        self._sched["dispatch_batch_full"] += len(full)
        take_idx = set()
        for i, r in enumerate(pending):
            if r.kind != "query" or r.name not in self._tenants:
                take_idx.add(i)
            elif r.deadline is not None and r.deadline <= now:
                take_idx.add(i)
                self._sched["dispatch_deadline"] += 1
        for name in full:
            cand = [i for i, r in enumerate(pending)
                    if r.kind == "query" and r.name == name
                    and i not in take_idx]
            cand.sort(key=lambda i: (-pending[i].priority, i))
            take_idx.update(cand[:self._max_batch])
        due_q = [pending[i].priority for i in take_idx
                 if pending[i].kind == "query"]
        if due_q:
            pmax = max(due_q)
            for i, r in enumerate(pending):
                if i not in take_idx and r.kind == "query" \
                        and r.priority > pmax:
                    take_idx.add(i)
                    self._sched["promoted"] = \
                        self._sched.get("promoted", 0) + 1
        take, keep = [], []
        next_wake: Optional[float] = None
        for i, r in enumerate(pending):
            if i in take_idx:
                take.append(r)
            else:
                keep.append(r)
                if r.deadline is not None and (next_wake is None
                                               or r.deadline < next_wake):
                    next_wake = r.deadline
        self._pending = keep
        if take:
            self._space.notify_all()
        return take, next_wake

    # -- execution ----------------------------------------------------------

    def _run(self, requests: List[_Request], drain: bool) -> int:
        """Execute a batch of taken requests: per-tenant ingest chains go
        to the pool (or run inline), queries resolve/coalesce on the
        calling thread.  ``drain=True`` additionally barriers on the
        chains before returning (flush semantics).  Returns the number
        of requests resolved or handed to the pool."""
        chains: Dict[str, List[_Request]] = {}
        queries: List[_Request] = []
        probes: List[_Request] = []
        for r in requests:
            if r.kind == "ingest":
                chains.setdefault(r.name, []).append(r)
            elif r.kind == "probe":
                probes.append(r)
            else:
                queries.append(r)
        # probes resolve the moment the scheduler path reaches them —
        # that round trip IS the signal they measure
        for r in probes:
            r.future._set(True)
        progress = len(probes) + sum(len(c) for c in chains.values())
        pool = None if self._inline_ingest \
            else (self._private_pool or _shared_pool())
        chain_futures = []
        for reqs in chains.values():
            if pool is None:
                self._run_ingest_chain(reqs)
            else:
                chain_futures.append(pool.submit(self._run_ingest_chain,
                                                 reqs))
        try:
            progress += self._run_queries(queries, drain=drain)
        finally:
            if drain:
                for f in chain_futures:
                    f.result()      # engine bugs only; per-request errors
                    #                 resolved on the owning futures already
        return progress

    def _run_ingest_chain(self, reqs: List[_Request]) -> None:
        """One tenant's queued ingests, in submission order.  EVERY exit
        path advances the watermark and notifies — a failed ingest still
        unblocks the queries that waited on it (they see the previous
        surplus, or its error semantics via their own checks)."""
        for req in reqs:
            grids, check, tag = req.payload
            committed = None
            with TraceAnnotation(SPAN_INGEST, seq=req.ingest_seq):
                try:
                    surplus = self._ingest_one(req.name, grids, check,
                                               req.ingest_seq)
                except Exception as exc:
                    req.future._set_error(exc)
                else:
                    req.future._set(surplus)
                    committed = surplus
                finally:
                    with self._work:
                        if req.ingest_seq > self._ingest_done.get(
                                req.name, 0):
                            self._ingest_done[req.name] = req.ingest_seq
                        self._work_seq += 1
                        self._work.notify_all()
            if committed is not None:
                # AFTER the ack and the watermark advance: a snapshot is
                # an optimization of future recovery, never on the ack
                # critical path — and never a reason to fail an ingest
                # that already succeeded
                self._maybe_snapshot(req.name, req.ingest_seq, tag,
                                     committed)

    def _ingest_one(self, name: str, nodal_grids, check_finite: bool,
                    seq: int = 0):
        """Dispatch + commit one ingest.  Device work runs OUTSIDE the
        lock; the commit is a compare-and-swap against the tenant record
        read before dispatch, retried when a concurrent refit/rebind
        swapped the record mid-flight.  The commit is NEWEST-SEQ-WINS:
        same-tenant chains taken by DIFFERENT pump passes run on the
        pool concurrently, so an older ingest finishing last must not
        clobber a newer one's committed surplus (its future still
        resolves with its own computed value).  The retry budget comes
        from the engine's ``RetryPolicy`` (no sleeping: losing the CAS
        means the record ALREADY changed, there is nothing to wait
        for)."""
        def attempt():
            with self._lock:
                tenant = self._tenants.get(name)
            if tenant is None:
                raise KeyError(f"tenant {name!r} was unregistered before "
                               f"its queued ingest ran")
            if tenant.spec.donate:
                # donated buffers are deleted once the executable has
                # consumed them — redispatching them (rebind-race retry,
                # or a failover resubmission) would hand XLA dead
                # buffers.  Fail the owning future with the NAMED error
                # instead.
                self._check_not_donated(name, nodal_grids)
            surplus = self._dispatch_ingest(tenant, nodal_grids)
            # device-side failures surface HERE, on the owning request —
            # never from a sibling's flush
            with TraceAnnotation(SPAN_INGEST_WAIT):
                jax.block_until_ready(surplus)
            if check_finite:
                with TraceAnnotation(SPAN_INGEST_CHECK):
                    finite = bool(_FINITE_CHECK(surplus))
            if check_finite and not finite:
                if tenant.spec.donate:
                    raise IngestBuffersDonated(
                        f"ingest for tenant {name!r} produced non-finite "
                        f"surplus values and its input buffers were "
                        f"donated — cannot retry; resubmit from host "
                        f"copies")
                raise FloatingPointError(
                    f"ingest for tenant {name!r} produced non-finite "
                    f"surplus values")
            with TraceAnnotation(SPAN_INGEST_COMMIT), self._work:
                cur = self._tenants.get(name)
                if cur is None:
                    raise KeyError(f"tenant {name!r} was unregistered "
                                   f"before its queued ingest ran")
                if cur is tenant:
                    if seq >= cur.surplus_seq:
                        cur.surplus = surplus
                        cur.surplus_seq = seq
                    self._counters["ingests"] += 1
                    return surplus
                self._sched["ingest_retries"] += 1
                raise _RebindRace(name)
        try:
            return self._retry.run(attempt, retry_on=(_RebindRace,),
                                   sleep=False)
        except _RebindRace:
            raise RuntimeError(
                f"ingest for tenant {name!r} kept losing the rebind race "
                f"({self._retry.attempts} attempts) — engine bug") from None

    def _run_queries(self, queries: List[_Request], drain: bool) -> int:
        """Resolve query requests: group the watermark-eligible ones by
        signature and dispatch; park the rest (requeue when pumping,
        wait for the in-flight ingests when draining)."""
        if not queries:
            return 0
        resolved = 0
        remaining = list(queries)
        give_up = time.monotonic() + _DRAIN_TIMEOUT_S
        while remaining:
            groups: Dict[Tuple, List[Tuple[_Request, Any, int]]] = {}
            waiting: List[_Request] = []
            with self._lock:
                for req in remaining:
                    t = self._tenants.get(req.name)
                    if t is None:
                        req.future._set_error(KeyError(
                            f"tenant {req.name!r} was unregistered before "
                            f"its queued query ran"))
                        resolved += 1
                        continue
                    if self._ingest_done.get(req.name, 0) < req.ingest_seq:
                        waiting.append(req)     # its ingest is in flight
                        continue
                    if t.surplus is None:
                        if self._ingest_done.get(req.name, 0) < \
                                self._ingest_submitted.get(req.name, 0):
                            # a re-registered tenant whose first surplus
                            # is still committing: the query predates the
                            # swap (its seq is already met) but must not
                            # observe the empty record
                            waiting.append(req)
                            continue
                        req.future._set_error(RuntimeError(
                            f"tenant {req.name!r} has no ingested state "
                            f"to query"))
                        resolved += 1
                        continue
                    points, _, qpad = req.payload
                    key = (t.surplus.shape, str(t.surplus.dtype),
                           str(points.dtype), qpad)
                    groups.setdefault(key, []).append(
                        (req, t.surplus, t.base_plan.dim))
            if groups:
                resolved += self._dispatch_query_groups(groups)
            if not waiting:
                break
            if not drain:
                with self._work:
                    self._pending[:0] = waiting
                    self._sched["requeued"] += len(waiting)
                break
            with self._work:
                def _unblocked(r):
                    t = self._tenants.get(r.name)
                    if t is None:
                        return True
                    done = self._ingest_done.get(r.name, 0)
                    return done >= r.ingest_seq and (
                        t.surplus is not None
                        or done >= self._ingest_submitted.get(r.name, 0))
                progressed = any(_unblocked(r) for r in waiting)
                if not progressed:
                    self._work.wait(0.05)
                    if time.monotonic() >= give_up:
                        for r in waiting:
                            r.future._set_error(TimeoutError(
                                f"query for tenant {r.name!r} timed out "
                                f"waiting for its in-flight ingest"))
                        resolved += len(waiting)
                        break
            remaining = waiting
        return resolved

    def _dispatch_query_groups(self, groups) -> int:
        """Batched eval of signature groups, highest priority / earliest
        deadline first, chunked to ``max_batch``.  Runs OUTSIDE the
        engine lock (device dispatch never holds locks); counters update
        under the lock afterwards."""
        _lockdep.note_dispatch("engine._dispatch_query_groups")

        def group_rank(item):
            entries = item[1]
            return (-max(r.priority for r, _, _ in entries),
                    min((r.deadline if r.deadline is not None else math.inf)
                        for r, _, _ in entries))

        count = 0
        for key, entries in sorted(groups.items(), key=group_rank):
            _, _, pts_dtype, qpad = key
            entries.sort(key=lambda e: (
                -e[0].priority,
                e[0].deadline if e[0].deadline is not None else math.inf))
            # chunk by max_batch AND break at priority boundaries: a
            # high-priority query dispatches in its own (small, small
            # T-pad) batch instead of padding into — and waiting on —
            # the low-priority mega-batch behind it
            chunks: List[List] = []
            for e in entries:
                if chunks and len(chunks[-1]) < self._max_batch \
                        and chunks[-1][0][0].priority == e[0].priority:
                    chunks[-1].append(e)
                else:
                    chunks.append([e])
            for chunk in chunks:
                with TraceAnnotation(SPAN_QUERY_BATCH, rows=len(chunk)):
                    self._eval_chunk(chunk, qpad, pts_dtype)
                count += len(chunk)
        return count

    def _eval_chunk(self, chunk, qpad: int, pts_dtype) -> None:
        """Evaluate ``chunk``: one eval per distinct surplus in it, over
        the points of all the rows that read it, each row's points at
        ``qpad`` apart and the rows padded to a power of two, so that
        under deadline dispatch a varying group size compiles nothing
        new.  Resolves every future of the chunk, with the answers or
        the error."""
        by_surplus: Dict[int, List[int]] = {}
        for i, (_, surplus, _) in enumerate(chunk):
            by_surplus.setdefault(id(surplus), []).append(i)
        evals = []
        try:
            for rows in by_surplus.values():
                _, surplus, dim = chunk[rows[0]]
                rpad = 1 << (len(rows) - 1).bit_length()
                with TraceAnnotation(SPAN_QUERY_EVAL, rows=len(rows),
                                     ppad=rpad * qpad):
                    self._warm_eval(surplus, qpad, pts_dtype, dim)
                    with TraceAnnotation(SPAN_QUERY_POINTS):
                        padded = np.zeros((rpad * qpad, dim), pts_dtype)
                        for j, i in enumerate(rows):
                            points, q, _ = chunk[i][0].payload
                            padded[j * qpad:j * qpad + q] = points
                        pts = jnp.asarray(padded)
                    with TraceAnnotation(SPAN_QUERY_LAUNCH):
                        evals.append((rows, _EVAL_BATCHED(surplus, pts)))
            with TraceAnnotation(SPAN_QUERY_WAIT):
                jax.block_until_ready([out for _, out in evals])
        except Exception as exc:
            for r, _, _ in chunk:
                r.future._set_error(exc)
            return
        for rows, out in evals:
            for j, i in enumerate(rows):
                r = chunk[i][0]
                r.future._set(functools.partial(_fetch_answer, out,
                                                j * qpad, r.payload[1]))
        with self._lock:
            self._counters["eval_batches"] += 1
            self._counters["surplus_evals"] += len(evals)
            self._counters["queries"] += len(chunk)
            self._counters["coalesced_queries"] += len(chunk) - 1

    def _warm_eval(self, surplus, qpad: int, pts_dtype, dim: int) -> None:
        """At the first eval of a surplus shape, dtype and sharding at
        ``qpad`` points a row, run the eval once at every row padding a
        chunk of ``max_batch`` rows can reach, so that no later chunk
        compiles, whatever mix of tenants it holds."""
        key = (surplus.shape, str(surplus.dtype), surplus.sharding, qpad,
               str(pts_dtype))
        with self._lock:
            seen = key in self._eval_warm
            self._eval_warm.add(key)
        if seen:
            return
        top = 1 << (self._max_batch - 1).bit_length()
        rpad = 1
        while rpad <= top:
            jax.block_until_ready(_EVAL_BATCHED(
                surplus, np.zeros((rpad * qpad, dim), pts_dtype)))
            rpad *= 2

    # -- synchronous conveniences -------------------------------------------

    def update(self, name: str, nodal_grids) -> jnp.ndarray:
        """Synchronous re-ingest (same scheme: no retrace, no recompile)."""
        fut = self.submit_ingest(name, nodal_grids)
        self.flush()
        return fut.result()

    def query(self, name: str, points) -> np.ndarray:
        """Synchronous point query (one-tenant batch)."""
        fut = self.submit_query(name, points)
        self.flush()
        return fut.result()

    # -- lifecycle: incremental plan paths per tenant -----------------------

    def refit(self, name: str, scheme: SchemeLike, nodal_grids) -> None:
        """Swap tenant ``name`` onto a (refined) scheme through the
        incremental ``extend_plan`` path, re-binding the shared
        executable (a signature-preserving refit recompiles nothing).  A
        failing ingest raises BEFORE any tenant state mutates."""
        tenant = self._tenant(name)
        plan = extend_plan(tenant.plan, scheme, spec=tenant.spec)
        self._commit(tenant, scheme, plan, nodal_grids)

    def extend(self, name: str, new_levels, nodal_grids) -> None:
        """Grow tenant ``name``'s downward-closed index set by
        ``new_levels`` (adaptive-serving convenience over ``refit``)."""
        tenant = self._tenant(name)
        scheme = tenant.scheme
        if not hasattr(scheme, "with_levels"):
            scheme = scheme.as_general()
        self.refit(name, scheme.with_levels(new_levels), nodal_grids)

    def drop_grid(self, name: str, failed, nodal_grids) -> None:
        """Serving-side fault recovery for one tenant: recombine without
        grid(s) ``failed`` (``repro.runtime.fault_tolerance.
        recombine_after_fault`` — coefficient-only when possible, so the
        plan, its slab split and the bound executable are all reused).
        Raises and leaves the tenant unchanged when the reduced scheme
        needs data the caller did not supply."""
        from repro.runtime.fault_tolerance import recombine_after_fault
        tenant = self._tenant(name)
        scheme, plan, _ = recombine_after_fault(tenant.scheme, failed,
                                                plan=tenant.plan)
        self._commit(tenant, scheme, plan, nodal_grids)

    def rebind(self, name: str, *, mesh: Any = _UNSET,
               axis_name: Any = _UNSET, n_slabs: Any = _UNSET,
               member_axis: Any = _UNSET) -> str:
        """Elastic-rebalance fast lane: move tenant ``name`` onto a new
        mesh / slab layout WITHOUT recomputing its surplus.  The base
        plan is re-sharded incrementally (``shard_plan(..., old=)``
        reuses unchanged slab buckets), the signature-shared executable
        is re-bound, and the served surplus carries over unchanged —
        queued queries keep resolving throughout.  Returns what
        happened: ``"kept"`` (spec unchanged), ``"sharded"``,
        ``"resharded"``, ``"unsharded"`` or ``"rebound"``."""
        tenant = self._tenant(name)
        changes = {}
        if mesh is not _UNSET:
            changes["mesh"] = mesh
        if axis_name is not _UNSET:
            changes["axis_name"] = axis_name
        if n_slabs is not _UNSET:
            changes["n_slabs"] = n_slabs
        if member_axis is not _UNSET:
            changes["member_axis"] = member_axis
        new_spec = dataclasses.replace(tenant.spec, **changes) \
            if changes else tenant.spec
        if new_spec == tenant.spec:
            return "kept"
        base = tenant.base_plan
        was_sharded = isinstance(tenant.plan, ShardedPlan)
        if new_spec.slabs > 1 or new_spec.groups > 1:
            plan = shard_plan(base, new_spec.slabs,
                              old=tenant.plan if was_sharded else None,
                              n_groups=new_spec.groups)
            outcome = "resharded" if was_sharded else "sharded"
        else:
            plan = base
            outcome = "unsharded" if was_sharded else "rebound"
        nxt = self._bind(name, tenant.scheme, new_spec, plan)
        nxt.surplus = tenant.surplus          # carried over: no recompute
        nxt.deadline_ms, nxt.priority = tenant.deadline_ms, tenant.priority
        with self._work:
            if self._tenants.get(name) is not tenant:
                raise RuntimeError(
                    f"tenant {name!r} changed during rebind (concurrent "
                    f"refit/unregister) — retry")
            self._tenants[name] = nxt
            self._work_seq += 1
            self._work.notify_all()
        return outcome

    def _commit(self, tenant: _Tenant, scheme: SchemeLike, plan,
                nodal_grids) -> None:
        """Re-bind a tenant onto (scheme, plan) and ingest atomically:
        bind + device work run outside the lock, the record swap is one
        locked step keyed by name (so queued work picks up the NEW
        record at its own dispatch time)."""
        nxt = self._bind(tenant.name, scheme, tenant.spec, plan)
        nxt.deadline_ms, nxt.priority = tenant.deadline_ms, tenant.priority
        surplus = self._dispatch_ingest(nxt, nodal_grids)  # raises first
        jax.block_until_ready(surplus)
        nxt.surplus = surplus
        with self._work:
            if tenant.name not in self._tenants:
                raise KeyError(f"tenant {tenant.name!r} was unregistered "
                               f"during refit")
            self._counters["ingests"] += 1
            self._tenants[tenant.name] = nxt
            self._work_seq += 1
            self._work.notify_all()
        if self._store is not None:
            # the scheme identity changed: refresh the durable meta and
            # snapshot immediately, superseding every WAL entry journaled
            # against the OLD scheme (replaying those through the new
            # plan would fail its grid validation)
            name = tenant.name
            self._store.register(
                name, scheme, full_levels=nxt.base_plan.full_levels,
                deadline_ms=nxt.deadline_ms, priority=nxt.priority)
            with self._lock:
                seq = self._ingest_submitted.get(name, 0)
                tag = self._last_tag.get(name)
            self._snapshot_now(name, seq, tag, surplus, scheme=scheme,
                               full_levels=nxt.base_plan.full_levels)

    # -- durability: snapshot / restore / replay ----------------------------

    def _snapshot_now(self, name: str, seq: int, tag: Optional[int],
                      surplus, *, scheme: SchemeLike,
                      full_levels) -> Optional[str]:
        """Best-effort durable snapshot.  A snapshot that fails (disk
        trouble, the injected crash-mid-snapshot) must never fail the
        serving path: the previous snapshot + the WAL already cover
        every acked ingest, so the failure is recorded and swallowed."""
        if self._store is None:
            return None
        try:
            path = self._store.snapshot(
                name, seq, np.asarray(surplus),
                tag=-1 if tag is None else int(tag),
                scheme=scheme, full_levels=full_levels)
        except Exception as exc:
            self._store.events.append(
                f"{self._host()}: snapshot of tenant {name!r} at seq "
                f"{seq} failed ({exc!r}); previous snapshot + WAL still "
                f"cover all acked ingests")
            return None
        with self._lock:
            if seq > self._snap_seq.get(name, 0):
                self._snap_seq[name] = seq
        return path

    def _maybe_snapshot(self, name: str, seq: int, tag: Optional[int],
                        surplus) -> None:
        """Snapshot when the done watermark advanced ``snapshot_interval``
        past the last snapshot (called by the ingest chain after the
        ack).  The claim on ``_snap_seq`` is taken under the lock so
        concurrent chains of one tenant snapshot once, not once each."""
        if self._store is None or self._snapshot_interval <= 0:
            return
        with self._lock:
            last = self._snap_seq.get(name, 0)
            tenant = self._tenants.get(name)
            if tenant is None or seq - last < self._snapshot_interval:
                return
            self._snap_seq[name] = seq          # claim before the IO
            scheme = tenant.scheme
            full_levels = tenant.base_plan.full_levels
        if self._snapshot_now(name, seq, tag, surplus, scheme=scheme,
                              full_levels=full_levels) is None:
            with self._lock:
                if self._snap_seq.get(name, 0) == seq:
                    self._snap_seq[name] = last     # un-claim: retry later

    def snapshot_tenant(self, name: str, *,
                        tag: Optional[int] = None) -> Optional[str]:
        """Force a durable snapshot of ``name``'s served surplus at the
        current watermark (``None`` without a store / without state).
        The cluster calls this after a failover adoption so the adopting
        host's store covers the adopted state before any new ingest."""
        if self._store is None:
            return None
        with self._lock:
            tenant = self._tenants.get(name)
            if tenant is None or tenant.surplus is None:
                return None
            seq = self._ingest_submitted.get(name, 0)
            if tag is None:
                tag = self._last_tag.get(name)
            scheme = tenant.scheme
            full_levels = tenant.base_plan.full_levels
            surplus = tenant.surplus
        return self._snapshot_now(name, seq, tag, surplus, scheme=scheme,
                                  full_levels=full_levels)

    def restore(self, store: Optional[DurableStore] = None, *,
                specs=None, names=None,
                replay: bool = True) -> Dict[str, RestoreInfo]:
        """Rebuild tenants from a durable store: adopt each tenant's
        newest intact snapshot, then replay the WAL entries newer than
        it through the NORMAL ingest executable — so the restored
        surplus is bit-identical to an engine that never crashed (full-
        dict ingests are last-writer-wins).

        ``specs`` maps tenant name -> ExecSpec (a dict or a callable;
        engine default otherwise) — how a cluster restores each tenant
        onto the host's own device slice.  ``replay=False`` defers the
        WAL replay (phase B) to an explicit ``replay()`` call: the
        cluster uses this to rejoin the ring after the fast snapshot
        adoption and serve stale-marked queries DURING the replay.
        Until ``replay()`` runs, non-stale queries wait on the admitted
        watermark, exactly as they would behind a long ingest queue."""
        store = store if store is not None else self._store
        if store is None:
            raise ValueError("restore: no store attached and none given")
        out: Dict[str, RestoreInfo] = {}
        for name in store.tenants():
            if names is not None and name not in names:
                continue
            t0 = time.monotonic()
            state = store.load(name)
            if callable(specs):
                spec = specs(name)
            elif isinstance(specs, dict):
                spec = specs.get(name)
            else:
                spec = None
            spec = spec or self._default_spec
            plan = build_plan(state.scheme, state.full_levels, spec=spec)
            self.register(
                name, state.scheme, spec=spec, plan=plan,
                surplus=(None if state.surplus is None
                         else jnp.asarray(state.surplus)),
                deadline_ms=state.deadline_ms, priority=state.priority,
                durable=False)      # its durable state IS this store
            with self._work:
                base = max(state.max_seq,
                           self._ingest_submitted.get(name, 0))
                self._ingest_submitted[name] = base
                self._ingest_done[name] = \
                    max(state.snapshot_seq, self._ingest_done.get(name, 0))
                self._snap_seq[name] = state.snapshot_seq
                if state.max_tag >= 0:
                    self._last_tag[name] = state.max_tag
                tenant = self._tenants[name]
                tenant.surplus_seq = state.snapshot_seq
                if state.entries:
                    self._replay_pending[name] = list(state.entries)
                self._work_seq += 1
                self._work.notify_all()
            restore_s = time.monotonic() - t0
            out[name] = RestoreInfo(
                name=name, snapshot_seq=state.snapshot_seq,
                base_seq=state.max_seq, tag=state.max_tag,
                snapshot_tag=state.snapshot_tag,
                pending=len(state.entries), replayed=0,
                restore_s=restore_s, replay_s=0.0,
                events=tuple(state.events))
        if replay:
            replayed = self.replay(
                names=list(out) if names is None else list(names))
            for name, r in replayed.items():
                if name in out:
                    out[name] = dataclasses.replace(
                        out[name], replayed=r["replayed"],
                        replay_s=r["seconds"])
        return out

    def replay(self, names=None) -> Dict[str, Dict[str, Any]]:
        """Apply the deferred WAL entries of ``restore(replay=False)``
        through the normal ingest executable, advancing the done
        watermark per entry (newest-seq-wins against any LIVE ingest
        submitted after the rejoin — replay never clobbers newer
        state)."""
        if names is None:
            with self._lock:
                names = list(self._replay_pending)
        out: Dict[str, Dict[str, Any]] = {}
        for name in names:
            with self._lock:
                entries = self._replay_pending.pop(name, [])
            t0 = time.monotonic()
            applied, skipped, last_tag = 0, 0, None
            for e in entries:
                with self._lock:
                    tenant = self._tenants.get(name)
                if tenant is None:
                    break               # unregistered mid-replay: moot
                surplus = self._dispatch_ingest(tenant, e.grids)
                jax.block_until_ready(surplus)
                if self._check_finite and not bool(_FINITE_CHECK(surplus)):
                    # a poisoned ingest journaled at admission (the crash
                    # raced the device-side finiteness check): its live
                    # submission would have FAILED, so replay must not
                    # commit it either — skip, advance the watermark so
                    # waiters don't hang, keep the previous surplus
                    with self._work:
                        if e.seq > self._ingest_done.get(name, 0):
                            self._ingest_done[name] = e.seq
                        self._work_seq += 1
                        self._work.notify_all()
                    skipped += 1
                    continue
                with self._work:
                    cur = self._tenants.get(name)
                    if cur is not None and e.seq >= cur.surplus_seq:
                        cur.surplus = surplus
                        cur.surplus_seq = e.seq
                    if e.seq > self._ingest_done.get(name, 0):
                        self._ingest_done[name] = e.seq
                    if e.tag >= 0:
                        self._last_tag[name] = e.tag
                    self._counters["ingests"] += 1
                    self._work_seq += 1
                    self._work.notify_all()
                applied += 1
                if e.tag >= 0:
                    last_tag = e.tag
            out[name] = {"replayed": applied, "skipped": skipped,
                         "seconds": time.monotonic() - t0,
                         "last_tag": last_tag}
        return out

    @property
    def store(self) -> Optional[DurableStore]:
        return self._store

    # -- accounting ---------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Aggregated serving statistics: the ingest count, the shared
        compile-cache counters, how each ingest was fed (``packed`` or
        ``per_part``), the continuous-batching eval counters,
        the scheduler's dispatch/backpressure accounting and the durable
        store's."""
        with self._lock:
            tenants = dict(self._tenants)
            counters = dict(self._counters)
            sched = dict(self._sched)
            pending = len(self._pending)
        with self._feed_lock:
            feed = dict(self._feed)
        # count over the LIVE tenants' executables (dedup by identity) —
        # an executable evicted from the LRU cache keeps serving its
        # tenants and must keep being counted
        uniq = {id(t.executable): t.executable for t in tenants.values()}
        jit_entries = sum(f._cache_size() for f in uniq.values())
        with _INGEST_CACHE_LOCK:
            cache_entries = len(_INGEST_EXECUTABLES)
        return {
            "host_id": self.host_id,
            "tenants": len(tenants),
            "ingests": counters["ingests"],
            "ingest_cache": {
                "entries": cache_entries,
                "hits": counters["cache_hits"],
                "misses": counters["cache_misses"],
                "jit_entries": jit_entries,
            },
            "ingest_feed": feed,
            "eval": {
                "queries": counters["queries"],
                "batches": counters["eval_batches"],
                "surplus_evals": counters["surplus_evals"],
                "coalesced_queries": counters["coalesced_queries"],
                "compiles": _EVAL_BATCHED._cache_size(),
            },
            "scheduler": {
                "pending": pending,
                "max_batch": self._max_batch,
                "max_pending": self._max_pending,
                "deadline_ms": self._deadline_ms,
                **sched,
            },
            "durability": (None if self._store is None else {
                "snapshot_interval": self._snapshot_interval,
                "replay_pending": {n: len(v) for n, v
                                   in self._replay_pending.items()},
                **self._store.stats(),
            }),
        }
