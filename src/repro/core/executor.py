"""Batched combination-technique executor.

The dict-based communication phase (``repro.core.combination``) walks a
Python dict of component grids and dispatches one hierarchization and one
embed per grid — for a d=10 scheme that is hundreds of dispatches per
combination step, none of which fuse.  This module replaces that with a
fixed, precomputed execution plan so the whole CT transform is ONE jitted
function:

  1. **Bucketing** — component grids are grouped by canonical shape:
     hierarchization is a tensor-product operator, so any grid can be
     transposed to descending-level axis order without changing the
     transform; all axis-permutations of one level multiset therefore
     share a bucket (e.g. d=10, |ell|=12 has 55 grids but 2 buckets).

  2. **Cost-model-driven bucket merging** (opt-in via
     ``build_plan(..., merge=MergeConfig(...))``) — near-shape buckets
     are merged into padded SUPER-buckets when a static cost model says
     the saved kernel-launch overhead outweighs the pad-waste HBM bytes.
     Members below the merged target use the kernel machinery built for
     exactly this: zero-padding to the common ``2**l - 1`` extents,
     per-member level vectors that mask every ancestor outside the
     member's own pole (so padded members transform exactly as their
     unpadded selves), and index-map routing of every pad position to a
     dump slot.  The planner picks
     the OPTIMAL CONTIGUOUS partition (interval DP) of the descending-
     sorted shape sequence; contiguity preserves the global member
     order, which is what keeps merged results bit-identical to the
     unmerged plan.  The merge decision is part of the plan (and of the
     ``build_plan`` cache key) and survives ``extend_plan`` /
     ``update_plan_coefficients`` / ``shard_plan``.

  3. **Batched hierarchization** — each bucket runs the Pallas kernels
     ONCE with the member index as the leading Pallas grid dimension
     (``repro.kernels.hierarchize.hierarchize_batched``): kernel
     launches scale with the number of (super-)buckets, not grids.

  4. **Static index plan** — the per-subspace gather/scatter dict is
     replaced by a per-bucket ``(G, P)`` int32 index map into the
     flattened common fine grid, precomputed from the scheme (embed
     offsets ``(j+1) * 2**(L-l) - 1`` and row strides, pad positions
     pointing at a dump slot).  The gather is one coefficient-weighted
     ``.at[idx].add`` per bucket, in bucket order (a per-slot left fold,
     which is what keeps merged and sharded results bit-identical); the
     scatter step is the same map read in reverse (``take``).

  5. **Compact gather** (one device) — the buckets accumulate into a
     compact vector over the sparse grid's distinct fine points
     (``ExecutorPlan.compact``: each bucket's map into it, and its map
     into the fine grid), which is then written into the fine grid by
     one scatter of unique, sorted indices.  Every point still receives
     its additions in bucket order from 0, so on the CPU the result is
     bitwise the per-bucket gather into the fine buffer (within a
     bucket, the order of a scatter's duplicate indices is XLA's).

``ct_transform`` / ``ct_scatter`` are end-to-end jittable (scheme static);
a meshed ``ExecSpec`` routes ``ct_transform`` through the slab-sharded
gather (``repro.core.distributed.ct_transform_sharded``).  Every entry
point takes its execution policy as one ``spec=repro.core.engine.
ExecSpec``.  Schemes are duck-typed: the classical ``CombinationScheme``
and the downward-closed ``GeneralScheme`` (adaptive / fault-reduced
index sets) both work everywhere.

**Incremental-rebuild contract** (the adaptive/fault hot path):

  * ``build_plan(scheme, full_levels)`` normalizes ``full_levels`` BEFORE
    the lru_cache key is formed, so the bare call and an explicit
    ``full_levels=fine_levels(scheme)`` share one cache entry.
  * ``extend_plan(old_plan, new_scheme)`` rebuilds only the buckets whose
    member list changed.  Untouched buckets are returned BY IDENTITY
    (``new.buckets[i] is old.buckets[j]``); buckets whose members are
    unchanged but whose coefficients moved share the old ``index`` array by
    identity; only genuinely new members get a fresh index-map row.  The
    result is bit-identical to a from-scratch ``build_plan(new_scheme)``
    provided ``fine_levels(new_scheme)`` still equals the old plan's
    ``full_levels`` — otherwise every embed index is stale and
    ``extend_plan`` transparently falls back to a full rebuild.
  * ``update_plan_coefficients(plan, scheme)`` is the coefficient-ONLY
    update (grid dropped -> inclusion-exclusion coefficients recomputed,
    every bucket and index map kept): members absent from ``scheme`` get
    coefficient 0, so their (stale, but finite) data cancels out of the
    gather.  The fault-tolerance hook
    (``repro.runtime.fault_tolerance.recombine_after_fault``) prefers this
    path and falls back to ``extend_plan`` when the reduced scheme
    activates a grid the plan never contained.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from dataclasses import dataclass
from typing import Dict, Mapping, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.analysis import lockdep as _lockdep

from repro.core.levels import (LevelVector, SchemeLike, canonical_levels,
                               fine_levels, grid_shape)
from repro.kernels.hierarchize import (batched_method, dehierarchize_batched,
                                       hierarchize_batched, tile_volume)

__all__ = ["ExecutorPlan", "Bucket", "ShardedPlan", "SlabBucket",
           "MergeConfig", "build_plan", "shard_plan", "extend_plan",
           "update_plan_coefficients", "ct_transform", "ct_scatter",
           "ct_transform_with_plan", "ct_scatter_with_plan",
           "bucket_surpluses", "bucket_nodal_stacks",
           "plan_launch_stats", "plan_ingest_stats", "clear_plan_cache"]


def ensure_spec(fn_name: str, spec):
    """The spec an entry point runs under: ``spec`` itself, or
    ``ExecSpec()`` for ``None``.  A named ``TypeError`` when ``spec=``
    receives a non-ExecSpec — e.g. a POSITIONAL caller whose third
    argument (``CTSurrogate(scheme, grids, True)``) lands in ``spec``
    and would otherwise die on an opaque attribute error deep inside
    plan construction."""
    from repro.core.engine import ExecSpec
    if spec is None:
        return ExecSpec()
    if not isinstance(spec, ExecSpec):
        raise TypeError(
            f"{fn_name}: spec must be a repro.core.engine.ExecSpec, got "
            f"{type(spec).__name__}; execution options are ExecSpec "
            f"fields, e.g. spec=ExecSpec(interpret=...)")
    return spec


@dataclass(frozen=True)
class Bucket:
    """One batch of component grids sharing a canonical (padded) shape."""

    ells: Tuple[LevelVector, ...]        # original level vectors
    perms: Tuple[Tuple[int, ...], ...]   # canon axis k <- original axis perm[k]
    levels: Tuple[LevelVector, ...]      # canonicalized member level vectors
    target: LevelVector                  # componentwise max over members
    coeffs: np.ndarray                   # (G,) combination coefficients
    index: np.ndarray                    # (G, P) int32 flat fine indices

    @property
    def shape(self) -> Tuple[int, ...]:
        return grid_shape(self.target)


@dataclass(frozen=True)
class ExecutorPlan:
    """Precomputed static execution plan for one scheme's comm phase.

    ``merge`` records the bucket-merging cost model the plan was built
    with (``None`` = one bucket per canonical shape); incremental rebuilds
    (``extend_plan`` / ``update_plan_coefficients``) re-apply it, so a
    merged plan stays merged through adaptive refinement and fault
    recombination."""

    dim: int
    full_levels: LevelVector
    fine_shape: Tuple[int, ...]
    buckets: Tuple[Bucket, ...]
    merge: Optional[MergeConfig] = None

    @property
    def fine_size(self) -> int:
        return int(np.prod(self.fine_shape))

    @property
    def num_grids(self) -> int:
        return sum(len(b.ells) for b in self.buckets)

    @functools.cached_property
    def compact(self) -> "CompactMaps":
        """The single-device gather's maps, built once per plan."""
        real = np.concatenate([b.index.ravel() for b in self.buckets])
        fine = np.unique(real[real < self.fine_size]).astype(np.int32)
        return CompactMaps(
            fine=fine,
            buckets=tuple(np.searchsorted(fine, b.index).astype(np.int32)
                          for b in self.buckets))


@dataclass(frozen=True)
class CompactMaps:
    """The sparse grid's N distinct fine points, as the compact gather
    addresses them: ``fine`` (N,) int32, sorted and unique, is each
    compact slot's flat fine index; ``buckets[i]`` has bucket i's
    ``index`` shape and gives each position's compact slot, pads the
    dump slot N.  N follows from the plan's member level vectors."""

    fine: np.ndarray
    buckets: Tuple[np.ndarray, ...]

    @property
    def size(self) -> int:
        return len(self.fine)


@dataclass(frozen=True)
class SlabBucket:
    """Per-slab split of one bucket's embed index map.

    The fine grid is partitioned into ``n_slabs`` contiguous slabs along
    its LEADING axis (``slab_rows`` rows each, the last one ragged when
    ``fine_shape[0] % n_slabs != 0``).  For slab ``s``:

    * ``index[s]`` — the bucket's ``(G, P)`` index map rewritten in
      slab-LOCAL flat coordinates: entries landing in slab ``s`` hold
      ``global - s * slab_rows * row_size``; every other entry (including
      the base map's pad positions) points at the slab dump slot
      ``slab_size``.  Each global index therefore lands in exactly one
      slab, so summing the per-slab scatter-adds reproduces the dense
      gather bit-for-bit (addition order per slot is preserved).
    * ``row_ranges[s, g]`` — the half-open range ``[start, stop)`` of
      member ``g``'s nodes along the ORIGINAL leading axis whose embedded
      rows fall in slab ``s`` (embedding is monotone per axis, so the set
      is contiguous).  This is the metadata a multi-controller deployment
      uses to ship only the relevant surplus rows to each group.

    When the plan is additionally COMPUTE-sharded over ``n_groups``
    member groups (the 2-D (member x slab) mesh ingest,
    ``repro.core.distributed.gather_slab_scatter_2d``), the bucket also
    carries the row-range-derived surplus SHIPPING maps — the flat
    realization of what ``row_ranges`` describes per member:

    * ``group_size`` — members per group (``ceil(G / n_groups)``; the
      stack is zero-padded at the tail to ``n_groups * group_size``
      rows, pad members carrying coefficient 0).
    * ``ship_src[i, s]`` — int32 gather indices into group i's LOCAL
      flattened weighted-surplus buffer (``group_size * P`` values plus
      one trailing zero slot): the payload group i ships to slab s,
      ordered by (member, position).  Pad entries read the zero slot.
    * ``ship_idx[s, i]`` — int32 slab-LOCAL scatter targets of exactly
      those values on the receiving side; pad entries point at the slab
      dump slot ``slab_size``.  Concatenating the payloads over i in
      group order replays the base map's global (g, p) scatter order
      restricted to slab s, so the slab owner's single ordered
      scatter-add over ALL groups' payloads reproduces the dense
      gather's per-slot left fold bit-for-bit.
    """

    index: np.ndarray        # (S, G, P) int32 slab-local indices
    row_ranges: np.ndarray   # (S, G, 2) int32 node ranges [start, stop)
    ship_src: Optional[np.ndarray] = None   # (n_groups, S, L) int32
    ship_idx: Optional[np.ndarray] = None   # (S, n_groups, L) int32
    group_size: int = 0                     # members per group (padded)


@dataclass(frozen=True)
class ShardedPlan:
    """Slab-sharded view of an ``ExecutorPlan``: the same buckets and
    coefficients, plus per-slab index maps so each of ``n_slabs`` device
    groups scatter-adds only into its own ``~fine_size / n_slabs`` slab
    of the fine grid (``repro.core.distributed.gather_slab_scatter``).

    ``plan`` is the unsharded base plan (shared by identity where
    possible); ``extend_plan`` / ``update_plan_coefficients`` accept a
    ``ShardedPlan`` directly and re-shard incrementally, so the adaptive
    and fault paths work unchanged on sharded plans.
    """

    plan: ExecutorPlan
    n_slabs: int
    slab_rows: int                        # ceil(fine_shape[0] / n_slabs)
    slab_buckets: Tuple[SlabBucket, ...]
    #: compute-shard group count of the 2-D (member x slab) mesh ingest:
    #: 1 = hierarchization replicated (the classic slab-only sharding);
    #: > 1 = each of ``n_groups`` device groups hierarchizes only its
    #: member shard and ships surpluses via the per-bucket ship maps.
    n_groups: int = 1

    @property
    def row_size(self) -> int:
        return int(np.prod(self.plan.fine_shape[1:], dtype=np.int64))

    @property
    def slab_size(self) -> int:
        return self.slab_rows * self.row_size

    # -- ExecutorPlan surface the fault/adaptive callers read --
    @property
    def dim(self) -> int:
        return self.plan.dim

    @property
    def full_levels(self) -> LevelVector:
        return self.plan.full_levels

    @property
    def fine_shape(self) -> Tuple[int, ...]:
        return self.plan.fine_shape

    @property
    def fine_size(self) -> int:
        return self.plan.fine_size

    @property
    def buckets(self) -> Tuple[Bucket, ...]:
        return self.plan.buckets

    @property
    def merge(self) -> Optional["MergeConfig"]:
        return self.plan.merge

    @property
    def num_grids(self) -> int:
        return self.plan.num_grids


def _group_ship_maps(index: np.ndarray, n_groups: int,
                     slab_size: int) -> tuple:
    """Surplus shipping maps of one bucket for the 2-D mesh ingest.

    Group i owns the contiguous member rows ``[i*gs, (i+1)*gs)`` of the
    bucket's compact ``(G, P)`` stack (``gs = ceil(G / n_groups)``).
    From the per-slab local maps ``index`` (S, G, P), build for every
    (destination slab s, source group i) the flat payload — group i's
    surplus positions landing in slab s, ordered by (member, position) —
    as a gather map into the group's local flattened stack plus the
    matching slab-local scatter targets, both padded to the bucket-wide
    max payload length (see ``SlabBucket`` for the full contract)."""
    n_slabs, g_total, p = index.shape
    gs = -(-g_total // n_groups)
    srcs, dsts = {}, {}
    pay_len = 1
    for s in range(n_slabs):
        for i in range(n_groups):
            loc = index[s, i * gs:(i + 1) * gs]        # (<=gs, P)
            gg, pp = np.nonzero(loc != slab_size)      # (member, pos) order
            srcs[s, i] = gg.astype(np.int64) * p + pp
            dsts[s, i] = loc[gg, pp]
            pay_len = max(pay_len, gg.size)
    zero_slot = gs * p
    ship_src = np.full((n_groups, n_slabs, pay_len), zero_slot, np.int32)
    ship_idx = np.full((n_slabs, n_groups, pay_len), slab_size, np.int32)
    for (s, i), src in srcs.items():
        ship_src[i, s, :src.size] = src
        ship_idx[s, i, :src.size] = dsts[s, i]
    return ship_src, ship_idx, gs


def _shard_bucket(bucket: Bucket, full_levels: LevelVector, n_slabs: int,
                  slab_rows: int, row_size: int,
                  n_groups: int = 1) -> SlabBucket:
    """Split one bucket's index map into per-slab local maps + row ranges
    (+ the member-group shipping maps when compute-sharded)."""
    n0 = (1 << full_levels[0]) - 1
    slab_size = slab_rows * row_size
    g = bucket.index.astype(np.int64)             # (G, P); dump == fine_size
    row = g // row_size                           # dump maps to row n0
    index = np.empty((n_slabs,) + g.shape, np.int32)
    ranges = np.zeros((n_slabs, g.shape[0], 2), np.int32)
    for s in range(n_slabs):
        lo, hi = s * slab_rows, min((s + 1) * slab_rows, n0)
        in_slab = (row >= lo) & (row < hi)
        index[s] = np.where(in_slab, g - lo * row_size, slab_size)
    for gi, ell in enumerate(bucket.ells):
        step = 1 << (full_levels[0] - ell[0])
        rows = (np.arange((1 << ell[0]) - 1) + 1) * step - 1
        for s in range(n_slabs):
            lo, hi = s * slab_rows, min((s + 1) * slab_rows, n0)
            hit = np.nonzero((rows >= lo) & (rows < hi))[0]
            if hit.size:
                ranges[s, gi] = (hit[0], hit[-1] + 1)
    if n_groups == 1:
        return SlabBucket(index=index, row_ranges=ranges)
    ship_src, ship_idx, gs = _group_ship_maps(index, n_groups, slab_size)
    return SlabBucket(index=index, row_ranges=ranges, ship_src=ship_src,
                      ship_idx=ship_idx, group_size=gs)


def shard_plan(plan: ExecutorPlan, n_slabs: Optional[int] = None,
               old: Optional["ShardedPlan"] = None, *,
               spec=None, n_groups: Optional[int] = None) -> ShardedPlan:
    """Slab-shard a plan for ``n_slabs`` device groups (and optionally
    compute-shard it over ``n_groups`` member groups for the 2-D
    (member x slab) mesh ingest).

    ``old`` (a prior sharding, e.g. before an incremental rebuild) lets
    buckets whose base ``index`` array survived BY IDENTITY reuse their
    slab split unchanged — the sharded analogue of ``extend_plan``'s
    bucket reuse.  ``n_slabs`` may instead come from a
    ``repro.core.engine.ExecSpec`` (``spec.slabs``: an explicit
    ``n_slabs`` field, else the mesh axis extent; ``spec.groups``
    supplies ``n_groups`` for a member-meshed spec).
    """
    if spec is not None:
        ensure_spec("shard_plan", spec)
        if n_slabs is not None:
            raise ValueError("shard_plan: pass n_slabs or spec, not both")
        n_slabs = spec.slabs
        if n_groups is None:
            n_groups = spec.groups
    if n_slabs is None:
        raise ValueError("shard_plan: n_slabs (or a sharded spec) required")
    if isinstance(plan, ShardedPlan):
        raise TypeError("shard_plan expects the unsharded base plan")
    if n_slabs < 1:
        raise ValueError(f"n_slabs must be >= 1, got {n_slabs}")
    n_groups = 1 if n_groups is None else int(n_groups)
    if n_groups < 1:
        raise ValueError(f"n_groups must be >= 1, got {n_groups}")
    n0 = plan.fine_shape[0]
    row_size = int(np.prod(plan.fine_shape[1:], dtype=np.int64))
    slab_rows = -(-n0 // n_slabs)
    reuse = {}
    if old is not None:
        # Identity reuse is only sound when the SLAB GEOMETRY (and the
        # member-group count) is unchanged: a surviving base ``index``
        # array proves the bucket's EMBED map did not change, but the
        # per-slab local maps additionally bake in slab_rows/row_size
        # (and ship maps bake in n_groups).  A refinement that grows
        # fine_shape[0] past ``n_slabs * slab_rows`` — any full_levels
        # change — moves the slab boundaries, so reusing the old split
        # would scatter through STALE slab offsets; fall back to a full
        # re-shard instead.
        same_geometry = (old.n_slabs == n_slabs
                         and old.n_groups == n_groups
                         and old.slab_rows == slab_rows
                         and old.row_size == row_size
                         and old.plan.full_levels == plan.full_levels)
        if same_geometry:
            reuse = {id(b.index): sb
                     for b, sb in zip(old.plan.buckets, old.slab_buckets)}
    slab_buckets = tuple(
        reuse.get(id(b.index)) or _shard_bucket(b, plan.full_levels, n_slabs,
                                                slab_rows, row_size, n_groups)
        for b in plan.buckets)
    return ShardedPlan(plan=plan, n_slabs=n_slabs, slab_rows=slab_rows,
                       slab_buckets=slab_buckets, n_groups=n_groups)


@dataclass(frozen=True)
class MergeConfig:
    """Static cost model for merging near-shape buckets into padded
    super-buckets.

    Hierarchization is memory-bound (the paper's central claim), so both
    sides of the trade are priced in HBM bytes:

    * each bucket costs a fixed dispatch overhead per kernel launch —
      ``launch_cost_bytes`` is one launch expressed as the HBM bytes the
      bus could have moved instead (TPU dispatch ~1-2us at ~800 GB/s is
      ~1-2 MiB; the default is deliberately on the low side of that);
    * merging pads every member to the super-bucket target, so each
      transform moves ``round_trips`` copies of the PADDED member volume
      through HBM (2 batched launches x read+write; Pallas buckets are
      priced at the sublane/lane TILE volume they actually transfer,
      jnp-path buckets at the raw volume).

    ``max_members`` optionally caps super-bucket size (bounds the padded
    assembly buffer).  Hashable, so the merge decision can live in the
    ``build_plan`` lru_cache key and in the plan itself.
    """

    launch_cost_bytes: int = 1 << 20
    round_trips: int = 4
    dtype_bytes: int = 8
    max_members: Optional[int] = None


def _bucket_cost(target: LevelVector, n_members: int,
                 merge: MergeConfig) -> float:
    """Modelled HBM cost of one bucket: launch overhead + member traffic.

    Mirrors ``plan_launch_stats``: a Pallas bucket dispatches tail +
    axis-0 (one launch when 1-D), a jnp bucket one pass per axis; every
    bucket then pays its XLA scatter dispatch and the compact-stack
    write+read round trip."""
    shape = grid_shape(target)
    p = int(np.prod(shape, dtype=np.int64))
    if batched_method(shape) == "pallas":
        launches, vol = (1 if len(shape) == 1 else 2), tile_volume(shape)
    else:
        launches, vol = len(shape), p
    return ((launches + 1) * merge.launch_cost_bytes
            + merge.round_trips * n_members * vol * merge.dtype_bytes
            + 2 * n_members * p * merge.dtype_bytes)


def _merge_partition(keys: Sequence[LevelVector],
                     sizes: Sequence[int],
                     merge: MergeConfig) -> Tuple[Tuple[int, int], ...]:
    """Optimal contiguous partition of the descending-sorted canonical
    keys into super-buckets, as half-open index segments ``(i, j)``.

    Contiguity is load-bearing, not a shortcut: scatter-adds run bucket
    by bucket in sorted order, so only merges of ADJACENT runs keep the
    global member order — and with it bit-identical results — intact.
    Adjacent keys are also the near-shape candidates (sorted neighbors
    differ in few axis levels).  The interval DP is exact under the cost
    model and O(B^2) in the bucket count.
    """
    n = len(keys)
    d = len(keys[0]) if n else 0
    # componentwise-max targets and member counts of every prefix i..j
    best = [0.0] * (n + 1)
    cut = [0] * (n + 1)
    for j in range(1, n + 1):
        best[j] = float("inf")
        target = list(keys[j - 1])
        members = 0
        for i in range(j - 1, -1, -1):
            for k in range(d):
                if keys[i][k] > target[k]:
                    target[k] = keys[i][k]
            members += sizes[i]
            if merge.max_members is not None and members > merge.max_members \
                    and j - i > 1:
                break
            c = best[i] + _bucket_cost(tuple(target), members, merge)
            if c < best[j]:
                best[j], cut[j] = c, i
    segments = []
    j = n
    while j > 0:
        segments.append((cut[j], j))
        j = cut[j]
    return tuple(reversed(segments))


def _member_index_map(ell: LevelVector, perm: Tuple[int, ...],
                      target: LevelVector, full_levels: LevelVector,
                      fine_strides: np.ndarray, dump: int) -> np.ndarray:
    """Flat fine-grid index for every position of the padded canonical
    member array; pad positions map to the dump slot past the buffer.

    Node j (0-based) of a level-l axis embeds at fine index
    ``(j + 1) * 2**(L - l) - 1`` — the strided write of ``embed_to_full``,
    expressed as a gather/scatter index map instead of a slice.
    """
    d = len(target)
    shape = grid_shape(target)
    idx = np.zeros(shape, np.int64)
    bad = np.zeros(shape, bool)
    for k in range(d):
        a = perm[k]                       # original axis this canon axis is
        l, big = ell[a], full_levels[a]
        n = (1 << l) - 1
        j = np.arange(shape[k])
        v = np.where(j < n, (j + 1) * (1 << (big - l)) - 1, 0)
        bc = [1] * d
        bc[k] = shape[k]
        idx += (v * fine_strides[a]).reshape(bc)
        bad |= (j >= n).reshape(bc)
    return np.where(bad, dump, idx).astype(np.int32).ravel()


def _fine_strides(fine_shape: Tuple[int, ...]) -> np.ndarray:
    strides = np.ones(len(fine_shape), np.int64)
    for a in range(len(fine_shape) - 2, -1, -1):
        strides[a] = strides[a + 1] * fine_shape[a + 1]
    return strides


def _group_members(scheme: SchemeLike) -> Dict[LevelVector, list]:
    """Group (ell, perm, canon, coeff) member records by canonical key."""
    groups: Dict[LevelVector, list] = {}
    for ell, c in scheme.grids:
        canon, perm = canonical_levels(ell)
        groups.setdefault(canon, []).append((ell, perm, canon, c))
    return groups


def _segment_member_lists(groups: Dict[LevelVector, list],
                          merge: Optional[MergeConfig]) -> list:
    """Deterministic bucket member lists: canonical groups in descending
    key order, optionally merged into contiguous super-bucket segments.
    Single construction site for ``build_plan`` and ``extend_plan`` — the
    same groups and ``merge`` always give the same partition and the same
    member order, which is what makes incremental rebuilds bit-identical
    to from-scratch builds."""
    keys = sorted(groups, reverse=True)
    if merge is None:
        return [list(groups[k]) for k in keys]
    segments = _merge_partition(keys, [len(groups[k]) for k in keys], merge)
    return [[m for k in keys[i:j] for m in groups[k]]
            for i, j in segments]


def _make_bucket(members: list, full_levels: LevelVector,
                 fine_strides: np.ndarray, fine_size: int,
                 old_rows: Optional[Dict[LevelVector, np.ndarray]] = None
                 ) -> Bucket:
    """Build one bucket from its member records; ``old_rows`` maps member
    level vectors to index-map rows an incremental rebuild may reuse
    instead of recomputing — the caller guarantees they were built for
    THIS bucket's target shape.  Single construction site, so
    ``build_plan`` and ``extend_plan`` cannot drift apart."""
    target = tuple(max(lv[k] for _, _, lv, _ in members)
                   for k in range(len(full_levels)))
    old_rows = old_rows or {}
    index = np.stack([
        old_rows[ell] if ell in old_rows else
        _member_index_map(ell, perm, target, full_levels, fine_strides,
                          dump=fine_size)
        for ell, perm, _, _ in members])
    return Bucket(
        ells=tuple(m[0] for m in members),
        perms=tuple(m[1] for m in members),
        levels=tuple(m[2] for m in members),
        target=target,
        coeffs=np.asarray([float(m[3]) for m in members]),
        index=index)


def build_plan(scheme: SchemeLike,
               full_levels: Optional[Sequence[int]] = None, *,
               merge: Optional[MergeConfig] = None,
               spec=None) -> ExecutorPlan:
    """Bucket (and optionally merge-plan) the scheme's grids and
    precompute the embed index plan.

    ``full_levels`` is normalized (``None`` -> ``fine_levels(scheme)``,
    sequences -> int tuple) BEFORE the cache key is formed, so equivalent
    calls share one lru_cache entry; ``merge`` (the bucket-merging cost
    model, hashable) is part of the key — merged and unmerged plans of
    one scheme coexist in the cache.  ``spec`` (a ``repro.core.engine.
    ExecSpec``) supplies ``merge`` instead — and, when the spec is
    sharded, makes this return the slab-sharded ``ShardedPlan`` directly
    (``build_plan(scheme, spec=spec)`` is the one-call plan constructor
    of the consolidated API).
    """
    if spec is not None:
        ensure_spec("build_plan", spec)
        if merge is not None:
            raise ValueError("build_plan: pass merge or spec, not both")
        merge = spec.merge
    if full_levels is None:
        full_levels = fine_levels(scheme)
    plan = _build_plan_cached(scheme, tuple(int(l) for l in full_levels),
                              merge)
    if spec is not None and (spec.slabs > 1 or spec.groups > 1):
        plan = shard_plan(plan, spec.slabs, n_groups=spec.groups)
    return plan


class _PlanCache:
    """Thread-safe LRU plan cache (replaces the old module-global
    ``functools.lru_cache``).

    Two properties the lru_cache could not give:

    * an explicit, exported ``clear_plan_cache()`` — tests and benchmarks
      that build many throwaway schemes no longer pin up to 64 plans'
      index maps for process lifetime;
    * a key/value contract: keys are ``(scheme, full_levels, merge)`` and
      values are host-side ``ExecutorPlan``s (numpy index maps only).
      Meshes, ``ExecSpec``s and slab-sharded plans NEVER enter the cache
      (``build_plan`` re-shards the cached base plan per call), so a
      retired device mesh is never kept alive by the plan cache — the
      old failure mode was a meshed caller pinning mesh refs and their
      device buffers until 64 other plans aged the entry out.

    Concurrent misses on one key may both build; the first insert wins so
    callers keep getting ONE object per key (identity reuse is load-
    bearing for ``extend_plan``'s incremental path).
    """

    def __init__(self, maxsize: int):
        self._data: "collections.OrderedDict" = collections.OrderedDict()
        self._lock = _lockdep.make_lock("plan-cache")
        self._maxsize = maxsize

    def get(self, key):
        with self._lock:
            val = self._data.get(key)
            if val is not None:
                self._data.move_to_end(key)
            return val

    def put(self, key, value):
        """Insert-if-absent; returns the winning (cached) value."""
        with self._lock:
            have = self._data.get(key)
            if have is not None:
                self._data.move_to_end(key)
                return have
            self._data[key] = value
            while len(self._data) > self._maxsize:
                self._data.popitem(last=False)
            return value

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)

    def keys(self):
        with self._lock:
            return list(self._data)

    def clear(self) -> None:
        with self._lock:
            self._data.clear()


_PLAN_CACHE = _PlanCache(maxsize=64)


def clear_plan_cache() -> None:
    """Drop every cached executor plan (tests / benchmarks).

    The plan cache holds host-side numpy index maps only — but a test or
    benchmark sweeping many schemes can still pin tens of MB of index
    maps; clear between sweeps to keep memory measurements honest."""
    _PLAN_CACHE.clear()


def _build_plan_cached(scheme: SchemeLike, full_levels: LevelVector,
                       merge: Optional[MergeConfig]) -> ExecutorPlan:
    key = (scheme, full_levels, merge)
    plan = _PLAN_CACHE.get(key)
    if plan is not None:
        return plan
    return _PLAN_CACHE.put(key, _build_plan_uncached(scheme, full_levels,
                                                     merge))


def _build_plan_uncached(scheme: SchemeLike, full_levels: LevelVector,
                         merge: Optional[MergeConfig]) -> ExecutorPlan:
    fine_shape = grid_shape(full_levels)
    fine_size = int(np.prod(fine_shape))
    fine_strides = _fine_strides(fine_shape)

    member_lists = _segment_member_lists(_group_members(scheme), merge)
    buckets = tuple(_make_bucket(members, full_levels, fine_strides,
                                 fine_size)
                    for members in member_lists)
    return ExecutorPlan(dim=scheme.dim, full_levels=full_levels,
                        fine_shape=fine_shape, buckets=buckets, merge=merge)


def extend_plan(plan: ExecutorPlan, scheme: SchemeLike,
                full_levels: Optional[Sequence[int]] = None, *,
                spec=None) -> ExecutorPlan:
    """Incremental plan rebuild after the scheme's index set changed.

    Produces exactly ``build_plan(scheme, full_levels, merge=plan.merge)``
    but reuses the old plan wherever possible: buckets with an unchanged
    member list AND unchanged coefficients are returned by object identity;
    buckets whose members are unchanged but whose inclusion-exclusion
    coefficients moved keep their ``index`` array by identity; buckets
    gaining (or losing) members recompute index-map rows only for members
    the old plan never held.  The merge partition is re-planned from the
    new scheme's groups (the cost model is deterministic, so unchanged
    groups re-partition identically).  Falls back to a full (cached)
    ``build_plan`` when the fine grid itself changed, since then every
    embed index is stale.
    """
    if spec is not None:
        ensure_spec("extend_plan", spec)
        plan_slabs = plan.n_slabs if isinstance(plan, ShardedPlan) else 1
        if (spec.n_slabs is not None or spec.mesh is not None) \
                and spec.slabs != plan_slabs:
            raise ValueError(
                f"extend_plan: spec requests {spec.slabs} slab(s) but the "
                f"plan is sharded for {plan_slabs}; re-shard explicitly "
                f"(shard_plan) instead of extending across layouts")
    if spec is not None and spec.merge != plan.merge:
        # an overriding merge model re-partitions below; the buckets (and
        # any slab split) stay valid until _segment_member_lists runs
        if isinstance(plan, ShardedPlan):
            plan = dataclasses.replace(
                plan, plan=dataclasses.replace(plan.plan, merge=spec.merge))
        else:
            plan = dataclasses.replace(plan, merge=spec.merge)
    if isinstance(plan, ShardedPlan):
        return shard_plan(extend_plan(plan.plan, scheme, full_levels),
                          plan.n_slabs, old=plan, n_groups=plan.n_groups)
    if full_levels is None:
        full_levels = fine_levels(scheme)
    full_levels = tuple(int(l) for l in full_levels)
    if full_levels != plan.full_levels:
        return build_plan(scheme, full_levels,
                          merge=plan.merge)       # full rebuild
    fine_shape = plan.fine_shape
    fine_size = plan.fine_size
    fine_strides = _fine_strides(fine_shape)
    # identity reuse is keyed by the member tuple (unique — buckets
    # partition the grids; a merged plan may hold several buckets with
    # the SAME componentwise-max target, so target is not a valid key)
    old_by_ells = {b.ells: b for b in plan.buckets}

    buckets = []
    for members in _segment_member_lists(_group_members(scheme), plan.merge):
        target = tuple(max(lv[k] for _, _, lv, _ in members)
                       for k in range(len(full_levels)))
        ells = tuple(m[0] for m in members)
        coeffs = np.asarray([float(m[3]) for m in members])
        ob = old_by_ells.get(ells)
        if ob is not None and ob.target == target:
            if np.array_equal(ob.coeffs, coeffs):
                buckets.append(ob)                # untouched: same object
            else:
                buckets.append(dataclasses.replace(ob, coeffs=coeffs))
            continue
        # row donors: any old bucket built for the same target shape
        old_rows = {ell: row for b in plan.buckets if b.target == target
                    for ell, row in zip(b.ells, b.index)}
        buckets.append(_make_bucket(members, full_levels, fine_strides,
                                    fine_size, old_rows=old_rows))
    return ExecutorPlan(dim=scheme.dim, full_levels=full_levels,
                        fine_shape=fine_shape, buckets=tuple(buckets),
                        merge=plan.merge)


def update_plan_coefficients(plan: ExecutorPlan,
                             scheme: SchemeLike) -> ExecutorPlan:
    """Coefficient-ONLY plan update: every bucket keeps its members and
    index maps (shared by identity); coefficients are re-read from
    ``scheme`` and members no longer in the scheme get coefficient 0.

    This is the fault-tolerance hot path: a dropped grid's (stale) data may
    stay in the nodal dict — it must merely be FINITE, since its zero
    coefficient multiplies it out of the gather.  Raises ``ValueError``
    when the reduced scheme activates a grid the plan does not hold (then
    an ``extend_plan`` rebuild is required instead).
    """
    if isinstance(plan, ShardedPlan):
        # every base index map is kept, so the slab splits are reused
        # verbatim (shared by identity via shard_plan's id() lookup)
        return shard_plan(update_plan_coefficients(plan.plan, scheme),
                          plan.n_slabs, old=plan, n_groups=plan.n_groups)
    coeff = {ell: float(c) for ell, c in scheme.grids}
    held = {ell for b in plan.buckets for ell in b.ells}
    missing = sorted(set(coeff) - held)
    if missing:
        raise ValueError(
            f"coefficient-only update impossible: scheme activates grid(s) "
            f"{missing} not present in the plan; use extend_plan")
    new_buckets = []
    for b in plan.buckets:
        nc = np.asarray([coeff.get(ell, 0.0) for ell in b.ells])
        new_buckets.append(b if np.array_equal(b.coeffs, nc)
                           else dataclasses.replace(b, coeffs=nc))
    return dataclasses.replace(plan, buckets=tuple(new_buckets))


def _check_nodal_grids(nodal_grids: Mapping[LevelVector, jnp.ndarray],
                       plan: ExecutorPlan) -> None:
    """Explicit input validation: an opaque ``KeyError`` (missing grid) or
    dtype error (empty mapping) deep inside the jitted gather is replaced by
    a message naming the missing level vector(s)."""
    if not nodal_grids:
        raise ValueError(
            f"nodal_grids is empty: the scheme has {plan.num_grids} "
            f"combination grids (one nodal array per level vector required)")
    missing = [ell for b in plan.buckets for ell in b.ells
               if ell not in nodal_grids]
    if missing:
        shown = ", ".join(map(str, missing[:5]))
        more = f" (+{len(missing) - 5} more)" if len(missing) > 5 else ""
        raise ValueError(
            f"nodal_grids is missing {len(missing)} scheme grid(s): "
            f"level vector(s) {shown}{more}")


def _assemble_members(parts: Sequence[jnp.ndarray],
                      perms: Sequence[Tuple[int, ...]],
                      shape: Tuple[int, ...]) -> jnp.ndarray:
    """Stack one bucket's member grids (given in bucket order): transpose
    to canonical order, zero-pad to the bucket target shape (pad values
    never reach the fine buffer — the index plan routes them to the dump
    slot).  Shared by the plan-driven gather and the engine's
    signature-shared executables, so both trace the same ops."""
    out = []
    for part, perm in zip(parts, perms):
        g = jnp.transpose(jnp.asarray(part), perm)
        pad = [(0, t - s) for t, s in zip(shape, g.shape)]
        out.append(jnp.pad(g, pad))
    return jnp.stack(out)


def _assemble_bucket(nodal_grids: Mapping[LevelVector, jnp.ndarray],
                     bucket: Bucket) -> jnp.ndarray:
    """``_assemble_members`` with the members read out of the nodal dict."""
    return _assemble_members([nodal_grids[ell] for ell in bucket.ells],
                             bucket.perms, bucket.shape)


def ct_transform(nodal_grids: Mapping[LevelVector, jnp.ndarray],
                 scheme: SchemeLike, *,
                 full_levels: Optional[Sequence[int]] = None,
                 spec=None) -> jnp.ndarray:
    """Gather phase, batched: nodal component grids -> sparse-grid surplus
    on the common fine grid.  Equals hierarchize-per-grid + ``combine_full``
    to machine precision, in one jittable computation.

    ``spec`` (a ``repro.core.engine.ExecSpec``) carries every execution
    policy — ``spec.merge`` opts into cost-model-driven bucket merging
    (bit-identical result, fewer kernel launches), a meshed spec routes
    through the slab-sharded multi-device gather
    (``repro.core.distributed.ct_transform_sharded``).
    """
    spec = ensure_spec("ct_transform", spec)
    if spec.mesh is not None:
        from repro.core.distributed import ct_transform_sharded
        return ct_transform_sharded(nodal_grids, scheme, spec.mesh,
                                    spec.axis_name, full_levels=full_levels,
                                    spec=dataclasses.replace(spec, mesh=None))
    return ct_transform_with_plan(nodal_grids,
                                  build_plan(scheme, full_levels,
                                             merge=spec.merge),
                                  spec=spec)


def bucket_surpluses(nodal_grids: Mapping[LevelVector, jnp.ndarray],
                     plan: ExecutorPlan, *,
                     interpret: Optional[bool] = None
                     ) -> Tuple[jnp.ndarray, ...]:
    """Per-bucket COMPACT hierarchical surpluses ``[(G_b, P_b), ...]`` —
    the batched hierarchization WITHOUT the embed.  This is the payload
    the slab-sharded gather replicates: its total size is the scheme's
    point count, not ``G * fine_size``."""
    if isinstance(plan, ShardedPlan):
        plan = plan.plan
    _check_nodal_grids(nodal_grids, plan)
    out = []
    for bucket in plan.buckets:
        x = _assemble_bucket(nodal_grids, bucket)
        alpha = hierarchize_batched(x, bucket.levels, interpret=interpret)
        out.append(alpha.reshape(len(bucket.ells), -1))
    return tuple(out)


def bucket_nodal_stacks(nodal_grids: Mapping[LevelVector, jnp.ndarray],
                        plan: ExecutorPlan) -> Tuple[jnp.ndarray, ...]:
    """Per-bucket assembled NODAL stacks ``[(G_b, P_b), ...]`` — assembly
    only, NO hierarchization.  This is what the 2-D (member x slab) mesh
    ingest feeds ``repro.core.distributed.gather_slab_scatter_2d``: the
    transform runs per member group INSIDE shard_map, so only the
    untransformed compact rows cross this boundary and no device ever
    hierarchizes (or even holds) more than its ``G_b / n_groups`` member
    shard of each stack."""
    if isinstance(plan, ShardedPlan):
        plan = plan.plan
    _check_nodal_grids(nodal_grids, plan)
    return tuple(
        _assemble_bucket(nodal_grids, b).reshape(len(b.ells), -1)
        for b in plan.buckets)


def _gather_one_bucket(full: jnp.ndarray, x: jnp.ndarray,
                       member_levels: Tuple[LevelVector, ...],
                       idx, cs, *, interpret: Optional[bool]) -> jnp.ndarray:
    """Accumulate one assembled bucket stack ``x`` (G members, canonical
    padded shape) into the flat buffer ``full`` (+1 dump slot) that
    ``idx`` addresses.

    ``idx`` (the (G, P) embed index map) and ``cs`` (the (G,) combination
    coefficients, already in ``full.dtype``) may be numpy plan constants
    OR traced jit arguments — the engine's signature-shared executables
    pass them as arguments so tenants with equal bucket signatures share
    one compilation; both spellings trace the same ops, so results are
    bit-identical either way."""
    g = len(member_levels)
    alpha = hierarchize_batched(x, member_levels, interpret=interpret)
    return full.at[jnp.asarray(idx)].add(cs[:, None] * alpha.reshape(g, -1))


def _gather_compact(stacks: Sequence[jnp.ndarray],
                    member_levels: Sequence[Tuple[LevelVector, ...]],
                    maps, coeffs, fine_map, fine_shape: Tuple[int, ...],
                    dtype, *, interpret: Optional[bool]) -> jnp.ndarray:
    """The single-device gather: every assembled bucket stack, in bucket
    order, into the compact vector over the sparse grid's points
    (``maps``, ``fine_map``: ``ExecutorPlan.compact``, as numpy plan
    constants or traced arguments), then one write of the fine grid: a
    scatter of sorted, unique indices, which XLA fuses with its
    zero-fill."""
    comp = jnp.zeros(len(fine_map) + 1, dtype)    # +1: pad dump slot
    for x, levels, idx, cs in zip(stacks, member_levels, maps, coeffs):
        comp = _gather_one_bucket(comp, x, levels, idx, cs,
                                  interpret=interpret)
    full = jnp.zeros(int(np.prod(fine_shape)), dtype)
    full = full.at[jnp.asarray(fine_map)].set(
        comp[:-1], indices_are_sorted=True, unique_indices=True)
    return full.reshape(fine_shape)


def ct_transform_with_plan(nodal_grids: Mapping[LevelVector, jnp.ndarray],
                           plan: ExecutorPlan, *,
                           spec=None) -> jnp.ndarray:
    """``ct_transform`` against an explicit (possibly incrementally rebuilt)
    plan — the adaptive-refinement / fault-recovery entry point.  A
    ``ShardedPlan`` is accepted and runs through its base plan (the
    single-device fallback); a MESHED ``spec`` routes the sharded plan
    through the slab-sharded gather
    (``repro.core.distributed.ct_transform_sharded``)."""
    spec = ensure_spec("ct_transform_with_plan", spec)
    if spec.mesh is not None:
        if not isinstance(plan, ShardedPlan):
            raise ValueError(
                "ct_transform_with_plan: spec has a mesh but the plan "
                "is not slab-sharded — build it with build_plan(scheme, "
                "spec=spec) (or shard_plan) so the multi-device gather "
                "can run; a meshed spec never silently degrades to the "
                "single-device path")
        from repro.core.distributed import ct_transform_sharded
        return ct_transform_sharded(nodal_grids, None, spec.mesh,
                                    spec.axis_name, plan=plan,
                                    spec=dataclasses.replace(spec, mesh=None))
    if isinstance(plan, ShardedPlan):
        plan = plan.plan
    _check_nodal_grids(nodal_grids, plan)
    dtype = jnp.result_type(*(jnp.asarray(nodal_grids[ell]).dtype
                              for b in plan.buckets for ell in b.ells))
    compact = plan.compact
    return _gather_compact(
        [_assemble_bucket(nodal_grids, b) for b in plan.buckets],
        [b.levels for b in plan.buckets], compact.buckets,
        [jnp.asarray(b.coeffs, dtype) for b in plan.buckets],
        compact.fine, plan.fine_shape, dtype, interpret=spec.interpret)


def ct_scatter(full: jnp.ndarray, scheme: SchemeLike, *,
               full_levels: Optional[Sequence[int]] = None,
               spec=None) -> Dict[LevelVector, jnp.ndarray]:
    """Scatter phase, batched: sparse-grid surplus -> nodal values of the
    combined solution on every component grid (truncating projection +
    batched dehierarchization; inverse-direction read of the index plan).
    ``spec`` supplies ``merge`` and ``interpret``.
    """
    spec = ensure_spec("ct_scatter", spec)
    return ct_scatter_with_plan(full,
                                build_plan(scheme, full_levels,
                                           merge=spec.merge),
                                interpret=spec.interpret)


def ct_scatter_with_plan(full: jnp.ndarray, plan: ExecutorPlan, *,
                         interpret: Optional[bool] = None
                         ) -> Dict[LevelVector, jnp.ndarray]:
    """``ct_scatter`` against an explicit plan (``ShardedPlan`` accepted:
    the scatter step is a local strided read, so it runs off the base
    plan against the gathered fine buffer)."""
    if isinstance(plan, ShardedPlan):
        plan = plan.plan
    flat = jnp.concatenate([full.ravel(),
                            jnp.zeros((1,), full.dtype)])  # dump slot reads 0
    out: Dict[LevelVector, jnp.ndarray] = {}
    for bucket in plan.buckets:
        g = len(bucket.ells)
        alpha = flat[jnp.asarray(bucket.index)].reshape((g,) + bucket.shape)
        nodal = dehierarchize_batched(alpha, bucket.levels,
                                      interpret=interpret)
        for i, (ell, perm) in enumerate(zip(bucket.ells, bucket.perms)):
            sl = tuple(slice(0, s) for s in grid_shape(bucket.levels[i]))
            inv = np.argsort(np.asarray(perm))
            out[ell] = jnp.transpose(nodal[i][sl], tuple(inv))
    return out


def plan_launch_stats(plan: ExecutorPlan, *,
                      dtype_bytes: int = 8) -> Dict[str, int]:
    """Plan-derived dispatch and gather-phase HBM accounting.

    Static mirror of what one ``ct_transform_with_plan`` execution
    dispatches (cross-checked against the traced counts of
    ``repro.kernels.hierarchize.count_launches`` in the benchmark);
    ``dtype_bytes`` is the gather's itemsize (default 8 = f64):

    * ``pallas_launches`` — Pallas kernel launches (tail + axis-0 per
      Pallas-path bucket, axis-0 alone for 1-D buckets);
    * ``einsum_dispatches`` — per-axis dispatches of jnp-path buckets;
    * ``scatter_dispatches`` — XLA scatter-adds (one per bucket);
    * ``launches`` — the sum: every device-queue dispatch of the gather;
    * ``transform_bytes`` — modelled HBM traffic of the batched
      transforms (``round_trips=4`` array touches of each member's padded
      volume: 2 launches x read+write; tile volume on the Pallas path);
    * ``stack_bytes`` — the compact-surplus round trip (write the
      ``(G, P)`` stack after the transform + read it back in the
      scatter).
    """
    if isinstance(plan, ShardedPlan):
        plan = plan.plan
    stats = {"buckets": len(plan.buckets), "members": plan.num_grids,
             "pallas_launches": 0, "einsum_dispatches": 0,
             "scatter_dispatches": len(plan.buckets), "launches": 0,
             "transform_bytes": 0, "stack_bytes": 0}
    for b in plan.buckets:
        shape = b.shape
        g = len(b.ells)
        p = int(np.prod(shape, dtype=np.int64))
        if batched_method(shape) == "pallas":
            stats["pallas_launches"] += 1 if len(shape) == 1 else 2
            vol = tile_volume(shape)
        else:
            stats["einsum_dispatches"] += len(shape)
            vol = p
        stats["transform_bytes"] += 4 * g * vol * dtype_bytes
        stats["stack_bytes"] += 2 * g * p * dtype_bytes
    stats["launches"] = (stats["pallas_launches"]
                         + stats["einsum_dispatches"]
                         + stats["scatter_dispatches"])
    return stats


def plan_ingest_stats(plan, *, dtype_bytes: int = 8) -> Dict[str, int]:
    """PER-DEVICE ingest compute and memory of the plan's execution mode —
    the numbers that must SHRINK with device count for the distributed
    ingest to scale (``benchmarks/executor_sharded.py`` asserts this):

    * ``ingest_flops`` — hierarchization flops one device performs.  On
      an unsharded or slab-only plan every device transforms the FULL
      compact stack (replicated compute); on a 2-D compute-sharded plan
      (``n_groups > 1``) each device transforms only its
      ``ceil(G_b / n_groups)`` member shard, plus its slab column's
      scatter-adds — 1 flop per REAL payload entry the busiest slab
      receives (pad entries add zeros into the dump slot; they are
      shipped, so they count toward ``ship_bytes``, but they are not
      useful arithmetic, so they do not count here).
    * ``ingest_bytes`` — the per-device ingest working set: the member
      shard of every compact stack (FULL stacks when replicated), the
      shipping payload sent + received + its scatter index map
      (2-D only), and the device's scatter target (slab buffer, or the
      full fine buffer when unsharded).

    Sizes are plan-derived (static), priced at ``dtype_bytes`` per
    surplus element and 4 bytes per int32 index entry."""
    splan = plan if isinstance(plan, ShardedPlan) else None
    base = splan.plan if splan is not None else plan
    n_groups = splan.n_groups if splan is not None else 1
    from repro.kernels.hierarchize import hier_flops
    flops = 0
    stack_bytes = 0
    ship_bytes = 0
    scatter_elems = 0
    for i, b in enumerate(base.buckets):
        g = len(b.ells)
        p = int(np.prod(b.shape, dtype=np.int64))
        gloc = -(-g // n_groups)
        flops += hier_flops(b.shape, gloc)
        stack_bytes += gloc * p * dtype_bytes
        if n_groups > 1:
            sb = splan.slab_buckets[i]
            pay = int(sb.ship_src.shape[-1])
            # sent (S rows) + received (n_groups rows) payload values
            # plus the receiver's int32 scatter map
            ship_bytes += (splan.n_slabs + n_groups) * pay * dtype_bytes
            ship_bytes += n_groups * pay * 4
            # real scatter-adds of the busiest slab: pads target the
            # dump slot (ship_idx == slab_size) and contribute zeros
            real = np.asarray(sb.ship_idx) != splan.slab_size
            scatter_elems += int(real.sum(axis=(1, 2)).max())
        else:
            scatter_elems += g * p
    if splan is not None:
        out_elems = splan.slab_size + 1
    else:
        out_elems = base.fine_size + 1
    return {"n_groups": n_groups,
            "n_slabs": splan.n_slabs if splan is not None else 1,
            "ingest_flops": flops + scatter_elems,
            "ingest_bytes": (stack_bytes + ship_bytes
                             + out_elems * dtype_bytes),
            "stack_bytes": stack_bytes,
            "ship_bytes": ship_bytes,
            "out_bytes": out_elems * dtype_bytes}
