"""Distributed combination technique: shard_map comm phase + grid placement.

Parallelism layers (DESIGN.md Sect. 4):

  * across combination grids — the paper's "very coarse" parallelism: each
    grid is solved by one device group; ``plan_grid_groups`` does the
    load-balanced placement (LPT on grid points).
  * the communication phase — in the hierarchical basis the gather step is
    a single weighted reduction of surpluses embedded in a common fine
    grid; the scatter step is a local strided read.  ``ct_transform_sharded``
    runs the gather on a mesh in one of two ways:

    - slab-sharded (``gather_slab_scatter``): the FINE GRID is
      partitioned into ``n_groups`` contiguous slabs along its leading
      axis and each device scatter-adds the compact (unembedded)
      surpluses into ONLY its own slab, followed by one tiled all-gather
      (or no gather at all: ``gather=False`` returns the slab-sharded
      buffer under a ``NamedSharding`` for downstream sharded
      consumers).  Per-device embedded memory is
      ``ceil(fine_shape[0] / n) * row_size`` — memory scales with device
      count; only the compact surpluses (the scheme's point count) are
      replicated.
    - 2-D (member x slab) mesh (``gather_slab_scatter_2d``): the
      hierarchization ITSELF is sharded too.  The mesh's two axes play
      different roles — flattening them member-major yields
      ``n_groups = members * slabs`` COMPUTE groups, and device
      ``(m, s)`` is compute group ``m * slabs + s`` while also being
      slab ``s``'s scatter owner (replicated over the member
      coordinate).  Each group assembles/hierarchizes only its
      contiguous ``ceil(G_b / n_groups)`` member shard of every compact
      stack and applies the combination coefficients at the source, so
      per-device ingest FLOPs AND stack memory scale with total device
      count; no device ever materializes a full ``(G_b, P_b)`` stack.

Surplus shipping contract of the 2-D path (the flat realization of the
``row_ranges`` metadata ``ShardedPlan`` records per member):

  * ``SlabBucket.ship_src[i, s]`` gathers, from group i's local
    flattened weighted stack, the payload it owes slab ``s`` — member
    rows cut at the slab boundaries ``row_ranges`` describes, ordered by
    (member, position); ``SlabBucket.ship_idx[s, i]`` holds the matching
    slab-LOCAL scatter targets on the receiving side.  Pad entries read
    an appended zero slot / write the slab dump slot.
  * the wire step is one tiled ``all_to_all`` over the SLAB axis (each
    device ships S payload rows, one per destination slab) followed by a
    tiled ``all_gather`` over the MEMBER axis, which lands the payloads
    on the slab owner ordered by source compute group — exactly global
    member-major order, so the owner's single ordered scatter-add over
    all groups' payloads replays the dense gather's per-slot left fold
    bit-for-bit.  (Summing per-group PARTIAL slab buffers instead would
    reassociate floating-point addition and break bit-identity — hence
    ship-then-fold, never fold-then-sum.)
  * overlap schedule: the per-bucket pipeline issues bucket ``b+1``'s
    hierarchize + all_to_all + all_gather BEFORE bucket ``b``'s
    scatter-add in program order, so the collectives overlap with the
    scatter work instead of serializing in front of it.

Slab partitioning invariants (``repro.core.executor.ShardedPlan``):

  * slab ``s`` owns fine rows ``[s * slab_rows, (s+1) * slab_rows)`` with
    ``slab_rows = ceil(fine_shape[0] / n_slabs)``; the last slab is
    ragged when ``n_slabs`` does not divide ``fine_shape[0]`` (its
    out-of-range tail receives no writes).
  * the per-slab index map ``SlabBucket.index[s]`` holds SLAB-LOCAL flat
    indices; every entry outside slab ``s`` (and every pad position of
    the base map) points at the slab dump slot ``slab_size``, so each
    global index lands in exactly one slab and the per-slot addition
    order of the dense gather is preserved — the sharded result is
    bit-identical, not just allclose.  The 2-D shipping maps inherit
    exactly-one-ownership from the per-slab maps they are cut from:
    every real (member, position) entry appears in exactly one
    ``(slab, group)`` payload.
  * ``SlabBucket.row_ranges[s, g]`` records which contiguous range of
    member ``g``'s original-leading-axis nodes embeds into slab ``s`` —
    what a multi-controller run ships to group ``s`` instead of
    replicating the compact surpluses.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.levels import LevelVector, SchemeLike, num_points

__all__ = ["plan_grid_groups", "gather_slab_scatter", "gather_slab_scatter_2d",
           "ct_transform_sharded"]


def plan_grid_groups(scheme: SchemeLike, num_groups: int
                     ) -> Tuple[Tuple[LevelVector, ...], ...]:
    """Longest-processing-time placement of combination grids onto groups.

    Returns a tuple of per-group tuples of level vectors.  Cost model is
    grid points (solver work and hierarchization bytes are both linear in
    points).
    """
    grids = sorted((ell for ell, _ in scheme.grids), key=num_points, reverse=True)
    loads = [0] * num_groups
    buckets: list[list[LevelVector]] = [[] for _ in range(num_groups)]
    for ell in grids:
        g = int(np.argmin(loads))
        buckets[g].append(ell)
        loads[g] += num_points(ell)
    return tuple(tuple(b) for b in buckets)


# ---------------------------------------------------------------------------
# Communication phase across grid groups
# ---------------------------------------------------------------------------

def _check_slab_gather_args(splan, mesh: Mesh, axis_name: str,
                            n_inputs: int, what: str) -> None:
    """Shared argument validation of the two slab-sharded gathers."""
    nshards = mesh.shape[axis_name]
    if nshards != splan.n_slabs:
        raise ValueError(
            f"plan is sharded for {splan.n_slabs} slab(s) but mesh axis "
            f"{axis_name!r} has {nshards} device(s); rebuild with "
            f"shard_plan(plan, {nshards})")
    if n_inputs != len(splan.plan.buckets):
        raise ValueError(
            f"got {n_inputs} {what} array(s) for "
            f"{len(splan.plan.buckets)} bucket(s)")


def _finish_slab_gather(out, splan, mesh: Mesh, axis_name: str,
                        gather: bool) -> jnp.ndarray:
    """Shared result handling: reshape the replicated gather, or hand the
    slab-padded buffer back under its NamedSharding."""
    if gather:
        return out[:splan.fine_size].reshape(splan.plan.fine_shape)
    padded = out.reshape((splan.n_slabs * splan.slab_rows,)
                         + splan.plan.fine_shape[1:])
    sharding = NamedSharding(
        mesh, P(axis_name, *([None] * (len(splan.plan.fine_shape) - 1))))
    if isinstance(padded, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(padded, sharding)
    return jax.device_put(padded, sharding)


def gather_slab_scatter(alphas, sharded_plan, mesh: Mesh, axis_name: str, *,
                        gather: bool = True, idx_arrays=None,
                        coeff_arrays=None) -> jnp.ndarray:
    """Slab-sharded gather step: per-bucket COMPACT surpluses ``alphas``
    (``repro.core.executor.bucket_surpluses``, one ``(G_b, P_b)`` array per
    bucket, replicated) are coefficient-weighted and scatter-added into the
    fine grid with each device group owning one leading-axis slab — the
    per-device embedded buffer is ``slab_size + 1`` elements instead of
    ``G * fine_size``.

    ``gather=True`` finishes with one tiled all-gather and returns the
    replicated combined buffer reshaped to ``fine_shape`` (drop-in for
    ``ct_transform``).  ``gather=False`` keeps the result sharded: the
    returned array has shape ``(n_slabs * slab_rows, *fine_shape[1:])``
    (leading axis slab-padded, rows past ``fine_shape[0]`` zero) under
    ``NamedSharding(mesh, P(axis_name, ...))`` for downstream sharded
    consumers.

    ``idx_arrays`` / ``coeff_arrays`` override the plan's numpy constants
    with (possibly traced) arrays of the same shapes — the hook
    ``repro.core.engine``'s signature-shared executables use so tenants
    with equal bucket signatures share one compilation.  The plan is then
    only read for its static slab metadata.
    """
    splan = sharded_plan
    _check_slab_gather_args(splan, mesh, axis_name, len(alphas), "surplus")
    nb = len(alphas)
    dtype = jnp.result_type(*(a.dtype for a in alphas))
    slab_size = splan.slab_size
    idx = [jnp.asarray(a) for a in (
        idx_arrays if idx_arrays is not None
        else [sb.index for sb in splan.slab_buckets])]
    coeffs = [jnp.asarray(c).astype(dtype) for c in (
        coeff_arrays if coeff_arrays is not None
        else [b.coeffs for b in splan.plan.buckets])]

    def local_fn(*args):
        idx_loc = args[:nb]              # (1, G, P) — this device's slab
        alpha = args[nb:2 * nb]          # (G, P) replicated compact rows
        cs = args[2 * nb:]               # (G,) replicated coefficients
        buf = jnp.zeros(slab_size + 1, dtype)       # +1: dump slot
        for i, a, c in zip(idx_loc, alpha, cs):
            buf = buf.at[i[0]].add(c[:, None] * a.astype(dtype))
        buf = buf[:slab_size]
        if gather:
            return jax.lax.all_gather(buf, axis_name, tiled=True)
        return buf[None]

    rep2, rep1 = P(None, None), P(None)
    in_specs = tuple([P(axis_name, None, None)] * nb
                     + [rep2] * nb + [rep1] * nb)
    out_specs = P(None) if gather else P(axis_name, None)
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    out = fn(*idx, *alphas, *coeffs)
    return _finish_slab_gather(out, splan, mesh, axis_name, gather)


def gather_slab_scatter_2d(stacks, sharded_plan, mesh: Mesh,
                           member_axis: str, axis_name: str, *,
                           gather: bool = True,
                           interpret: bool | None = None,
                           idx_arrays=None, coeff_arrays=None,
                           dtype=None) -> jnp.ndarray:
    """2-D (member x slab) mesh gather: the hierarchization itself is
    sharded.  Consumes per-bucket NODAL compact stacks
    (``repro.core.executor.bucket_nodal_stacks``, one ``(G_b, P_b)``
    array per bucket) and runs, per device = compute group
    ``m * n_slabs + s`` (member-major mesh flattening):

    1. batched hierarchization of ONLY its contiguous member shard
       (``hierarchize_batched`` — the member level table rides along as
       a G-sharded array), coefficients applied at the
       source;
    2. the surplus all-to-all: gather the per-destination-slab payloads
       through ``SlabBucket.ship_src``, one tiled ``all_to_all`` over
       the slab axis + one tiled ``all_gather`` over the member axis
       lands every group's payload on the slab owner in global group
       order;
    3. the slab owner's SINGLE ordered scatter-add of all payloads
       through ``SlabBucket.ship_idx`` — the same per-slot left fold as
       the dense gather, so the result is BIT-identical (partial-sum
       combining across groups would reassociate; see the module notes).

    The per-bucket pipeline is overlap-scheduled: bucket ``b+1``'s
    transform + collectives are issued before bucket ``b``'s scatter in
    program order.  Per-device ingest flops and stack bytes are
    ``1 / n_groups`` of the replicated path's
    (``repro.core.executor.plan_ingest_stats``).

    ``idx_arrays`` overrides the plan's shipping maps with (possibly
    traced) ``(ship_src, ship_idx)`` pairs and ``coeff_arrays`` the
    coefficients — the signature-shared-executable hook, as in
    ``gather_slab_scatter``.  Same ``gather`` semantics as the 1-D
    gathers.
    """
    from repro.kernels.hierarchize import (hierarchize_batched,
                                           member_level_array)
    splan = sharded_plan
    nb = len(stacks)
    _check_slab_gather_args(splan, mesh, axis_name, nb, "nodal-stack")
    if member_axis not in mesh.shape:
        raise ValueError(
            f"member_axis {member_axis!r} is not an axis of the mesh "
            f"(axes: {tuple(mesh.shape)})")
    if member_axis == axis_name:
        raise ValueError(
            f"member_axis and axis_name must differ, both {axis_name!r}")
    n_members = int(mesh.shape[member_axis])
    n_slabs = splan.n_slabs
    n_groups = n_members * n_slabs
    if splan.n_groups != n_groups:
        raise ValueError(
            f"plan is compute-sharded for {splan.n_groups} group(s) but "
            f"the (member x slab) mesh has {n_groups}; rebuild with "
            f"shard_plan(plan, {n_slabs}, n_groups={n_groups})")
    if dtype is None:
        dtype = jnp.result_type(*(a.dtype for a in stacks))
    slab_size = splan.slab_size
    buckets = splan.plan.buckets
    if idx_arrays is None:
        idx_arrays = [(sb.ship_src, sb.ship_idx)
                      for sb in splan.slab_buckets]
    srcs = [jnp.asarray(a) for a, _ in idx_arrays]
    dsts = [jnp.asarray(d) for _, d in idx_arrays]
    coeffs = [jnp.asarray(c) for c in (
        coeff_arrays if coeff_arrays is not None
        else [b.coeffs for b in buckets])]
    gsizes = [sb.group_size for sb in splan.slab_buckets]
    shapes = [b.shape for b in buckets]
    # member level tables, padded and G-sharded like the stacks;
    # signature-determined (bucket levels), so baked as trace constants
    xs, cs, lvs = [], [], []
    for b, a, c, gs in zip(buckets, stacks, coeffs, gsizes):
        g, p = a.shape
        pad = n_groups * gs - g
        xs.append(jnp.pad(a, ((0, pad), (0, 0))))
        cs.append(jnp.pad(c.astype(dtype), (0, pad)))
        # pad members get level 0 (no real nodes) -> their (zero) rows
        # pass through; their payload entries are never gathered anyway
        lvs.append(jnp.asarray(np.pad(member_level_array(b.levels),
                                      ((0, pad), (0, 0)))))

    # one compiled program per bucket shape even when the gather runs
    # eagerly (an eager shard_map otherwise dispatches the kernel body op
    # by op); inlined as-is under an outer jit
    transform = jax.jit(partial(hierarchize_batched, interpret=interpret))

    def local_fn(*args):
        src = args[:nb]                  # (1, S, L) this group's gathers
        dst = args[nb:2 * nb]            # (1, n_groups, L) this slab's map
        x = args[2 * nb:3 * nb]          # (gloc, P) this group's members
        cl = args[3 * nb:4 * nb]         # (gloc,) their coefficients
        lv = args[4 * nb:]               # (gloc, d) their level vectors

        def ship(i):
            gloc = x[i].shape[0]
            xg = x[i].reshape((gloc,) + shapes[i])
            alpha = transform(xg, lv[i])
            w = cl[i][:, None] * alpha.reshape(gloc, -1).astype(dtype)
            flat = jnp.concatenate([w.reshape(-1),
                                    jnp.zeros((1,), dtype)])
            payload = flat[src[i][0]]                       # (S, L)
            payload = jax.lax.all_to_all(payload, axis_name, 0, 0,
                                         tiled=True)
            return jax.lax.all_gather(payload, member_axis, axis=0,
                                      tiled=True)           # (n_groups, L)

        buf = jnp.zeros(slab_size + 1, dtype)               # +1: dump slot
        pending = ship(0)
        for i in range(nb):
            # overlap: issue bucket i+1's transform + collectives before
            # bucket i's scatter-add
            nxt = ship(i + 1) if i + 1 < nb else None
            buf = buf.at[dst[i][0].reshape(-1)].add(pending.reshape(-1))
            pending = nxt
        buf = buf[:slab_size]
        if gather:
            return jax.lax.all_gather(buf, axis_name, tiled=True)
        return buf[None]

    both = (member_axis, axis_name)      # member-major group flattening
    in_specs = tuple([P(both, None, None)] * nb       # ship_src by group
                     + [P(axis_name, None, None)] * nb  # ship_idx by slab
                     + [P(both, None)] * nb           # stacks by member rows
                     + [P(both)] * nb                 # coefficients
                     + [P(both, None)] * nb)          # level tables
    out_specs = P(None) if gather else P(axis_name, None)
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    out = fn(*srcs, *dsts, *xs, *cs, *lvs)
    return _finish_slab_gather(out, splan, mesh, axis_name, gather)


def ct_transform_sharded(nodal_grids, scheme: SchemeLike, mesh: Mesh,
                         axis_name: str, *,
                         full_levels: Sequence[int] | None = None,
                         plan=None, gather: bool = True,
                         spec=None, member_axis: str | None = None
                         ) -> jnp.ndarray:
    """Memory-scaling distributed gather: bucket-batched hierarchization,
    then the slab-sharded scatter-add — the multi-device ``ct_transform``,
    whose per-device embedded memory is ``fine_size / n_groups``.

    Pass ``plan`` (a ``repro.core.executor.shard_plan`` result) to reuse
    a live plan (the adaptive / fault path); otherwise one is built for
    ``mesh.shape[axis_name]`` slabs.  ``gather=False`` returns the
    slab-sharded fine buffer (see ``gather_slab_scatter``).  ``spec``
    (a ``repro.core.engine.ExecSpec``) supplies ``interpret`` and
    ``merge``.

    ``member_axis`` (or ``spec.member_axis``) names the SECOND axis of a
    2-D (member x slab) mesh: the ingest then also compute-shards the
    hierarchization over ``members * slabs`` groups and routes through
    ``gather_slab_scatter_2d`` (bit-identical; see the module notes).
    """
    from repro.core.executor import (build_plan, bucket_nodal_stacks,
                                     bucket_surpluses, ensure_spec,
                                     shard_plan)
    spec = ensure_spec("ct_transform_sharded", spec)
    interpret = spec.interpret
    if member_axis is None:
        member_axis = spec.member_axis
    n_groups = 1
    if member_axis is not None:
        n_groups = (int(mesh.shape[member_axis])
                    * int(mesh.shape[axis_name]))
    if plan is None:
        plan = shard_plan(build_plan(scheme, full_levels, merge=spec.merge),
                          mesh.shape[axis_name], n_groups=n_groups)
    elif full_levels is not None and plan.full_levels != \
            tuple(int(l) for l in full_levels):
        raise ValueError(
            f"plan embeds into {plan.full_levels}, caller "
            f"asked for {tuple(int(l) for l in full_levels)}")
    if member_axis is not None and n_groups > 1:
        # 2-D compute-sharded route.  A degenerate 1x1 mesh has nothing
        # to compute-shard and falls through to the slab path.
        stacks = bucket_nodal_stacks(nodal_grids, plan.plan)
        return gather_slab_scatter_2d(stacks, plan, mesh,
                                      member_axis, axis_name,
                                      gather=gather, interpret=interpret)
    alphas = bucket_surpluses(nodal_grids, plan.plan, interpret=interpret)
    return gather_slab_scatter(alphas, plan, mesh, axis_name, gather=gather)
