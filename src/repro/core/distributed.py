"""Distributed combination technique: shard_map comm phase + grid placement.

Parallelism layers (DESIGN.md Sect. 4):

  * across combination grids — the paper's "very coarse" parallelism: each
    grid is solved by one device group; ``plan_grid_groups`` does the
    load-balanced placement (LPT on grid points).
  * within a grid — pole-parallel hierarchization: sharding any non-working
    axis needs NO communication; only the transform along the sharded axis
    itself communicates.  ``hierarchize_sharded`` shards axis 0, runs the
    tail transform locally and realizes the axis-0 transform as
    (local operator rows) @ (all-gathered poles) — one all-gather of the
    grid per full d-dimensional hierarchization.
  * the communication phase — in the hierarchical basis the gather step is
    a single weighted reduction of surpluses embedded in a common fine
    grid; the scatter step is a local strided read.  Two realizations:

    - grid-replicated (``gather_full_psum`` / ``ct_transform_psum``): the
      grid axis is sharded and every device materializes full
      ``fine_shape`` buffers before ONE psum.  Per-device memory is
      ``(G / n) * fine_size`` — compute scales, memory does not.
    - slab-sharded (``gather_slab_scatter`` / ``ct_transform_sharded``):
      the FINE GRID is partitioned into ``n_groups`` contiguous slabs
      along its leading axis and each device scatter-adds the compact
      (unembedded) surpluses into ONLY its own slab, followed by one
      tiled all-gather (or no gather at all: ``gather=False`` returns the
      slab-sharded buffer under a ``NamedSharding`` for downstream
      sharded consumers).  Per-device embedded memory is
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

import dataclasses
from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.levels import (LevelVector, SchemeLike, fine_levels,
                               num_points)
from repro.kernels.hierarchize import _padded_operator  # shared constant builder

__all__ = ["plan_grid_groups", "hierarchize_sharded", "gather_full_psum",
           "gather_slab_scatter", "gather_slab_scatter_2d", "comm_phase_sharded",
           "ct_transform_psum", "ct_transform_sharded"]


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
# Pole-parallel hierarchization under shard_map
# ---------------------------------------------------------------------------

def hierarchize_sharded(x_padded: jnp.ndarray, level0: int, mesh: Mesh,
                        axis_name: str) -> jnp.ndarray:
    """Hierarchize a d-dim grid whose axis 0 is padded to 2**level0 and
    sharded over ``axis_name``; remaining axes are unpadded (2**l - 1) and
    replicated.

    Communication: exactly one all-gather of the array (the axis-0
    transform); the tail axes are transformed locally.
    """
    n0p = x_padded.shape[0]
    assert n0p == 1 << level0, "axis 0 must be padded to 2**level0"
    nshards = mesh.shape[axis_name]
    assert n0p % nshards == 0
    shard = n0p // nshards
    hmat = jnp.asarray(_padded_operator(level0, np.float32, npad=n0p),
                       dtype=x_padded.dtype)

    def local_fn(h, x_loc):
        # tail axes: pole bundles are fully local -> no communication
        if x_loc.ndim > 1:
            x_loc = _hier_tail_local(x_loc)
        # axis 0: rows of the operator live here, columns are all-gathered
        xg = jax.lax.all_gather(x_loc, axis_name, axis=0, tiled=True)
        i = jax.lax.axis_index(axis_name)
        h_rows = jax.lax.dynamic_slice_in_dim(h, i * shard, shard, axis=0)
        return jnp.tensordot(h_rows, xg, axes=[[1], [0]]).astype(x_loc.dtype)

    def _hier_tail_local(x_loc):
        for ax in range(1, x_loc.ndim):
            moved = jnp.moveaxis(x_loc, ax, 0)
            from repro.kernels.ref import hierarchize_1d_ref
            moved = hierarchize_1d_ref(moved, axis=0)
            x_loc = jnp.moveaxis(moved, 0, ax)
        return x_loc

    spec = P(axis_name, *([None] * (x_padded.ndim - 1)))
    fn = jax.shard_map(partial(local_fn, hmat), mesh=mesh,
                       in_specs=(spec,), out_specs=spec, check_vma=False)
    return fn(x_padded)


# ---------------------------------------------------------------------------
# Communication phase across grid groups
# ---------------------------------------------------------------------------

def gather_full_psum(embedded: jnp.ndarray, coeff: jnp.ndarray, mesh: Mesh,
                     axis_name: str) -> jnp.ndarray:
    """Gather step over grid groups: combined = psum_g coeff_g * embedded_g.

    ``embedded``: (G, *full_shape) — group g's hierarchized surpluses already
    embedded in the common fine grid (zero where the grid has no node);
    sharded over ``axis_name``.  Returns the replicated combined buffer.
    """
    def local_fn(e_loc, c_loc):
        contrib = jnp.tensordot(c_loc, e_loc, axes=[[0], [0]])
        return jax.lax.psum(contrib, axis_name)

    in_specs = (P(axis_name, *([None] * (embedded.ndim - 1))), P(axis_name))
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=P(*([None] * (embedded.ndim - 1))),
                       check_vma=False)
    return fn(embedded, coeff)


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
                         plan=None, sharded_plan=None, gather: bool = True,
                         interpret: bool | None = None,
                         spec=None, member_axis: str | None = None
                         ) -> jnp.ndarray:
    """Memory-scaling distributed gather: bucket-batched hierarchization,
    then the slab-sharded scatter-add — the multi-device ``ct_transform``
    whose per-device embedded memory is ``fine_size / n_groups``, not
    ``G * fine_size``.

    Pass ``plan`` (a ``repro.core.executor.shard_plan`` result) to reuse
    a live plan (the adaptive / fault path); otherwise one is built for
    ``mesh.shape[axis_name]`` slabs.  ``gather=False`` returns the
    slab-sharded fine buffer (see ``gather_slab_scatter``).  ``spec``
    (a ``repro.core.engine.ExecSpec``) consolidates
    ``interpret``/``merge``; the bare ``interpret=`` kwarg and the old
    ``sharded_plan=`` spelling of ``plan=`` remain as deprecation shims.

    ``member_axis`` (or ``spec.member_axis``) names the SECOND axis of a
    2-D (member x slab) mesh: the ingest then also compute-shards the
    hierarchization over ``members * slabs`` groups and routes through
    ``gather_slab_scatter_2d`` (bit-identical; see the module notes).
    """
    from repro.core.executor import (build_plan, bucket_nodal_stacks,
                                     bucket_surpluses, resolve_spec,
                                     shard_plan, warn_legacy_kwargs)
    if sharded_plan is not None:
        if plan is not None:
            raise ValueError("ct_transform_sharded: pass plan= or the "
                             "deprecated sharded_plan=, not both")
        warn_legacy_kwargs("ct_transform_sharded", ("sharded_plan",))
        plan = sharded_plan
    spec = resolve_spec("ct_transform_sharded", spec, interpret=interpret)
    interpret = spec.interpret
    if member_axis is None:
        member_axis = spec.member_axis
    n_groups = 1
    if member_axis is not None:
        n_groups = (int(mesh.shape[member_axis])
                    * int(mesh.shape[axis_name]))
    sharded_plan = plan
    if sharded_plan is None:
        sharded_plan = shard_plan(build_plan(scheme, full_levels,
                                             merge=spec.merge),
                                  mesh.shape[axis_name],
                                  n_groups=n_groups)
    elif full_levels is not None and sharded_plan.full_levels != \
            tuple(int(l) for l in full_levels):
        raise ValueError(
            f"sharded_plan embeds into {sharded_plan.full_levels}, caller "
            f"asked for {tuple(int(l) for l in full_levels)}")
    if member_axis is not None and n_groups > 1:
        # 2-D compute-sharded route.  A degenerate 1x1 mesh has nothing to compute-shard and falls
        # through to the classic slab path.
        stacks = bucket_nodal_stacks(nodal_grids, sharded_plan.plan)
        return gather_slab_scatter_2d(stacks, sharded_plan, mesh,
                                      member_axis, axis_name,
                                      gather=gather, interpret=interpret)
    alphas = bucket_surpluses(nodal_grids, sharded_plan.plan,
                              interpret=interpret)
    return gather_slab_scatter(alphas, sharded_plan, mesh, axis_name,
                               gather=gather)


def comm_phase_sharded(hier_grids, scheme: SchemeLike, mesh: Mesh,
                       axis_name: str, full_levels: Sequence[int] | None = None,
                       sharded_plan=None, *, plan=None, spec=None):
    """Full communication phase: gather + per-grid extract.

    Single-controller convenience wrapper.  Default (``plan=None``)
    is the grid-replicated psum: embeds every grid, stacks, psums over the
    grid axis.  With a slab-sharded ``plan`` — or a sharded ``spec``, from
    which one is built — the gather runs slab-sharded instead: the
    already-hierarchized grids are packed into compact bucket rows (no
    ``(G, *fine_shape)`` stack is ever materialized) and scatter-added
    slab-locally.  In a multi-controller deployment each group computes
    only its own embed/extract.  ``sharded_plan=`` is the deprecated
    spelling of ``plan=``.
    """
    from repro.core.combination import embed_to_full, extract_from_full
    from repro.core.executor import (build_plan, ensure_spec,
                                     warn_legacy_kwargs)
    ensure_spec("comm_phase_sharded", spec)
    if sharded_plan is not None:
        if plan is not None:
            raise ValueError("comm_phase_sharded: pass plan= or the "
                             "deprecated sharded_plan=, not both")
        warn_legacy_kwargs("comm_phase_sharded", ("sharded_plan",))
    else:
        sharded_plan = plan
    if sharded_plan is None and spec is not None and spec.slabs > 1:
        sharded_plan = build_plan(scheme, full_levels, spec=spec)
    if full_levels is None:
        full_levels = fine_levels(scheme)
    ells = [ell for ell, _ in scheme.grids]
    if sharded_plan is not None:
        from repro.core.executor import _assemble_bucket
        if sharded_plan.full_levels != tuple(full_levels):
            raise ValueError(
                f"sharded_plan embeds into {sharded_plan.full_levels}, "
                f"comm phase asked for {tuple(full_levels)}")
        alphas = [_assemble_bucket(hier_grids, b).reshape(len(b.ells), -1)
                  for b in sharded_plan.plan.buckets]
        combined = gather_slab_scatter(alphas, sharded_plan, mesh, axis_name)
        return {ell: extract_from_full(combined, ell, full_levels)
                for ell in ells}
    coeffs = jnp.asarray([float(c) for _, c in scheme.grids])
    emb = jnp.stack([embed_to_full(hier_grids[ell], ell, full_levels)
                     for ell in ells])
    g = emb.shape[0]
    nshards = mesh.shape[axis_name]
    pad = (-g) % nshards
    if pad:
        emb = jnp.pad(emb, [(0, pad)] + [(0, 0)] * (emb.ndim - 1))
        coeffs = jnp.pad(coeffs, (0, pad))
    combined = gather_full_psum(emb, coeffs, mesh, axis_name)
    return {ell: extract_from_full(combined, ell, full_levels) for ell in ells}


def ct_transform_psum(nodal_grids, scheme: SchemeLike, mesh: Mesh,
                      axis_name: str,
                      full_levels: Sequence[int] | None = None,
                      sharded_plan=None, *, plan=None,
                      spec=None) -> jnp.ndarray:
    """Distributed batched gather: the executor's bucket-batched
    hierarchization + static index plan produce the per-grid embedded
    surpluses, then ONE weighted psum over grid groups combines them —
    the multi-node realization of ``repro.core.executor.ct_transform``.

    Returns the replicated sparse-grid surplus on the common fine grid.
    Pass a slab-sharded ``plan`` (or a spec with ``n_slabs``) to run the
    memory-scaling slab-sharded gather instead (no ``(G, *fine_shape)``
    stack is materialized; see ``ct_transform_sharded``) — same result,
    per-device embedded memory ``fine_size / n_groups``.
    ``sharded_plan=`` is the deprecated spelling of ``plan=``.
    """
    from repro.core.executor import resolve_spec, warn_legacy_kwargs
    if sharded_plan is not None:
        if plan is not None:
            raise ValueError("ct_transform_psum: pass plan= or the "
                             "deprecated sharded_plan=, not both")
        warn_legacy_kwargs("ct_transform_psum", ("sharded_plan",))
        plan = sharded_plan
    spec = resolve_spec("ct_transform_psum", spec)
    if plan is None and spec.slabs > 1:
        from repro.core.executor import build_plan
        plan = build_plan(scheme, full_levels, spec=spec)
    if plan is not None:
        return ct_transform_sharded(nodal_grids, scheme, mesh, axis_name,
                                    full_levels=full_levels, plan=plan,
                                    spec=dataclasses.replace(
                                        spec, mesh=None, n_slabs=None))
    from repro.core.executor import ct_embedded
    embedded, coeffs, _ = ct_embedded(nodal_grids, scheme,
                                      full_levels=full_levels,
                                      spec=spec)
    g = embedded.shape[0]
    nshards = mesh.shape[axis_name]
    pad = (-g) % nshards
    if pad:
        embedded = jnp.pad(embedded,
                           [(0, pad)] + [(0, 0)] * (embedded.ndim - 1))
        coeffs = jnp.pad(coeffs, (0, pad))
    return gather_full_psum(embedded, coeffs.astype(embedded.dtype),
                            mesh, axis_name)
