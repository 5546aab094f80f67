"""2-D (member x slab) compute-sharded ingest: ship-map invariants
(pure numpy, no devices), per-device accounting, the extend-across-the-
slab-boundary regression, and multi-device property tests pinning the
fully distributed hierarchization to the single-device ``ct_transform``
BIT-identically."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from proptest import cases, integers, seeds

from jax.sharding import AxisType
from repro.core.distributed import ct_transform_sharded
from repro.core.engine import CTEngine, ExecSpec
from repro.core.executor import (build_plan, ct_transform,
                                 ct_transform_with_plan, extend_plan,
                                 plan_ingest_stats, shard_plan,
                                 update_plan_coefficients, ShardedPlan)
from repro.core.levels import (CombinationScheme, GeneralScheme,
                               admissible_extensions, fine_levels,
                               grid_shape)


def _random_general_scheme(seed, dim, steps, max_level=4):
    rng = np.random.default_rng(seed)
    gs = GeneralScheme.regular(dim, 1)
    for _ in range(steps):
        cands = [c for c in admissible_extensions(gs.index_set)
                 if max(c) <= max_level]
        if not cands:
            break
        gs = gs.with_levels([cands[int(rng.integers(len(cands)))]])
    return gs


def _random_grids(scheme, rng, dtype=np.float64):
    return {ell: jnp.asarray(rng.standard_normal(grid_shape(ell)), dtype)
            for ell, _ in scheme.grids}


def _mesh2d(m, s):
    return jax.make_mesh((m, s), ("member", "slab"),
                         devices=np.array(jax.devices()[:m * s]),
                         axis_types=(AxisType.Auto, AxisType.Auto))


# ---------------------------------------------------------------------------
# (a) ship-map invariants — single-device, no mesh required
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_slabs,n_members",
                         [(3, 1), (5, 1), (7, 1), (2, 3), (3, 2), (4, 2),
                          (2, 2)])
def test_ship_maps_partition_exactly_one_owner(n_slabs, n_members):
    """Exactly-one-owner under the 2-D assignment: every non-pad entry
    of every member's index map is shipped by exactly ONE group (the one
    owning the member) to exactly ONE slab (the one owning the fine
    row), where it reconstructs the slab-local index; pad entries ship
    nothing.  Odd counts leave both a ragged last slab and a ragged last
    member group."""
    n_groups = n_slabs * n_members
    gs = _random_general_scheme(7 * n_slabs + n_members, 3, 6)
    plan = build_plan(gs)
    splan = shard_plan(plan, n_slabs, n_groups=n_groups)
    assert splan.n_groups == n_groups
    for b, sb in zip(plan.buckets, splan.slab_buckets):
        g_total, p = b.index.shape
        gsz = sb.group_size
        assert gsz == -(-g_total // n_groups)
        assert sb.ship_src.shape[:2] == (n_groups, n_slabs)
        assert sb.ship_idx.shape[:2] == (n_slabs, n_groups)
        hits = np.zeros((n_slabs,) + b.index.shape, np.int64)
        for i in range(n_groups):
            for s in range(n_slabs):
                src = sb.ship_src[i, s]
                dst = sb.ship_idx[s, i]
                real = src != gsz * p
                assert np.all(dst[~real] == splan.slab_size)  # pads dump
                mem = src[real] // p + i * gsz
                pos = src[real] % p
                assert np.all(mem < g_total)    # pad members ship nothing
                hits[s, mem, pos] += 1
                np.testing.assert_array_equal(dst[real],
                                              sb.index[s, mem, pos])
        pad = b.index == plan.fine_size
        assert np.all(hits.sum(axis=0)[~pad] == 1)
        assert np.all(hits[:, pad] == 0)


def test_per_device_ingest_work_scales_down():
    """No device materializes the full compact stack: plan-derived
    per-device ingest FLOPs and bytes shrink STRICTLY as the group count
    grows 1 -> 2 -> 4 (the CI benchmark assertion, in-process)."""
    plan = build_plan(CombinationScheme(3, 5))
    stats = [plan_ingest_stats(shard_plan(plan, s, n_groups=s))
             for s in (1, 2, 4)]
    for key in ("ingest_flops", "ingest_bytes", "stack_bytes"):
        vals = [st[key] for st in stats]
        assert vals[0] > vals[1] > vals[2], (key, vals)
    # the sharded stacks really are member SHARDS, not replicas
    full = plan_ingest_stats(plan)["stack_bytes"]
    assert stats[2]["stack_bytes"] < full


def test_shard_plan_group_validation():
    plan = build_plan(CombinationScheme(2, 3))
    with pytest.raises(ValueError, match="n_groups"):
        shard_plan(plan, 2, n_groups=0)


def test_extend_plan_reshards_across_slab_boundary():
    """Bugfix regression: refinement that grows ``fine_shape[0]`` past
    ``n_slabs * slab_rows`` changes the slab geometry — the incremental
    path must fall back to a FULL re-shard (no stale identity-reused
    index arrays), and the result must equal a from-scratch shard."""
    gs = GeneralScheme.regular(2, 3)
    splan = shard_plan(build_plan(gs), 3, n_groups=6)
    lead = fine_levels(gs)[0]
    # refine until the leading fine level (and so fine_shape[0]) grows
    while fine_levels(gs)[0] == lead:
        cands = admissible_extensions(gs.index_set)
        gs = gs.with_levels([max(cands, key=lambda c: c[0])])
    assert grid_shape(fine_levels(gs))[0] > splan.n_slabs * splan.slab_rows

    s2 = extend_plan(splan, gs)
    assert isinstance(s2, ShardedPlan)
    assert s2.n_slabs == 3 and s2.n_groups == 6
    assert s2.slab_rows * s2.n_slabs >= s2.plan.fine_shape[0]
    old = {id(sb) for sb in splan.slab_buckets}
    assert all(id(sb) not in old for sb in s2.slab_buckets)  # full re-shard
    fresh = shard_plan(build_plan(gs), 3, n_groups=6)
    for a, b in zip(s2.slab_buckets, fresh.slab_buckets):
        np.testing.assert_array_equal(a.index, b.index)
        np.testing.assert_array_equal(a.row_ranges, b.row_ranges)
        np.testing.assert_array_equal(a.ship_src, b.ship_src)
        np.testing.assert_array_equal(a.ship_idx, b.ship_idx)


def test_incremental_reshard_keeps_reuse_when_geometry_unchanged():
    """The fast path survives the fix: a coefficient-only update (same
    full_levels, same slab geometry, same groups) still reuses every
    SlabBucket by identity — and a GROUP-count change alone also forces
    the rebuild (ship maps depend on it)."""
    gs = GeneralScheme.regular(3, 3)
    splan = shard_plan(build_plan(gs), 4, n_groups=8)
    dropped = max(ell for ell, _ in gs.grids)
    s2 = update_plan_coefficients(splan, gs.without_levels([dropped]))
    assert all(a is b for a, b in zip(s2.slab_buckets, splan.slab_buckets))

    regrouped = shard_plan(splan.plan, 4, old=splan, n_groups=4)
    assert regrouped.n_groups == 4
    assert all(a is not b for a, b in
               zip(regrouped.slab_buckets, splan.slab_buckets))


# ---------------------------------------------------------------------------
# (b) 2-D gather == single-device ct_transform, bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.multidevice
@pytest.mark.parametrize("m,s", [(1, 2), (2, 1), (2, 2), (2, 4), (4, 2),
                                 (8, 1), (1, 8)])
def test_2d_gather_bit_identical(m, s):
    """Each member's surpluses are computed by exactly one group with
    the same kernels and operands as the single-device path, and the
    slab owner performs the ONE ordered scatter fold — so the 2-D
    gather is bit-identical, not merely allclose."""
    scheme = CombinationScheme(3, 4)
    grids = _random_grids(scheme, np.random.default_rng(10 * m + s))
    want = np.asarray(ct_transform(grids, scheme))
    got = np.asarray(ct_transform_sharded(grids, scheme, _mesh2d(m, s),
                                          "slab", member_axis="member"))
    np.testing.assert_array_equal(got, want)


@pytest.mark.multidevice
@pytest.mark.parametrize("dim,steps,ms,seed", cases(
    lambda r: (integers(r, 2, 3), integers(r, 2, 8), integers(r, 0, 5),
               seeds(r)), n=10))
def test_2d_gather_random_schemes(dim, steps, ms, seed):
    """Seeded random downward-closed schemes x random 2-D mesh shapes
    (ragged member groups AND ragged last slabs): bit-identical to the
    single-device transform."""
    m, s = [(1, 3), (2, 2), (3, 2), (2, 3), (2, 4), (4, 2)][ms]
    gs = _random_general_scheme(seed, dim, steps)
    grids = _random_grids(gs, np.random.default_rng(seed))
    want = np.asarray(ct_transform(grids, gs))
    got = np.asarray(ct_transform_sharded(grids, gs, _mesh2d(m, s),
                                          "slab", member_axis="member"))
    np.testing.assert_array_equal(got, want)


@pytest.mark.multidevice
def test_2d_gather_through_spec_and_plan_reuse():
    """``spec.member_axis`` routes the 2-D path, and a prebuilt 2-D
    ``ShardedPlan`` is reused (including after the incremental
    coefficient update)."""
    gs = GeneralScheme.regular(3, 3)
    mesh = _mesh2d(2, 4)
    spec = ExecSpec(mesh=mesh, axis_name="slab", member_axis="member")
    assert spec.members == 2 and spec.groups == 8
    grids = _random_grids(gs, np.random.default_rng(3))
    want = np.asarray(ct_transform(grids, gs))
    got = np.asarray(ct_transform_sharded(grids, gs, mesh, "slab",
                                          spec=spec))
    np.testing.assert_array_equal(got, want)

    splan = shard_plan(build_plan(gs), 4, n_groups=8)
    got2 = np.asarray(ct_transform_sharded(grids, gs, mesh, "slab",
                                           member_axis="member",
                                           plan=splan))
    np.testing.assert_array_equal(got2, want)

    gs2 = gs.without_levels([max(ell for ell, _ in gs.grids)])
    s2 = update_plan_coefficients(splan, gs2)
    got3 = np.asarray(ct_transform_sharded(grids, gs2, mesh, "slab",
                                           member_axis="member", plan=s2))
    # oracle on the SAME fine grid: the updated plan keeps full_levels
    np.testing.assert_array_equal(
        got3, np.asarray(ct_transform_with_plan(grids, s2)))


@pytest.mark.multidevice
def test_2d_plan_group_mismatch_raises():
    gs = GeneralScheme.regular(2, 3)
    grids = _random_grids(gs, np.random.default_rng(4))
    splan = shard_plan(build_plan(gs), 2, n_groups=2)   # slab-only groups
    with pytest.raises(ValueError, match="n_groups"):
        ct_transform_sharded(grids, gs, _mesh2d(2, 2), "slab",
                             member_axis="member", plan=splan)


# ---------------------------------------------------------------------------
# (c) engine + elastic serving on the 2-D mesh
# ---------------------------------------------------------------------------

@pytest.mark.multidevice
def test_engine_serves_2d_meshed_tenant():
    """A tenant registered under a 2-D ExecSpec ingests through the
    compute-sharded executable: surplus and queries bit-match the
    unmeshed engine."""
    scheme = CombinationScheme(2, 4)
    rng = np.random.default_rng(31)
    host_grids = {ell: rng.standard_normal(grid_shape(ell))
                  for ell, _ in scheme.grids}
    ref = CTEngine()
    ref.register("t", scheme, host_grids)
    spec = ExecSpec(mesh=_mesh2d(2, 2), axis_name="slab",
                    member_axis="member")
    eng = CTEngine(spec)
    eng.register("t", scheme, host_grids)
    assert isinstance(eng.plan("t"), ShardedPlan)
    assert eng.plan("t").n_groups == 4
    np.testing.assert_array_equal(np.asarray(eng.surplus("t")),
                                  np.asarray(ref.surplus("t")))
    pts = np.random.default_rng(310).random((16, 2))
    np.testing.assert_array_equal(eng.query("t", pts), ref.query("t", pts))


@pytest.mark.multidevice
def test_rebalance_engine_onto_2d_mesh_and_back():
    """The elastic fast lane carries the member axis: tenants move onto
    a 2-D mesh (no surplus recompute), the NEXT ingest runs fully
    distributed, and the mesh=None path clears the member axis."""
    from repro.runtime.elastic import rebalance_engine
    scheme = GeneralScheme.regular(2, 4)
    rng = np.random.default_rng(37)
    eng = CTEngine()
    eng.register("a", scheme, _random_grids(scheme, rng))
    pts = np.random.default_rng(370).random((16, 2))
    want = eng.query("a", pts)
    ingests = eng.stats()["ingests"]

    out = rebalance_engine(eng, _mesh2d(2, 4), member_axis="member")
    assert out == {"a": "sharded"}
    assert eng.stats()["ingests"] == ingests        # carried over
    assert eng.plan("a").n_groups == 8
    np.testing.assert_array_equal(eng.query("a", pts), want)

    g2 = _random_grids(scheme, rng)
    eng.update("a", g2)
    np.testing.assert_array_equal(np.asarray(eng.surplus("a")),
                                  np.asarray(ct_transform(g2, scheme)))

    out = rebalance_engine(eng, None)
    assert out == {"a": "unsharded"}
    assert eng.spec("a").member_axis is None
    np.testing.assert_array_equal(
        np.asarray(eng.surplus("a")),
        np.asarray(ct_transform(g2, scheme)))
