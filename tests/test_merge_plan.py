"""Cost-model-driven bucket merging.

Three layers:

  (a) merge-plan invariants — pure numpy, no devices: every scheme grid
      lands in exactly one super-bucket slot, pad positions all route to
      the dump slot, the partition is contiguous in the descending shape
      order, and incremental rebuilds of merged plans are bit-identical
      to from-scratch merged builds.
  (b) seeded end-to-end property tests of below-target (padded) bucket
      members: merged ``ct_transform`` bit-identical (f64; 1e-6 at f32)
      to the unmerged path over random downward-closed schemes,
      ``ct_scatter`` through merged plans against the unmerged oracle.
  (c) the sharded gather through merged and Pallas-path plans with
      per-slab local maps (multidevice tier).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from proptest import cases, integers, seeds

from repro.core.executor import (MergeConfig, build_plan, bucket_surpluses,
                                 ct_scatter_with_plan,
                                 ct_transform, ct_transform_with_plan,
                                 extend_plan, plan_launch_stats, shard_plan,
                                 update_plan_coefficients)
from repro.kernels.hierarchize import batched_method
from repro.core.levels import (CombinationScheme, GeneralScheme,
                               admissible_extensions, canonical_levels,
                               grid_shape)

#: merge everything the member cap allows: launch overhead priced far above
#: any pad waste at test scale, so below-target members are guaranteed
AGGRESSIVE = MergeConfig(launch_cost_bytes=1 << 30)
#: pure pad-waste pricing: launches are free, so nothing should merge
NO_MERGE_GAIN = MergeConfig(launch_cost_bytes=0)


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


# ---------------------------------------------------------------------------
# (a) merge-plan invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim,steps,seed", cases(
    lambda r: (integers(r, 2, 4), integers(r, 2, 10), seeds(r)), n=10))
def test_every_member_in_exactly_one_super_bucket(dim, steps, seed):
    gs = _random_general_scheme(seed, dim, steps)
    plan = build_plan(gs, merge=AGGRESSIVE)
    slots = [(ell, g) for b in plan.buckets for g, ell in enumerate(b.ells)]
    assert len(slots) == len(gs.grids)
    assert sorted(ell for ell, _ in slots) == sorted(ell for ell, _ in
                                                     gs.grids)
    # contiguity: buckets stay sorted by descending canonical target, and
    # member canonical keys never interleave across buckets
    targets = [b.target for b in plan.buckets]
    assert targets == sorted(targets, reverse=True)
    key_seq = [canonical_levels(ell)[0] for b in plan.buckets
               for ell in b.ells]
    assert key_seq == sorted(key_seq, reverse=True)


@pytest.mark.parametrize("dim,steps,seed", cases(
    lambda r: (integers(r, 2, 3), integers(r, 3, 10), seeds(r)), n=8))
def test_merged_index_maps_route_pads_to_dump(dim, steps, seed):
    """Below-target members: real positions inject into the fine buffer,
    every pad position of the padded canonical array hits the dump slot."""
    gs = _random_general_scheme(seed, dim, steps)
    plan = build_plan(gs, merge=AGGRESSIVE)
    assert any(len(set(b.levels)) > 1 for b in plan.buckets), \
        "aggressive merge produced no below-target members"
    for b in plan.buckets:
        for g, ell in enumerate(b.ells):
            n_real = int(np.prod(grid_shape(ell)))
            idx = b.index[g]
            real = idx[idx < plan.fine_size]
            assert len(real) == n_real
            assert len(set(real.tolist())) == n_real      # injective
            assert (idx[idx >= plan.fine_size] == plan.fine_size).all()


def test_merge_cost_model_extremes():
    """Launch-dominated pricing merges everything (one super-bucket);
    zero launch cost keeps the exact-canonical partition."""
    scheme = CombinationScheme(3, 4)
    base = build_plan(scheme)
    assert len(build_plan(scheme, merge=AGGRESSIVE).buckets) == 1
    free = build_plan(scheme, merge=NO_MERGE_GAIN)
    assert [b.target for b in free.buckets] == [b.target for b in
                                                base.buckets]
    capped = build_plan(scheme,
                        merge=MergeConfig(launch_cost_bytes=1 << 30,
                                          max_members=3))
    assert len(capped.buckets) > 1
    assert all(len(b.ells) <= max(3, max(len(g.ells) for g in base.buckets))
               for b in capped.buckets)


def test_merge_reduces_launches_wide_diagonal():
    """The ROADMAP acceptance shape: d=10 wide diagonal, >= 2x fewer
    dispatches under the default cost model."""
    scheme = CombinationScheme(10, 2)
    s0 = plan_launch_stats(build_plan(scheme))
    s1 = plan_launch_stats(build_plan(scheme, merge=MergeConfig()))
    assert s1["buckets"] < s0["buckets"]
    assert s0["launches"] >= 2 * s1["launches"]


@pytest.mark.parametrize("dim,steps,seed", cases(
    lambda r: (integers(r, 2, 3), integers(r, 2, 8), seeds(r)), n=6))
def test_extend_merged_plan_bit_identical_to_scratch(dim, steps, seed):
    """extend_plan on a merged plan == from-scratch merged build of the
    extended scheme, array for array; surviving buckets reused."""
    gs = _random_general_scheme(seed, dim, steps)
    plan = build_plan(gs, merge=AGGRESSIVE)
    adds = [c for c in admissible_extensions(gs.index_set) if max(c) <= 4][:2]
    if not adds:
        pytest.skip("frontier exhausted")
    gs2 = gs.with_levels(adds)
    inc = extend_plan(plan, gs2)
    scratch = build_plan(gs2, merge=AGGRESSIVE)
    assert inc.merge == scratch.merge == AGGRESSIVE
    assert len(inc.buckets) == len(scratch.buckets)
    for a, b in zip(inc.buckets, scratch.buckets):
        assert a.ells == b.ells and a.target == b.target
        assert a.perms == b.perms and a.levels == b.levels
        np.testing.assert_array_equal(a.coeffs, b.coeffs)
        np.testing.assert_array_equal(a.index, b.index)


def test_extend_plan_identity_reuse_with_duplicate_targets():
    """Two super-buckets may share a componentwise-max target (the member
    cap splits a run); identity reuse is keyed by the member tuple, so an
    unchanged scheme still returns EVERY bucket by object identity."""
    from dataclasses import dataclass
    from typing import Tuple

    @dataclass(frozen=True)
    class _FakeScheme:
        dim: int
        grids: Tuple

    gs = _FakeScheme(2, (((3, 2), 1), ((2, 3), 1), ((3, 1), 1),
                         ((1, 3), 1), ((2, 2), 1)))
    cfg = MergeConfig(launch_cost_bytes=1 << 30, max_members=3)
    plan = build_plan(gs, merge=cfg)
    targets = [b.target for b in plan.buckets]
    assert len(targets) != len(set(targets)), \
        "expected a duplicate-target partition for this scheme/config"
    again = extend_plan(plan, gs)
    assert all(a is b for a, b in zip(plan.buckets, again.buckets))


def test_coefficient_update_keeps_super_buckets():
    gs = GeneralScheme.regular(3, 3)
    plan = build_plan(gs, merge=AGGRESSIVE)
    dropped = max(ell for ell, _ in gs.grids)
    upd = update_plan_coefficients(plan, gs.without_levels([dropped]))
    assert upd.merge == AGGRESSIVE
    assert all(a.index is b.index for a, b in zip(plan.buckets, upd.buckets))
    assert all(a.ells == b.ells for a, b in zip(plan.buckets, upd.buckets))


def test_merged_shard_plan_partitions_like_base():
    """shard_plan on a merged plan: every non-pad entry of every merged
    index map still lands in exactly one slab."""
    gs = GeneralScheme.regular(3, 3)
    plan = build_plan(gs, merge=AGGRESSIVE)
    splan = shard_plan(plan, 5)
    for b, sb in zip(plan.buckets, splan.slab_buckets):
        hits = np.zeros(b.index.shape, np.int64)
        for s in range(5):
            hits += sb.index[s] != splan.slab_size
        pad = b.index == plan.fine_size
        assert np.all(hits[~pad] == 1)
        assert np.all(hits[pad] == 0)


# ---------------------------------------------------------------------------
# (b) end-to-end: padded members through transform / scatter / embedded
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim,steps,dtype,seed", cases(
    lambda r: (integers(r, 2, 3), integers(r, 2, 8),
               ("float32", "float64")[integers(r, 0, 1)], seeds(r)), n=12))
def test_merged_fused_transform_matches_unmerged(dim, steps, dtype, seed):
    """Random downward-closed schemes x dtypes: merged plan == unmerged
    path — bit-identical at f64, 1e-6 at f32 (the 3-term kernels are
    bitwise padding-independent; the f32 tolerance only covers
    platforms whose scatter departs)."""
    gs = _random_general_scheme(seed, dim, steps)
    grids = _random_grids(gs, np.random.default_rng(seed), np.dtype(dtype))
    plain = build_plan(gs)
    merged = build_plan(gs, merge=AGGRESSIVE)
    want = np.asarray(ct_transform_with_plan(grids, plain))
    for plan in (merged, build_plan(gs, merge=MergeConfig())):
        got = np.asarray(ct_transform_with_plan(grids, plan))
        assert got.dtype == want.dtype
        if dtype == "float64":
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dim,level", [(2, 4), (3, 3)])
def test_merged_scatter_matches_unmerged(dim, level):
    """Scatter phase through a merged plan: below-target members read
    their strided slots and dehierarchize with the padded inverse
    operators — equal to the unmerged scatter on every grid."""
    scheme = CombinationScheme(dim, level)
    grids = _random_grids(scheme, np.random.default_rng(1))
    full = ct_transform(grids, scheme)
    want = ct_scatter_with_plan(full, build_plan(scheme))
    got = ct_scatter_with_plan(full, build_plan(scheme, merge=AGGRESSIVE))
    assert set(got) == set(want)
    for ell in got:
        np.testing.assert_allclose(np.asarray(got[ell]),
                                   np.asarray(want[ell]),
                                   rtol=1e-12, atol=1e-12)


#: a near-square scheme whose every bucket takes the Pallas path
_PALLAS_SCHEME = GeneralScheme.from_levels([(6, 5), (5, 6)], close=True)


def test_pallas_plan_accounting_and_merge_bitwise():
    """The near-square scheme runs the Pallas kernels for most of its
    volume: the plan-derived accounting counts one scatter per bucket
    and the kernel launches, and a merged plan stays bit-identical."""
    plan = build_plan(_PALLAS_SCHEME)
    assert batched_method(plan.buckets[0].shape) == "pallas"
    stats = plan_launch_stats(plan)
    assert stats["scatter_dispatches"] == len(plan.buckets)
    assert stats["pallas_launches"] >= 2 and stats["stack_bytes"] > 0
    grids = _random_grids(_PALLAS_SCHEME, np.random.default_rng(4))
    want = np.asarray(ct_transform_with_plan(grids, plan))
    merged = build_plan(_PALLAS_SCHEME, merge=MergeConfig())
    np.testing.assert_array_equal(
        np.asarray(ct_transform_with_plan(grids, merged)), want)


def test_fused_transform_jits_once():
    """The Pallas-path transform keeps the one-trace contract of the
    executor."""
    gs = _PALLAS_SCHEME
    plan = build_plan(gs, merge=MergeConfig())
    traces = []

    def fn(grids):
        traces.append(1)
        return ct_transform_with_plan(grids, plan)

    jitted = jax.jit(fn)
    out1 = jitted(_random_grids(gs, np.random.default_rng(0)))
    out2 = jitted(_random_grids(gs, np.random.default_rng(1)))
    jax.block_until_ready((out1, out2))
    assert len(traces) == 1 and jitted._cache_size() == 1


# ---------------------------------------------------------------------------
# (c) sharded gather through merged plans / Pallas-path plans
# ---------------------------------------------------------------------------

def _mesh(n, name="slab"):
    from jax.sharding import AxisType
    return jax.make_mesh((n,), (name,), devices=np.array(jax.devices()[:n]),
                         axis_types=(AxisType.Auto,))


@pytest.mark.multidevice
@pytest.mark.parametrize("dim,steps,n_groups,seed", cases(
    lambda r: (integers(r, 2, 3), integers(r, 2, 8), integers(r, 2, 8),
               seeds(r)), n=6))
def test_sharded_gather_merged_plan_matches_single_device(dim, steps,
                                                          n_groups, seed):
    """Slab-sharded gather off a MERGED plan (padded members routed via
    per-slab local maps) == single-device unmerged ct_transform, bitwise."""
    from repro.core.distributed import ct_transform_sharded
    gs = _random_general_scheme(seed, dim, steps)
    grids = _random_grids(gs, np.random.default_rng(seed))
    splan = shard_plan(build_plan(gs, merge=AGGRESSIVE), n_groups)
    want = np.asarray(ct_transform(grids, gs))
    got = np.asarray(ct_transform_sharded(grids, gs, _mesh(n_groups), "slab",
                                          plan=splan))
    np.testing.assert_array_equal(got, want)


@pytest.mark.multidevice
@pytest.mark.parametrize("n_groups", [2, 5, 8])
def test_sharded_pallas_plan_matches_single_device(n_groups):
    """gather_slab_scatter over a Pallas-path plan (per-slab local maps,
    ragged slabs included) == single-device ct_transform, bitwise."""
    from repro.core.distributed import gather_slab_scatter
    grids = _random_grids(_PALLAS_SCHEME, np.random.default_rng(n_groups))
    splan = shard_plan(build_plan(_PALLAS_SCHEME), n_groups)
    got = np.asarray(gather_slab_scatter(
        bucket_surpluses(grids, splan), splan, _mesh(n_groups), "slab"))
    np.testing.assert_array_equal(
        got, np.asarray(ct_transform(grids, _PALLAS_SCHEME)))
