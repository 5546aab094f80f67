"""Slab-sharded scatter-add executor: ShardedPlan invariants (pure
numpy, no devices needed) and multi-device property tests pinning the
sharded gather to the single-device ``ct_transform`` over random
downward-closed schemes, group counts (ragged last slab included) and
dtypes."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from proptest import cases, integers, seeds

from jax.sharding import AxisType
from repro.core.distributed import ct_transform_sharded, gather_slab_scatter
from repro.core.executor import (build_plan, bucket_surpluses, ct_transform,
                                 ct_transform_with_plan, extend_plan,
                                 shard_plan, update_plan_coefficients,
                                 ShardedPlan)
from repro.core.levels import (CombinationScheme, GeneralScheme,
                               admissible_extensions, fine_levels,
                               grid_shape)


def _random_general_scheme(seed, dim, steps, max_level=4):
    """Seeded random downward-closed index set grown by admissible steps."""
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


def _mesh(n, name="slab"):
    return jax.make_mesh((n,), (name,), devices=np.array(jax.devices()[:n]),
                         axis_types=(AxisType.Auto,))


# ---------------------------------------------------------------------------
# (a) ShardedPlan invariants — single-device, no mesh required
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim,steps,n_slabs,seed", cases(
    lambda r: (integers(r, 2, 3), integers(r, 2, 8), integers(r, 1, 9),
               seeds(r)), n=10))
def test_slab_split_partitions_index_map(dim, steps, n_slabs, seed):
    """Every non-pad entry of the base index map lands in EXACTLY one
    slab (at its slab-local offset); pad entries dump in every slab; the
    per-member row ranges agree with the rows that actually land."""
    gs = _random_general_scheme(seed, dim, steps)
    plan = build_plan(gs)
    splan = shard_plan(plan, n_slabs)
    assert splan.slab_rows * n_slabs >= plan.fine_shape[0]
    row_size = splan.row_size
    for b, sb in zip(plan.buckets, splan.slab_buckets):
        assert sb.index.shape == (n_slabs,) + b.index.shape
        hits = np.zeros(b.index.shape, np.int64)
        for s in range(n_slabs):
            local = sb.index[s]
            in_slab = local != splan.slab_size
            hits += in_slab
            # slab-local offset reconstructs the global index
            np.testing.assert_array_equal(
                (local + s * splan.slab_size)[in_slab], b.index[in_slab])
            # row ranges: exactly the members' leading-axis nodes in slab s
            for gi, ell in enumerate(b.ells):
                step = 1 << (plan.full_levels[0] - ell[0])
                rows = (np.arange((1 << ell[0]) - 1) + 1) * step - 1
                lo, hi = s * splan.slab_rows, (s + 1) * splan.slab_rows
                want = np.nonzero((rows >= lo) & (rows < hi))[0]
                start, stop = sb.row_ranges[s, gi]
                np.testing.assert_array_equal(np.arange(start, stop), want)
        pad = b.index == plan.fine_size
        assert np.all(hits[~pad] == 1)      # exactly one owning slab
        assert np.all(hits[pad] == 0)       # pads dump everywhere


def test_shard_plan_validation():
    plan = build_plan(CombinationScheme(2, 3))
    with pytest.raises(ValueError, match="n_slabs"):
        shard_plan(plan, 0)
    with pytest.raises(TypeError, match="unsharded"):
        shard_plan(shard_plan(plan, 2), 2)


def test_sharded_plan_single_device_fallback():
    """ct_transform_with_plan accepts a ShardedPlan and runs the base
    plan — bit-identical to the unsharded transform."""
    gs = GeneralScheme.regular(3, 3)
    splan = shard_plan(build_plan(gs), 4)
    grids = _random_grids(gs, np.random.default_rng(0))
    np.testing.assert_array_equal(
        np.asarray(ct_transform_with_plan(grids, splan)),
        np.asarray(ct_transform(grids, gs)))


def test_sharded_plan_incremental_updates_reuse_slabs():
    """extend_plan / update_plan_coefficients on a ShardedPlan re-shard
    incrementally: surviving buckets keep their SlabBucket by identity,
    and the result equals a from-scratch shard of the rebuilt base."""
    gs = GeneralScheme.regular(3, 3)
    splan = shard_plan(build_plan(gs), 4)

    # coefficient-only: every slab split survives by identity
    dropped = max(ell for ell, _ in gs.grids)
    gs2 = gs.without_levels([dropped])
    s2 = update_plan_coefficients(splan, gs2)
    assert isinstance(s2, ShardedPlan) and s2.n_slabs == 4
    assert all(a is b for a, b in zip(s2.slab_buckets, splan.slab_buckets))

    # extension below the fine grid: untouched buckets' splits survive
    adds = [c for c in admissible_extensions(gs.index_set)
            if max(c) <= max(fine_levels(gs))][:2]
    gs3 = gs.with_levels(adds)
    s3 = extend_plan(splan, gs3)
    assert s3.full_levels == splan.full_levels
    old = {id(b.index): sb
           for b, sb in zip(splan.plan.buckets, splan.slab_buckets)}
    reused = sum(old.get(id(b.index)) is sb
                 for b, sb in zip(s3.plan.buckets, s3.slab_buckets))
    assert reused > 0
    fresh = shard_plan(build_plan(gs3), 4)
    for a, b in zip(s3.slab_buckets, fresh.slab_buckets):
        np.testing.assert_array_equal(a.index, b.index)
        np.testing.assert_array_equal(a.row_ranges, b.row_ranges)


# ---------------------------------------------------------------------------
# (b) sharded scatter-add == single-device ct_transform (property tests)
# ---------------------------------------------------------------------------

@pytest.mark.multidevice
@pytest.mark.parametrize("dim,steps,n_groups,dtype,seed", cases(
    lambda r: (integers(r, 2, 3), integers(r, 2, 8), integers(r, 1, 8),
               ("float32", "float64")[integers(r, 0, 1)], seeds(r)), n=10))
def test_sharded_gather_matches_single_device(dim, steps, n_groups, dtype,
                                              seed):
    """Random downward-closed GeneralScheme, random group count (the fine
    leading extent 2**L - 1 is odd, so any even n_groups forces a ragged
    last slab), random dtype: slab-sharded gather == ct_transform."""
    gs = _random_general_scheme(seed, dim, steps)
    grids = _random_grids(gs, np.random.default_rng(seed), np.dtype(dtype))
    mesh = _mesh(n_groups)
    want = np.asarray(ct_transform(grids, gs))
    assert want.dtype == np.dtype(dtype)
    got = np.asarray(ct_transform_sharded(grids, gs, mesh, "slab"))
    assert got.dtype == want.dtype
    rtol = 1e-6 if dtype == "float32" else 1e-12
    np.testing.assert_allclose(got, want, rtol=rtol, atol=rtol)


@pytest.mark.multidevice
@pytest.mark.parametrize("n_groups", [2, 3, 4, 5, 7, 8])
def test_sharded_gather_bit_identical_ragged(n_groups):
    """The slab decomposition preserves per-slot addition order, so the
    sharded gather is bit-identical (not just allclose) to the dense one
    — across odd group counts too.  On the 15-row leading extent the
    counts 2/4/7/8 leave a short ragged last slab while the odd divisors
    3 and 5 split it evenly, so both slab geometries are pinned here."""
    scheme = CombinationScheme(3, 4)
    ragged = grid_shape(fine_levels(scheme))[0] % n_groups != 0
    assert ragged == (n_groups not in (3, 5))
    grids = _random_grids(scheme, np.random.default_rng(n_groups))
    want = np.asarray(ct_transform(grids, scheme))
    got = np.asarray(ct_transform_sharded(grids, scheme, mesh=_mesh(n_groups),
                                          axis_name="slab"))
    np.testing.assert_array_equal(got, want)


@pytest.mark.multidevice
def test_gather_slab_scatter_validates_inputs():
    gs = GeneralScheme.regular(2, 3)
    grids = _random_grids(gs, np.random.default_rng(1))
    splan = shard_plan(build_plan(gs), 4)
    alphas = bucket_surpluses(grids, splan)
    with pytest.raises(ValueError, match="8 device"):
        gather_slab_scatter(alphas, splan, _mesh(8), "slab")
    with pytest.raises(ValueError, match="bucket"):
        gather_slab_scatter(alphas[:-1], splan, _mesh(4), "slab")


@pytest.mark.multidevice
def test_sharded_gather_after_fault_recombination():
    """recombine_after_fault on a ShardedPlan: the sharded gather through
    the updated plan equals the serial recombination (stale finite data in
    the dropped grid cancels)."""
    from repro.runtime.fault_tolerance import recombine_after_fault
    gs = GeneralScheme.regular(3, 3)
    splan = shard_plan(build_plan(gs), 8)
    dropped = max(ell for ell, _ in gs.grids)
    s2, p2, coeff_only = recombine_after_fault(gs, [dropped], plan=splan)
    assert coeff_only and isinstance(p2, ShardedPlan)

    grids = _random_grids(gs, np.random.default_rng(5))
    grids[dropped] = jnp.full_like(grids[dropped], 7.7)   # stale, finite
    mesh = _mesh(8)
    alphas = bucket_surpluses(grids, p2)
    got = np.asarray(gather_slab_scatter(alphas, p2, mesh, "slab"))
    want = np.asarray(ct_transform_with_plan(grids, p2))
    np.testing.assert_array_equal(got, want)
    # and against the serial recombination of the reduced scheme
    reduced = {ell: grids[ell] for ell, _ in s2.grids}
    from repro.core import combination as comb
    from repro.kernels.ops import hierarchize
    serial = comb.combine_full({ell: hierarchize(u, "ref")
                                for ell, u in reduced.items()}, s2)[0]
    emb = comb.embed_to_full(serial, fine_levels(s2), p2.full_levels)
    np.testing.assert_allclose(got, np.asarray(emb), rtol=1e-12, atol=1e-12)
