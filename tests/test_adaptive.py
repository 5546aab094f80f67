"""Generalized schemes, incremental executor plans, dimension-adaptive
refinement, and the fault-tolerance recombination hook.

The dict-loop communication phase (``repro.core.combination``) is the
oracle: random downward-closed index sets must round-trip through the
batched executor exactly like the regular schemes do in test_executor.py.
"""

import jax
import numpy as np
import jax.numpy as jnp
import pytest
from proptest import cases, integers, seeds

from repro.core import combination as comb
from repro.core.adaptive import (AdaptiveConfig, AdaptiveDriver,
                                 interpolation_error,
                                 make_anisotropic_target, nodal_sampler)
from repro.core.engine import ExecSpec
from repro.core.executor import (build_plan, ct_scatter, ct_transform,
                                 ct_transform_with_plan, extend_plan,
                                 update_plan_coefficients)
from repro.core.interpolation import sample_function
from repro.core.levels import (CombinationScheme, GeneralScheme,
                               admissible_extensions, downward_closure,
                               fine_levels, grid_shape,
                               inclusion_exclusion_coefficients,
                               is_downward_closed)
from repro.kernels.ops import dehierarchize, hierarchize
from repro.runtime.fault_tolerance import recombine_after_fault


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


def _random_grids(scheme, rng):
    return {ell: jnp.asarray(rng.standard_normal(grid_shape(ell)))
            for ell, _ in scheme.grids}


def _dict_gather(grids, scheme):
    hier = {ell: hierarchize(u, "ref") for ell, u in grids.items()}
    return comb.combine_full(hier, scheme)[0]


# ---------------------------------------------------------------------------
# (a) GeneralScheme: the regular scheme is a special case
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim,level", [(1, 4), (2, 1), (2, 3), (3, 4),
                                       (4, 3), (6, 3), (10, 3)])
def test_general_regular_matches_classical(dim, level):
    cs = CombinationScheme(dim, level)
    gs = GeneralScheme.regular(dim, level)
    assert dict(cs.grids) == dict(gs.grids)
    assert cs.as_general() == gs
    assert fine_levels(cs) == fine_levels(gs)
    assert cs.total_points() == gs.total_points()
    assert cs.sparse_points() == gs.sparse_points()
    assert gs.validate_partition_of_unity()


def test_downward_closure_and_validation():
    closed = downward_closure([(3, 2), (1, 4)])
    assert is_downward_closed(closed)
    assert (1, 1) in closed and (2, 2) in closed and (3, 1) in closed
    with pytest.raises(ValueError, match="downward closed"):
        GeneralScheme(2, ((1, 1), (2, 2)))
    with pytest.raises(ValueError, match="empty"):
        GeneralScheme.from_levels([])
    with pytest.raises(ValueError, match="min level"):
        GeneralScheme(2, ((0, 1), (1, 1)))      # zero-point grids rejected
    # from_levels(close=True) normalizes any generating set
    gs = GeneralScheme.from_levels([(3, 2), (1, 4)], close=True)
    assert gs.index_set == closed


@pytest.mark.parametrize("dim,steps,seed", cases(
    lambda r: (integers(r, 2, 4), integers(r, 2, 8), seeds(r)), n=12))
def test_partition_of_unity_random_sets(dim, steps, seed):
    """Inclusion-exclusion coefficients cover every subspace of ANY
    downward-closed set with total coefficient exactly 1."""
    gs = _random_general_scheme(seed, dim, steps)
    assert gs.validate_partition_of_unity()
    # and the coefficient formula only reports nonzeros
    coeffs = inclusion_exclusion_coefficients(gs.index_set)
    assert all(c != 0 for c in coeffs.values())


# ---------------------------------------------------------------------------
# (b) executor round trips on random downward-closed sets
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim,steps,seed", cases(
    lambda r: (integers(r, 2, 3), integers(r, 2, 10), seeds(r)), n=8) + [
        (4, 6, 123)])
def test_general_ct_transform_matches_dict_path(dim, steps, seed):
    gs = _random_general_scheme(seed, dim, steps)
    grids = _random_grids(gs, np.random.default_rng(seed))
    want = np.asarray(_dict_gather(grids, gs))
    got = np.asarray(ct_transform(grids, gs))
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


def test_general_ct_scatter_roundtrip():
    """transform -> scatter against the subspace-dict oracle, on a set
    whose buckets include singletons (an adaptive set is rarely permutation
    -symmetric)."""
    gs = GeneralScheme.from_levels([(4, 1), (2, 2), (1, 3)], close=True)
    plan = build_plan(gs)
    assert any(len(b.ells) == 1 for b in plan.buckets)
    grids = _random_grids(gs, np.random.default_rng(7))
    hier = {ell: hierarchize(u, "ref") for ell, u in grids.items()}
    combined = comb.gather_subspaces(hier, gs)
    scattered = comb.scatter_subspaces(combined, gs)
    want = {ell: dehierarchize(a, "ref") for ell, a in scattered.items()}
    got = ct_scatter(ct_transform(grids, gs), gs)
    assert set(got) == set(want)
    for ell in got:
        np.testing.assert_allclose(np.asarray(got[ell]),
                                   np.asarray(want[ell]),
                                   rtol=1e-11, atol=1e-12)


def test_executor_input_validation():
    """Missing/empty nodal grids raise a message naming the level vector
    instead of an opaque KeyError."""
    gs = GeneralScheme.regular(2, 3)
    with pytest.raises(ValueError, match="empty"):
        ct_transform({}, gs)
    grids = _random_grids(gs, np.random.default_rng(0))
    del grids[(1, 2)]
    with pytest.raises(ValueError, match=r"\(1, 2\)"):
        ct_transform(grids, gs)


def test_build_plan_cache_normalization():
    """The bare call and every equivalent full_levels spelling share ONE
    lru_cache entry (no duplicate plans)."""
    gs = GeneralScheme.regular(3, 3)
    p = build_plan(gs)
    assert build_plan(gs, fine_levels(gs)) is p
    assert build_plan(gs, list(fine_levels(gs))) is p
    assert build_plan(gs, np.asarray(fine_levels(gs))) is p


# ---------------------------------------------------------------------------
# (c) incremental plan rebuilds
# ---------------------------------------------------------------------------

def _assert_plans_equal(a, b):
    assert a.full_levels == b.full_levels and a.fine_shape == b.fine_shape
    assert len(a.buckets) == len(b.buckets)
    for x, y in zip(a.buckets, b.buckets):
        assert x.ells == y.ells and x.perms == y.perms
        assert x.levels == y.levels and x.target == y.target
        assert np.array_equal(x.coeffs, y.coeffs)
        assert np.array_equal(x.index, y.index)


def test_extend_plan_reuses_untouched_buckets():
    """Adding k grids below the fine grid: untouched buckets come back BY
    IDENTITY, members-unchanged buckets share the index array, and the
    result is bit-identical to a from-scratch build_plan."""
    gs = GeneralScheme.regular(3, 3)
    plan = build_plan(gs)
    adds = [c for c in admissible_extensions(gs.index_set)
            if max(c) <= max(fine_levels(gs))][:3]
    gs2 = gs.with_levels(adds)
    assert fine_levels(gs2) == fine_levels(gs)

    p2 = extend_plan(plan, gs2)
    _assert_plans_equal(p2, build_plan(gs2))

    old_members = {b.target: b for b in plan.buckets}
    for b in p2.buckets:
        ob = old_members.get(b.target)
        if ob is not None and ob.ells == b.ells:
            # untouched member list -> at minimum the index map is shared
            assert b.index is ob.index
            if np.array_equal(ob.coeffs, b.coeffs):
                assert b is ob          # fully untouched -> same object
    # and at least one bucket of the old plan must survive identically
    old_ids = {id(b) for b in plan.buckets}
    assert any(id(b) in old_ids for b in p2.buckets)

    # numerics through the incrementally extended plan
    grids = _random_grids(gs2, np.random.default_rng(3))
    want = np.asarray(_dict_gather(grids, gs2))
    np.testing.assert_allclose(np.asarray(ct_transform_with_plan(grids, p2)),
                               want, rtol=1e-12, atol=1e-12)


def test_extend_plan_full_rebuild_when_fine_grid_grows():
    gs = GeneralScheme.regular(2, 3)
    plan = build_plan(gs)
    gs2 = gs.with_levels([(4, 1)])        # raises fine level of axis 0
    p2 = extend_plan(plan, gs2)
    assert p2.full_levels != plan.full_levels
    _assert_plans_equal(p2, build_plan(gs2))


def test_update_plan_coefficients_keeps_buckets():
    """Grid dropped -> coefficients recomputed, every bucket's index map
    kept by identity; zero-weighted stale data cancels out of the gather."""
    gs = GeneralScheme.regular(3, 3)
    plan = build_plan(gs)
    dropped = max(ell for ell, _ in gs.grids)     # a maximal grid
    gs2 = gs.without_levels([dropped])
    p2 = update_plan_coefficients(plan, gs2)
    assert all(a.index is b.index for a, b in zip(p2.buckets, plan.buckets))
    assert [b.ells for b in p2.buckets] == [b.ells for b in plan.buckets]

    grids = _random_grids(gs, np.random.default_rng(5))
    grids[dropped] = jnp.full_like(grids[dropped], 7.7)   # stale, finite
    want = comb.combine_full(
        {ell: hierarchize(grids[ell], "ref") for ell, _ in gs2.grids}, gs2)[0]
    want_emb = comb.embed_to_full(want, fine_levels(gs2), plan.full_levels)
    np.testing.assert_allclose(np.asarray(ct_transform_with_plan(grids, p2)),
                               np.asarray(want_emb), rtol=1e-12, atol=1e-12)


def test_recombine_after_fault_paths():
    """The fault hook prefers the coefficient-only update and falls back to
    an incremental rebuild when the reduced scheme activates a grid the
    plan never held (the classic d=2 (2,2)-drop -> -u_(1,1) case)."""
    # coefficient-only: drop a corner grid of the top diagonal
    gs = GeneralScheme.regular(2, 3)
    plan = build_plan(gs)
    s2, p2, coeff_only = recombine_after_fault(gs, [(3, 1)], plan=plan)
    assert coeff_only
    assert dict(s2.grids) == {(1, 3): 1, (2, 2): 1, (1, 2): -1}
    assert all(a.index is b.index for a, b in zip(p2.buckets, plan.buckets))

    # fallback: dropping (2, 2) activates (1, 1) with coefficient -1
    s3, p3, coeff_only = recombine_after_fault(gs, [(2, 2)], plan=plan)
    assert not coeff_only
    assert dict(s3.grids) == {(1, 3): 1, (3, 1): 1, (1, 1): -1}
    assert p3.full_levels == plan.full_levels     # same embed indices
    grids = _random_grids(s3, np.random.default_rng(6))
    want = comb.combine_full(
        {ell: hierarchize(u, "ref") for ell, u in grids.items()}, s3)[0]
    want_emb = comb.embed_to_full(want, fine_levels(s3), p3.full_levels)
    np.testing.assert_allclose(np.asarray(ct_transform_with_plan(grids, p3)),
                               np.asarray(want_emb), rtol=1e-12, atol=1e-12)
    # a CombinationScheme input is generalized first
    s4, _, _ = recombine_after_fault(CombinationScheme(2, 3), [(3, 1)],
                                     plan=plan)
    assert dict(s4.grids) == dict(s2.grids)


# ---------------------------------------------------------------------------
# (d) dimension-adaptive refinement
# ---------------------------------------------------------------------------

def test_adaptive_skips_exactly_resolved_axis():
    """f = sin(pi x) * tent(y): the y-factor IS the level-1 hat, so every
    y-refined subspace has zero surplus — the driver must spend its budget
    on x only."""
    f = make_anisotropic_target(2, decay=1e9)   # y-factor ~ exact tent
    drv = AdaptiveDriver(nodal_sampler(f), dim=2,
                         config=AdaptiveConfig(max_points=400, max_level=8))
    drv.run()
    max_x = max(ell[0] for ell in drv.scheme.index_set)
    max_y = max(ell[1] for ell in drv.scheme.index_set)
    assert max_x >= 4          # deep in the axis that needs it
    assert max_y <= 2          # candidates appear but are never refined


@pytest.mark.slow
def test_adaptive_beats_regular_3x_on_anisotropic_6d():
    """The ISSUE's acceptance case: same max-norm error as the regular
    d=6 n=4 scheme with >= 3x fewer combination-grid points.  Slow tier
    (~40 s: the n=4 baseline transform dominates); the refinement
    MECHANISM is covered fast by test_adaptive_skips_exactly_resolved_axis
    and test_adaptive_driver_budget_and_records."""
    from repro.configs.sparse_grid import get_ct_adaptive_config
    cfg = get_ct_adaptive_config("aniso_6d")
    f = make_anisotropic_target(cfg.dim, cfg.decay)
    pts = jnp.asarray(np.random.default_rng(cfg.eval_seed)
                      .random((cfg.eval_points, cfg.dim)))
    sample = nodal_sampler(f)

    reg = CombinationScheme(cfg.dim, cfg.baseline_level)
    nodal = {ell: sample(ell) for ell, _ in reg.grids}
    err_reg = interpolation_error(ct_transform(nodal, reg), f, pts)

    drv = AdaptiveDriver(nodal_sampler(f), dim=cfg.dim,
                         config=AdaptiveConfig(max_points=cfg.max_points,
                                               max_level=cfg.max_level))
    while interpolation_error(drv.surplus, f, pts) > err_reg:
        assert drv.step() is not None, drv.stop_reason
    ratio = reg.total_points() / drv.scheme.total_points()
    assert ratio >= 3.0, ratio
    # surplus indicators ranked the axes by importance
    maxlev = [max(ell[i] for ell in drv.scheme.index_set)
              for i in range(cfg.dim)]
    assert maxlev == sorted(maxlev, reverse=True), maxlev


def test_adaptive_driver_budget_and_records():
    f = make_anisotropic_target(3)
    drv = AdaptiveDriver(nodal_sampler(f), dim=3,
                         config=AdaptiveConfig(max_points=300))
    res = drv.run()
    assert res.stop_reason == "budget"
    assert res.scheme.validate_partition_of_unity()
    assert drv.solved_points() <= 300
    for rec in res.history:
        assert rec.solved_points <= 300
        assert rec.indicator > 0
        # every expansion stays downward closed and admissible
        assert is_downward_closed(res.scheme.index_set)
    # identity-based reuse accounting matches the full_rebuild flag
    assert all(r.buckets_reused == 0 for r in res.history if r.full_rebuild)


def test_ct_surrogate_general_scheme_and_fault():
    """CTSurrogate serves a GeneralScheme and recovers from a dropped grid
    via the coefficient-only path."""
    from repro.launch.serve import CTSurrogate
    gs = GeneralScheme.from_levels([(4, 1), (3, 2), (2, 3), (1, 4)],
                                   close=True)
    u = lambda a, b: jnp.sin(2 * a) * (b - b * b)
    grids = {ell: sample_function(u, ell) for ell, _ in gs.grids}
    srv = CTSurrogate(gs, grids)
    pts = np.random.default_rng(8).random((32, 2))
    want = np.asarray(comb.combined_interpolant_points(
        grids, gs, jnp.asarray(pts)))
    np.testing.assert_allclose(srv.query(pts), want, rtol=1e-9, atol=1e-10)

    dropped = (4, 1)
    reduced = gs.without_levels([dropped])
    grids_after = dict(grids)
    grids_after[dropped] = jnp.zeros_like(grids[dropped])
    srv.drop_grid([dropped], grids_after)
    assert srv.scheme == reduced
    want2 = np.asarray(comb.combined_interpolant_points(
        {ell: grids[ell] for ell, _ in reduced.grids}, reduced,
        jnp.asarray(pts)))
    np.testing.assert_allclose(srv.query(pts), want2, rtol=1e-9, atol=1e-10)
    # the ingest step was rebound: a routine update() after the fault must
    # recombine with the REDUCED coefficients, not the pre-fault scheme's
    srv.update({ell: 2.0 * g for ell, g in grids_after.items()})
    np.testing.assert_allclose(srv.query(pts), 2 * want2,
                               rtol=1e-9, atol=1e-10)


@pytest.mark.multidevice
def test_ct_surrogate_on_mesh_matches_single_device_and_fault():
    """CTSurrogate under ``ExecSpec(mesh=)`` runs the slab-sharded ingest:
    queries, drop_grid (coefficient-only path) and post-fault updates all
    equal the single-device surrogate bit-for-bit."""
    from jax.sharding import AxisType
    from repro.launch.serve import CTSurrogate
    mesh = jax.make_mesh((8,), ("slab",), axis_types=(AxisType.Auto,))
    gs = GeneralScheme.from_levels([(4, 1), (3, 2), (2, 3), (1, 4)],
                                   close=True)
    u = lambda a, b: jnp.sin(2 * a) * (b - b * b)
    grids = {ell: sample_function(u, ell) for ell, _ in gs.grids}
    srv = CTSurrogate(gs, grids, spec=ExecSpec(mesh=mesh))
    ref = CTSurrogate(gs, grids)
    pts = np.random.default_rng(8).random((32, 2))
    np.testing.assert_array_equal(np.asarray(srv.surplus),
                                  np.asarray(ref.surplus))
    np.testing.assert_array_equal(srv.query(pts), ref.query(pts))

    dropped = (4, 1)
    grids_after = dict(grids)
    grids_after[dropped] = jnp.zeros_like(grids[dropped])
    srv.drop_grid([dropped], grids_after)
    ref.drop_grid([dropped], grids_after)
    assert srv.scheme == gs.without_levels([dropped]) == ref.scheme
    np.testing.assert_array_equal(srv.query(pts), ref.query(pts))
    # the rebound ingest keeps running sharded with reduced coefficients
    srv.update({ell: 2.0 * g for ell, g in grids_after.items()})
    ref.update({ell: 2.0 * g for ell, g in grids_after.items()})
    np.testing.assert_array_equal(srv.query(pts), ref.query(pts))


@pytest.mark.multidevice
def test_ct_surrogate_on_mesh_fault_fallback_path():
    """The extend_plan fallback (dropping (2,2) activates (1,1)) also works
    on a mesh: failure leaves the surrogate unchanged, success re-shards
    the extended plan and matches the serial recombination."""
    from jax.sharding import AxisType
    from repro.launch.serve import CTSurrogate
    mesh = jax.make_mesh((8,), ("slab",), axis_types=(AxisType.Auto,))
    gs = GeneralScheme.regular(2, 3)
    u = lambda a, b: jnp.sin(2 * a) * (b - b * b)
    grids = {ell: sample_function(u, ell) for ell, _ in gs.grids}
    pts = np.random.default_rng(9).random((32, 2))

    srv = CTSurrogate(gs, grids, spec=ExecSpec(mesh=mesh))
    before = srv.query(pts)
    with pytest.raises(ValueError, match=r"\(1, 1\)"):
        srv.drop_grid([(2, 2)], grids)      # (1, 1) data not supplied
    assert srv.scheme == gs                  # untouched on failure
    np.testing.assert_array_equal(srv.query(pts), before)

    full = dict(grids)
    full[(1, 1)] = sample_function(u, (1, 1))
    srv.drop_grid([(2, 2)], full)
    reduced = gs.without_levels([(2, 2)])
    assert srv.scheme == reduced
    want = np.asarray(comb.combined_interpolant_points(
        {ell: full[ell] for ell, _ in reduced.grids}, reduced,
        jnp.asarray(pts)))
    np.testing.assert_allclose(srv.query(pts), want, rtol=1e-9, atol=1e-10)


def test_ct_surrogate_fault_fallback_path():
    """Dropping (2,2) from the regular 2-D scheme activates (1,1): with
    its data supplied the surrogate recovers through the extend_plan
    fallback; without it, drop_grid raises and leaves the state intact."""
    from repro.launch.serve import CTSurrogate
    gs = GeneralScheme.regular(2, 3)
    u = lambda a, b: jnp.sin(2 * a) * (b - b * b)
    grids = {ell: sample_function(u, ell) for ell, _ in gs.grids}
    pts = np.random.default_rng(9).random((32, 2))

    srv = CTSurrogate(gs, grids)
    before = srv.query(pts)
    with pytest.raises(ValueError, match=r"\(1, 1\)"):
        srv.drop_grid([(2, 2)], grids)      # (1, 1) data not supplied
    assert srv.scheme == gs                  # untouched on failure
    np.testing.assert_allclose(srv.query(pts), before)

    full = dict(grids)
    full[(1, 1)] = sample_function(u, (1, 1))
    srv.drop_grid([(2, 2)], full)
    reduced = gs.without_levels([(2, 2)])
    assert srv.scheme == reduced
    want = np.asarray(comb.combined_interpolant_points(
        {ell: full[ell] for ell, _ in reduced.grids}, reduced,
        jnp.asarray(pts)))
    np.testing.assert_allclose(srv.query(pts), want, rtol=1e-9, atol=1e-10)
