"""Batched CT executor == dict-based communication phase, single-jit proof,
bucketing edge cases."""

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from proptest import cases, integers, seeds

from repro.core import combination as comb
from repro.core.executor import build_plan, ct_scatter, ct_transform
from repro.core.levels import (CombinationScheme, LevelVector,
                               canonical_levels, grid_shape)
from repro.kernels.hierarchize import (hierarchize_batched,
                                       hierarchize_batched_jnp)
from repro.kernels.ops import dehierarchize, hierarchize


def _random_grids(scheme, rng):
    return {ell: jnp.asarray(rng.standard_normal(grid_shape(ell)))
            for ell, _ in scheme.grids}


def _dict_gather(grids, scheme):
    hier = {ell: hierarchize(u, "ref") for ell, u in grids.items()}
    return comb.combine_full(hier, scheme)[0]


# ---------------------------------------------------------------------------
# (a) equivalence with the dict path, d in {2, 3, 4}
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim,level,seed", cases(
    lambda r: (integers(r, 2, 3), integers(r, 2, 3), seeds(r)), n=6) + [
        (2, 4, 11), (2, 5, 12), (4, 2, 13), (4, 3, 14),
        pytest.param(3, 4, 15, marks=pytest.mark.slow),
        pytest.param(4, 4, 16, marks=pytest.mark.slow)])
def test_ct_transform_matches_dict_path(dim, level, seed):
    scheme = CombinationScheme(dim, level)
    grids = _random_grids(scheme, np.random.default_rng(seed))
    want = np.asarray(_dict_gather(grids, scheme))
    got = np.asarray(ct_transform(grids, scheme))
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("dim,level", [
    (2, 4), (3, 3),
    pytest.param(2, 5, marks=pytest.mark.slow),
    pytest.param(3, 4, marks=pytest.mark.slow),
    pytest.param(4, 3, marks=pytest.mark.slow)])
def test_ct_scatter_matches_dict_path(dim, level):
    """Scatter phase: executor == subspace-gather/scatter + dehierarchize."""
    scheme = CombinationScheme(dim, level)
    grids = _random_grids(scheme, np.random.default_rng(1))
    hier = {ell: hierarchize(u, "ref") for ell, u in grids.items()}
    combined = comb.gather_subspaces(hier, scheme)
    scattered = comb.scatter_subspaces(combined, scheme)
    want = {ell: dehierarchize(a, "ref") for ell, a in scattered.items()}
    got = ct_scatter(ct_transform(grids, scheme), scheme)
    assert set(got) == set(want)
    for ell in got:
        np.testing.assert_allclose(np.asarray(got[ell]),
                                   np.asarray(want[ell]),
                                   rtol=1e-11, atol=1e-12)


@pytest.mark.slow
def test_high_dim_scheme():
    """d=10 exercises the jnp (no-tile-padding) batched path end to end
    (pallas==jnp numerics are also pinned fast by
    test_batched_pallas_matches_jnp)."""
    scheme = CombinationScheme(10, 2)
    grids = _random_grids(scheme, np.random.default_rng(3))
    want = np.asarray(_dict_gather(grids, scheme))
    got = np.asarray(ct_transform(grids, scheme))
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


# ---------------------------------------------------------------------------
# (b) the whole pipeline is ONE jitted function
# ---------------------------------------------------------------------------

def test_single_trace_single_cache_entry():
    """New grid VALUES never retrace: the bucket plan and index maps are
    trace-time constants, so the jit cache holds exactly one entry per
    scheme shape signature regardless of grid count."""
    scheme = CombinationScheme(3, 4)      # 22 grids -> must stay 1 trace
    traces = []

    def fn(nodal_grids):
        traces.append(1)
        return ct_transform(nodal_grids, scheme)

    jitted = jax.jit(fn)
    out1 = jitted(_random_grids(scheme, np.random.default_rng(0)))
    out2 = jitted(_random_grids(scheme, np.random.default_rng(1)))
    jax.block_until_ready((out1, out2))
    assert len(traces) == 1
    assert jitted._cache_size() == 1
    assert not np.allclose(np.asarray(out1), np.asarray(out2))


def test_make_ct_step_jits_once():
    from repro.launch.steps import make_ct_step
    scheme = CombinationScheme(2, 4)
    step = make_ct_step(scheme)
    a = step(_random_grids(scheme, np.random.default_rng(0)))
    b = step(_random_grids(scheme, np.random.default_rng(4)))
    jax.block_until_ready((a, b))
    assert step._cache_size() == 1
    want = _dict_gather(_random_grids(scheme, np.random.default_rng(4)),
                        scheme)
    np.testing.assert_allclose(np.asarray(b), np.asarray(want),
                               rtol=1e-12, atol=1e-12)


def test_make_ct_eval_step_fused_transform_eval():
    """The one-shot transform+eval step == ct_transform followed by
    hierarchical-basis interpolation (and == the direct interpolant)."""
    from repro.core.interpolation import interpolate_hierarchical
    from repro.launch.steps import make_ct_eval_step
    scheme = CombinationScheme(2, 4)
    grids = _random_grids(scheme, np.random.default_rng(9))
    pts = jnp.asarray(np.random.default_rng(10).random((32, 2)))
    step = make_ct_eval_step(scheme)
    got = step(grids, pts)
    want = interpolate_hierarchical(ct_transform(grids, scheme), pts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-12, atol=1e-12)
    direct = comb.combined_interpolant_points(grids, scheme, pts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(direct),
                               rtol=1e-8, atol=1e-9)
    assert step._cache_size() == 1


# ---------------------------------------------------------------------------
# (c) bucketing edge cases
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class _FakeScheme:
    """Minimal scheme stand-in: arbitrary (level vector, coefficient) sets."""
    dim: int
    grids: Tuple[Tuple[LevelVector, int], ...]


def test_all_singleton_buckets():
    """A scheme where no two grids share a shape (even up to transposition)
    degrades to one launch per grid but stays exact."""
    scheme = _FakeScheme(2, (((1, 2), 1), ((1, 3), -1), ((2, 3), 1),
                             ((3, 3), 1)))
    plan = build_plan(scheme)
    assert len(plan.buckets) == 4
    assert all(len(b.ells) == 1 for b in plan.buckets)
    rng = np.random.default_rng(5)
    grids = {ell: jnp.asarray(rng.standard_normal(grid_shape(ell)))
             for ell, _ in scheme.grids}
    hier = {ell: hierarchize(u, "ref") for ell, u in grids.items()}
    want = np.asarray(comb.combine_full(hier, scheme)[0])
    np.testing.assert_allclose(np.asarray(ct_transform(grids, scheme)),
                               want, rtol=1e-12, atol=1e-12)


def test_transposed_grids_share_bucket():
    """All axis-permutations of one level multiset land in one bucket."""
    scheme = CombinationScheme(3, 3)      # diagonal |ell|=5 has (3,1,1) perms
    plan = build_plan(scheme)
    n_perm_classes = len({tuple(sorted(ell, reverse=True))
                          for ell, _ in scheme.grids})
    assert len(plan.buckets) == n_perm_classes
    assert plan.num_grids == len(scheme.grids)
    for b in plan.buckets:
        for ell, perm, canon in zip(b.ells, b.perms, b.levels):
            assert tuple(ell[p] for p in perm) == canon
            assert canonical_levels(ell)[0] == tuple(sorted(ell,
                                                            reverse=True))


def test_bucket_count_collapses_in_high_dim():
    """The reason bucketing matters: d=10 diagonals are almost entirely
    permutations of each other (55 grids on |ell|=12 -> 2 buckets)."""
    plan = build_plan(CombinationScheme(10, 3))
    # diagonals: |ell|=12 (C(11,9)=55 grids), |ell|=11 (10), |ell|=10 (1)
    assert plan.num_grids == math.comb(11, 9) + math.comb(10, 9) + 1
    # level multisets: (3,1^9), (2,2,1^8) | (2,1^9) | (1^10)
    assert len(plan.buckets) == 4


def test_index_plan_covers_grid_points_exactly():
    """Every non-pad position maps into the fine buffer exactly where
    embed_to_full writes; pads map to the dump slot."""
    scheme = CombinationScheme(2, 4)
    plan = build_plan(scheme)
    fine_size = plan.fine_size
    for b in plan.buckets:
        for g, ell in enumerate(b.ells):
            n_real = int(np.prod(grid_shape(ell)))
            idx = b.index[g]
            real = idx[idx < fine_size]
            assert len(real) == n_real
            assert len(set(real.tolist())) == n_real  # injective
            assert (idx[idx >= fine_size] == fine_size).all()


def test_ct_surrogate_serving():
    """serve.CTSurrogate answers point queries with the combined
    interpolant (== the direct weighted sum of multilinear interpolants)."""
    from repro.core.interpolation import sample_function
    from repro.launch.serve import CTSurrogate
    scheme = CombinationScheme(2, 5)
    u = lambda a, b: jnp.sin(2 * a) * (b - b * b)
    grids = {ell: sample_function(u, ell) for ell, _ in scheme.grids}
    srv = CTSurrogate(scheme, grids)
    pts = np.random.default_rng(8).random((64, 2))
    want = np.asarray(comb.combined_interpolant_points(
        grids, scheme, jnp.asarray(pts)))
    np.testing.assert_allclose(srv.query(pts), want, rtol=1e-9, atol=1e-10)
    # update with new state re-uses the jitted ingest (no retrace)
    grids2 = {ell: 2.0 * g for ell, g in grids.items()}
    srv.update(grids2)
    np.testing.assert_allclose(srv.query(pts), 2 * want,
                               rtol=1e-9, atol=1e-10)
    assert srv._ingest._cache_size() == 1


# ---------------------------------------------------------------------------
# batched kernels: pallas path == jnp path (incl. padded members)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,levels", [
    ((7, 15), ((3, 4), (3, 4))),
    ((15, 15), ((3, 4), (4, 4))),         # first member padded along axis 0
    ((7, 7, 7), ((3, 3, 3), (3, 2, 1))),  # mixed sub-target levels
])
def test_batched_pallas_matches_jnp(shape, levels):
    rng = np.random.default_rng(6)
    x = np.zeros((len(levels),) + shape)
    for g, lv in enumerate(levels):
        sl = tuple(slice(0, (1 << l) - 1) for l in lv)
        x[g][sl] = rng.standard_normal(tuple((1 << l) - 1 for l in lv))
    xj = jnp.asarray(x)
    a = np.asarray(hierarchize_batched(xj, levels, method="pallas"))
    b = np.asarray(hierarchize_batched_jnp(xj, levels))
    # same neighbours, masks, update and axis order: bitwise, both ways
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(
        np.asarray(hierarchize_batched(jnp.asarray(a), levels, inverse=True,
                                       method="pallas")),
        np.asarray(hierarchize_batched_jnp(jnp.asarray(a), levels,
                                           inverse=True)))
    for g, lv in enumerate(levels):
        sl = tuple(slice(0, (1 << l) - 1) for l in lv)
        want = np.asarray(hierarchize(xj[g][sl], "ref"))
        np.testing.assert_allclose(a[g][sl], want, rtol=1e-12, atol=1e-13)
