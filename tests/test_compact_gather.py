"""The one-device compact gather: every bucket accumulates into a vector
over the sparse grid's distinct fine points (``ExecutorPlan.compact``),
which is then written into the fine grid once.

Pinned here: the engine's surplus (both feeds) and
``ct_transform_with_plan`` against the hierarchize-per-grid +
``combine_full`` oracle, and BITWISE against the slab-sharded gather,
which still scatters into the fine grid per slab; the compact length N
against the sparse grid's point count, from the plan's maps alone; the
maps a tenant holds after ``extend`` and ``drop_grid``; and which
maps each kind of tenant binds.
"""

import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core import combination as comb
from repro.core.engine import CTEngine, ExecSpec, clear_compile_cache
from repro.core.executor import (MergeConfig, build_plan,
                                 ct_transform_with_plan, shard_plan)
from repro.core.hierarchize import hierarchize
from repro.core.levels import (CombinationScheme, GeneralScheme,
                               admissible_extensions, grid_shape)
from repro.kernels.hierarchize import batched_method


@pytest.fixture(autouse=True)
def _fresh_caches():
    clear_compile_cache()
    yield


def _adaptive_scheme(seed=5, dim=3, steps=7, max_level=4):
    """Seeded downward-closed index set grown by admissible steps."""
    rng = np.random.default_rng(seed)
    gs = GeneralScheme.regular(dim, 1)
    for _ in range(steps):
        cands = [c for c in admissible_extensions(gs.index_set)
                 if max(c) <= max_level]
        gs = gs.with_levels([cands[int(rng.integers(len(cands)))]])
    return gs


#: name -> (scheme, merge): the regular 2-D and 3-D schemes, an adaptive
#: index set, and a near-square scheme merged into padded buckets that
#: take the Pallas path (interpret mode off the chip)
CASES = {
    "regular2d": (CombinationScheme(2, 4), None),
    "regular3d": (CombinationScheme(3, 3), None),
    "adaptive": (_adaptive_scheme(), None),
    "padded_pallas": (GeneralScheme.from_levels([(6, 5), (5, 6)], close=True),
                      MergeConfig(launch_cost_bytes=1 << 30)),
}
FEEDS = ["packed", "per_part"]


def _grids(scheme, seed, feed):
    """Host grids take the packed feed, device arrays the per-part one."""
    rng = np.random.default_rng(seed)
    grids = {ell: rng.standard_normal(grid_shape(ell))
             for ell, _ in scheme.grids}
    if feed == "per_part":
        grids = {ell: jnp.asarray(g) for ell, g in grids.items()}
    return grids


def _oracle(grids, scheme):
    hier = {ell: hierarchize(jnp.asarray(u)) for ell, u in grids.items()}
    full, _ = comb.combine_full(hier, scheme)
    return np.asarray(full)


def _engine_surplus(scheme, merge, grids, feed):
    eng = CTEngine(ingest_workers=0, spec=ExecSpec(merge=merge))
    eng.register("t", scheme, grids)
    assert eng.stats()["ingest_feed"][feed] == 1
    return np.asarray(eng.surplus("t"))


def _sparse_grid_points(scheme):
    """Distinct nodes of the union of the scheme's grids, counted per
    hierarchical subspace: subspace ``l`` (every ``l <= ell`` of some
    grid) holds ``prod 2**(l_k - 1)`` nodes."""
    ells = [ell for ell, _ in scheme.grids]
    subspaces = {l for ell in ells
                 for l in itertools.product(*(range(1, e + 1) for e in ell))}
    return sum(math.prod(1 << (k - 1) for k in l) for l in subspaces)


def _mesh(n):
    return jax.make_mesh((n,), ("slab",), devices=np.array(jax.devices()[:n]),
                         axis_types=(AxisType.Auto,))


def test_the_padded_case_has_pad_slots_on_the_pallas_path():
    scheme, merge = CASES["padded_pallas"]
    plan = build_plan(scheme, merge=merge)
    n = plan.compact.size
    assert any((m == n).any() for m in plan.compact.buckets)
    assert any(batched_method(b.shape) == "pallas" for b in plan.buckets)


@pytest.mark.parametrize("feed", FEEDS)
@pytest.mark.parametrize("case", list(CASES))
def test_compact_gather_matches_the_oracle(case, feed):
    scheme, merge = CASES[case]
    grids = _grids(scheme, 11, feed)
    want = _oracle(grids, scheme)
    got = _engine_surplus(scheme, merge, grids, feed)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)
    plan = build_plan(scheme, merge=merge)
    np.testing.assert_array_equal(
        np.asarray(ct_transform_with_plan(grids, plan)), got)


@pytest.mark.multidevice
@pytest.mark.parametrize("feed", FEEDS)
@pytest.mark.parametrize("case", list(CASES))
def test_compact_gather_is_bitwise_the_slab_sharded_gather(case, feed):
    scheme, merge = CASES[case]
    grids = _grids(scheme, 12, feed)
    got = _engine_surplus(scheme, merge, grids, feed)
    splan = shard_plan(build_plan(scheme, merge=merge), 4)
    sharded = ct_transform_with_plan(grids, splan,
                                     spec=ExecSpec(mesh=_mesh(4)))
    np.testing.assert_array_equal(got, np.asarray(sharded))


@pytest.mark.parametrize("scheme,points", [
    (CombinationScheme(2, 11), 20_481),
    (CombinationScheme(3, 9), 18_943),
    (CASES["regular2d"][0], None),
    (CASES["adaptive"][0], None),
    (CASES["padded_pallas"][0], None),
], ids=["fig6_2d", "prod_3d", "regular2d", "adaptive", "padded"])
def test_compact_length_is_the_sparse_grids_point_count(scheme, points):
    """N and both maps, from the plan alone: the fine map is sorted and
    unique, and reading it through a bucket's compact map gives back the
    bucket's fine index, with every pad on the dump slot N."""
    plan = build_plan(scheme)
    compact = plan.compact
    n = compact.size
    assert n == _sparse_grid_points(scheme)
    if points is not None:
        assert n == points
    fine = compact.fine
    assert fine.dtype == np.int32 and np.all(np.diff(fine) > 0)
    assert 0 <= fine[0] and fine[-1] < plan.fine_size
    for b, m in zip(plan.buckets, compact.buckets):
        assert m.dtype == np.int32 and m.shape == b.index.shape
        real = b.index < plan.fine_size
        np.testing.assert_array_equal(fine[m[real]], b.index[real])
        assert np.all(m[~real] == n)


def _tenant_maps(eng, name):
    maps, fine = eng._tenant(name).idxs
    return [np.asarray(m) for m in maps], np.asarray(fine)


def _assert_tenant_holds_its_plans_maps(eng, name):
    compact = eng.plan(name).compact
    maps, fine = _tenant_maps(eng, name)
    np.testing.assert_array_equal(fine, compact.fine)
    assert len(maps) == len(compact.buckets)
    for got, want in zip(maps, compact.buckets):
        np.testing.assert_array_equal(got, want)


def test_extend_rebinds_the_compact_maps():
    scheme = GeneralScheme.regular(2, 3)
    eng = CTEngine(ingest_workers=0)
    eng.register("t", scheme, _grids(scheme, 1, "packed"))
    n0 = eng.plan("t").compact.size
    _assert_tenant_holds_its_plans_maps(eng, "t")

    grown = scheme.with_levels([(4, 1)])
    grids = _grids(grown, 2, "packed")
    eng.extend("t", [(4, 1)], grids)
    n1 = eng.plan("t").compact.size
    assert n1 == _sparse_grid_points(grown) > n0
    _assert_tenant_holds_its_plans_maps(eng, "t")
    np.testing.assert_allclose(np.asarray(eng.surplus("t")),
                               _oracle(grids, grown), rtol=0, atol=1e-12)


def test_drop_grid_rebinds_the_compact_maps():
    """The coefficient-only recombination keeps every member, so N
    stays; the tenant holds the new plan's maps, and the dropped grid
    adds nothing."""
    scheme = GeneralScheme.from_levels([(4, 1), (3, 2), (2, 3), (1, 4)],
                                       close=True)
    grids = _grids(scheme, 3, "packed")
    eng = CTEngine(ingest_workers=0)
    eng.register("t", scheme, grids)
    plan0 = eng.plan("t")

    eng.drop_grid("t", [(4, 1)], grids)
    assert eng.plan("t") is not plan0
    assert eng.plan("t").compact.size == plan0.compact.size
    _assert_tenant_holds_its_plans_maps(eng, "t")
    reduced = eng.scheme("t")
    want = ct_transform_with_plan(
        {ell: grids[ell] for ell, _ in reduced.grids},
        build_plan(reduced, eng.plan("t").full_levels))
    np.testing.assert_array_equal(np.asarray(eng.surplus("t")),
                                  np.asarray(want))


def test_same_signature_tenants_bind_their_own_compact_maps():
    """Two one-device tenants of one plan signature share one executable;
    each binds its own upload of its plan's compact maps as arguments."""
    scheme = CombinationScheme(2, 3)
    eng = CTEngine(ingest_workers=0)
    eng.register("a", scheme, _grids(scheme, 4, "packed"))
    eng.register("b", scheme, _grids(scheme, 5, "per_part"))
    assert eng._tenant("a").executable is eng._tenant("b").executable
    assert eng.stats()["ingest_cache"]["misses"] == 1
    for name in ("a", "b"):
        _assert_tenant_holds_its_plans_maps(eng, name)
    (maps_a, fine_a), (maps_b, fine_b) = (eng._tenant(n).idxs
                                          for n in ("a", "b"))
    assert fine_a is not fine_b
    assert all(ma is not mb for ma, mb in zip(maps_a, maps_b))


@pytest.mark.multidevice
def test_slab_sharded_tenants_keep_the_fine_grid_maps():
    """The slab-sharded gather is left alone: its tenant binds each slab
    bucket's index into the fine grid, not the compact maps."""
    scheme = CombinationScheme(2, 4)
    eng = CTEngine(ingest_workers=0, spec=ExecSpec(mesh=_mesh(2)))
    eng.register("t", scheme, _grids(scheme, 7, "packed"))
    slabs = eng.plan("t").slab_buckets
    idxs = eng._tenant("t").idxs
    assert len(idxs) == len(slabs)
    for got, sb in zip(idxs, slabs):
        np.testing.assert_array_equal(np.asarray(got), sb.index)
