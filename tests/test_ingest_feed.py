"""How an ingest reaches the device: host grids packed into one buffer
(one host-to-device copy, unpacked inside the ingest executable) or,
where any grid already lives on the device or the dtypes differ, one
copy per grid part.

The packed feed must be invisible in the result: every case pins the
packed surplus BITWISE to the per-part one, forced by passing the same
grids as ``jax.Array``s.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType
from proptest import cases, integers, seeds

from repro.core import engine as E
from repro.core.engine import CTEngine, ExecSpec, clear_compile_cache
from repro.core.executor import MergeConfig, build_plan
from repro.core.levels import (CombinationScheme, GeneralScheme,
                               admissible_extensions, grid_shape)


@pytest.fixture(autouse=True)
def _fresh_caches():
    clear_compile_cache()
    yield


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


def _host_grids(scheme, seed, dtype=np.float64):
    rng = np.random.default_rng(seed)
    return {ell: rng.standard_normal(grid_shape(ell)).astype(dtype)
            for ell, _ in scheme.grids}


def _on_device(grids):
    return {ell: jnp.asarray(g) for ell, g in grids.items()}


def _feed(eng):
    return eng.stats()["ingest_feed"]


def _packed_and_per_part(scheme, grids, spec=None):
    """Register ``grids`` once from the host and once from the device;
    return both surpluses and the engine's feed counts."""
    eng = CTEngine(spec, ingest_workers=0)
    eng.register("host", scheme, grids)
    eng.register("device", scheme, _on_device(grids))
    got = np.asarray(eng.surplus("host")), np.asarray(eng.surplus("device"))
    feed = _feed(eng)
    eng.close()
    return got, feed


@pytest.mark.parametrize("dim,steps,merged,seed", cases(
    lambda r: (integers(r, 2, 3), integers(r, 1, 8), integers(r, 0, 1),
               seeds(r)), n=6))
@pytest.mark.parametrize("x64", [False, True])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_packed_feed_is_bitwise_the_per_part_feed(dim, steps, merged, seed,
                                                  x64, dtype):
    """Seeded random downward-closed schemes, merged and unmerged plans,
    f32 and f64 host grids, x64 off (f64 grids arrive as f32 either way)
    and on."""
    scheme = _random_general_scheme(seed, dim, steps)
    spec = ExecSpec(merge=MergeConfig() if merged else None)
    grids = _host_grids(scheme, seed, dtype)
    with jax.enable_x64(x64):
        (packed, per_part), feed = _packed_and_per_part(scheme, grids, spec)
    assert feed == {"packed": 1, "per_part": 1}
    assert packed.dtype == per_part.dtype
    np.testing.assert_array_equal(packed, per_part)


def test_merged_plans_pack_members_of_unlike_shapes():
    """The merged case above is not vacuous: a merged bucket holds
    members of different shapes, which the packed buffer lays out in
    bucket order."""
    scheme = CombinationScheme(3, 4)
    merged = build_plan(scheme, merge=MergeConfig())
    assert len(merged.buckets) < len(build_plan(scheme).buckets)
    assert any(len({grid_shape(ell) for ell in b.ells}) > 1
               for b in merged.buckets)
    with jax.enable_x64(False):
        (packed, per_part), _ = _packed_and_per_part(
            scheme, _host_grids(scheme, 5),
            ExecSpec(merge=MergeConfig()))
    np.testing.assert_array_equal(packed, per_part)


def test_one_device_grid_among_host_grids_goes_per_part():
    scheme = CombinationScheme(2, 5)
    grids = _host_grids(scheme, 1)
    mixed = dict(grids)
    first = next(iter(mixed))
    mixed[first] = jnp.asarray(mixed[first])
    eng = CTEngine(ingest_workers=0)
    eng.register("host", scheme, grids)
    eng.register("mixed", scheme, mixed)
    assert _feed(eng) == {"packed": 1, "per_part": 1}
    np.testing.assert_array_equal(np.asarray(eng.surplus("mixed")),
                                  np.asarray(eng.surplus("host")))


def test_mixed_dtypes_go_per_part():
    """f32 and f64 host grids under x64 have different canonical dtypes:
    the per-part path promotes them as it always has."""
    scheme = CombinationScheme(2, 4)
    grids = _host_grids(scheme, 2)
    first = next(iter(grids))
    grids[first] = grids[first].astype(np.float32)
    eng = CTEngine(ingest_workers=0)
    eng.register("t", scheme, grids)
    assert _feed(eng) == {"packed": 0, "per_part": 1}
    assert eng.surplus("t").dtype == jnp.float64


def test_a_grid_of_the_wrong_shape_still_raises():
    scheme = CombinationScheme(2, 4)
    grids = _host_grids(scheme, 3)
    first = next(iter(grids))
    grids[first] = np.zeros(grids[first].size + 1)
    eng = CTEngine(ingest_workers=0)
    with pytest.raises(ValueError):
        eng.register("t", scheme, grids)
    assert _feed(eng) == {"packed": 0, "per_part": 1}
    assert "t" not in eng


def test_feed_counts_every_ingest_path():
    """register, update and refit all dispatch through the one feed;
    each ingest counts once, on the path it took."""
    scheme = CombinationScheme(2, 4)
    grids = _host_grids(scheme, 4)
    eng = CTEngine(ingest_workers=0)
    eng.register("t", scheme, grids)
    eng.update("t", grids)
    eng.update("t", _on_device(grids))
    assert _feed(eng) == {"packed": 2, "per_part": 1}
    general = scheme.as_general()
    bigger = general.with_levels(
        [admissible_extensions(general.index_set)[0]])
    eng.refit("t", bigger, _host_grids(bigger, 5))
    assert _feed(eng) == {"packed": 3, "per_part": 1}
    assert eng.stats()["ingests"] == 4


def test_donating_packed_feed_leaves_the_callers_grids_alone():
    """``donate=True`` with host grids donates only the engine's own
    packed buffer: the caller's dict is untouched and can be ingested
    again, with the same surplus as a non-donating engine."""
    scheme = CombinationScheme(2, 4)
    grids = _host_grids(scheme, 6)
    kept = {ell: g.copy() for ell, g in grids.items()}
    plain = CTEngine(ingest_workers=0)
    plain.register("t", scheme, grids)
    want = np.asarray(plain.surplus("t"))

    eng = CTEngine(ExecSpec(donate=True), ingest_workers=0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")      # CPU cannot use a donation
        eng.register("t", scheme, grids)
        first = np.asarray(eng.surplus("t"))
        again = np.asarray(eng.update("t", grids))
    assert _feed(eng) == {"packed": 2, "per_part": 0}
    for ell, g in grids.items():
        np.testing.assert_array_equal(g, kept[ell])
    np.testing.assert_array_equal(first, want)
    np.testing.assert_array_equal(again, want)


def test_jit_entries_count_each_feed_once():
    scheme = CombinationScheme(2, 4)
    grids = _host_grids(scheme, 7)
    eng = CTEngine(ingest_workers=0)
    eng.register("t", scheme, grids)
    eng.update("t", grids)
    assert eng.stats()["ingest_cache"]["jit_entries"] == 1
    eng.update("t", _on_device(grids))
    eng.update("t", _on_device(grids))
    assert eng.stats()["ingest_cache"]["jit_entries"] == 2
    exe = eng._tenant("t").executable
    assert exe.packed._cache_size() == 1 and exe.per_part._cache_size() == 1


def test_packed_executable_keeps_the_ingest_program_name():
    """Device-trace readers find the ingest by the ``jit_ingest`` prefix
    of its module name."""
    scheme = CombinationScheme(2, 4)
    spec = ExecSpec()
    plan = build_plan(scheme, spec=spec)
    exe = E._IngestExecutable(plan, spec)
    idxs, coeffs = E._tenant_arrays(plan)
    flat = jnp.zeros(exe.packed_size)
    text = exe.packed.lower(flat, idxs, coeffs).as_text()
    assert text.startswith("module @jit_ingest")


# ---------------------------------------------------------------------------
# meshed specs: the unpack runs before the unchanged meshed bodies
# ---------------------------------------------------------------------------

def _mesh(shape, names):
    n = int(np.prod(shape))
    return jax.make_mesh(shape, names, devices=np.array(jax.devices()[:n]),
                         axis_types=(AxisType.Auto,) * len(shape))


@pytest.mark.multidevice
@pytest.mark.parametrize("layout", ["slab2", "member2xslab2"])
@pytest.mark.parametrize("dim", [2, 3])
def test_meshed_packed_feed_is_bitwise_the_per_part_feed(layout, dim):
    if layout == "slab2":
        spec = ExecSpec(mesh=_mesh((2,), ("slab",)), axis_name="slab")
    else:
        spec = ExecSpec(mesh=_mesh((2, 2), ("member", "slab")),
                        axis_name="slab", member_axis="member")
    scheme = CombinationScheme(dim, 4)
    (packed, per_part), feed = _packed_and_per_part(
        scheme, _host_grids(scheme, 10 + dim), spec)
    assert feed == {"packed": 1, "per_part": 1}
    np.testing.assert_array_equal(packed, per_part)
