"""CTEngine / ExecSpec front door: compile-cache sharing across tenants,
continuous-batching query coalescing, multi-tenant bit-identity against
the per-scheme executor + dict oracle, lifecycle routing through the
incremental plan paths, and the one ``spec=`` spelling of the
execution policy at every entry point.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proptest import cases, integers, seeds

from repro.core import combination as comb
from repro.core import engine as E
from repro.core.engine import CTEngine, ExecSpec, clear_compile_cache
from repro.core.executor import MergeConfig, build_plan, ct_transform
from repro.core.levels import (CombinationScheme, GeneralScheme,
                               admissible_extensions, grid_shape)


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


@pytest.fixture(autouse=True)
def _fresh_caches():
    """Deterministic compile-cache counters per test."""
    clear_compile_cache()
    yield


# ---------------------------------------------------------------------------
# ExecSpec semantics
# ---------------------------------------------------------------------------

def test_execspec_defaults_and_resolution():
    spec = ExecSpec()
    assert spec.slabs == 1 and spec.merge is None
    assert spec.resolve_interpret() == (jax.default_backend() != "tpu")
    assert ExecSpec(dtype=jnp.float32).dtype == "float32"
    assert ExecSpec(n_slabs=4).slabs == 4
    assert ExecSpec().result_dtype(jnp.float32, jnp.float64) == jnp.float64
    assert ExecSpec(dtype="float32").result_dtype(jnp.float64) == jnp.float32
    with pytest.raises(ValueError, match="n_slabs"):
        ExecSpec(n_slabs=0)


def test_execspec_is_hashable_and_plan_constructor():
    s1, s2 = ExecSpec(merge=MergeConfig()), ExecSpec(merge=MergeConfig())
    assert s1 == s2 and hash(s1) == hash(s2)
    scheme = CombinationScheme(2, 3)
    assert s1.plan(scheme) is build_plan(scheme, merge=MergeConfig())


# ---------------------------------------------------------------------------
# Compile-cache sharing (the tentpole's dedup claim)
# ---------------------------------------------------------------------------

def test_same_signature_tenants_compile_once():
    """Two schemes with identical bucket signatures — the classical scheme
    and its GeneralScheme spelling — share ONE jitted ingest executable;
    results stay bit-identical to the per-scheme constants-baked
    ``ct_transform``."""
    s_classic = CombinationScheme(2, 4)
    s_general = GeneralScheme.regular(2, 4)      # same grids, other object
    rng = np.random.default_rng(1)
    ga, gb = _random_grids(s_classic, rng), _random_grids(s_general, rng)

    eng = CTEngine()
    eng.register("a", s_classic, ga)
    eng.register("b", s_general, gb)
    st = eng.stats()["ingest_cache"]
    assert st["misses"] == 1 and st["hits"] == 1 and st["jit_entries"] == 1

    np.testing.assert_array_equal(np.asarray(eng.surplus("a")),
                                  np.asarray(ct_transform(ga, s_classic)))
    np.testing.assert_array_equal(np.asarray(eng.surplus("b")),
                                  np.asarray(ct_transform(gb, s_general)))


def test_distinct_signature_tenants_compile_separately():
    eng = CTEngine()
    rng = np.random.default_rng(2)
    for i, scheme in enumerate([CombinationScheme(2, 3),
                                CombinationScheme(2, 4),
                                CombinationScheme(3, 3)]):
        eng.register(f"t{i}", scheme, _random_grids(scheme, rng))
    st = eng.stats()["ingest_cache"]
    assert st["misses"] == 3 and st["hits"] == 0


def test_coefficient_only_fault_reuses_executable():
    """``drop_grid`` on the coefficient-only path keeps the member list
    (dropped members get coefficient 0), so the plan SIGNATURE — and with
    it the compiled executable — is reused: zero new cache misses."""
    gs = GeneralScheme.from_levels([(4, 1), (3, 2), (2, 3), (1, 4)],
                                   close=True)
    rng = np.random.default_rng(3)
    grids = _random_grids(gs, rng)
    eng = CTEngine()
    eng.register("t", gs, grids)
    misses_before = eng.stats()["ingest_cache"]["misses"]

    dropped = (4, 1)
    after = dict(grids)
    after[dropped] = jnp.zeros_like(grids[dropped])
    eng.drop_grid("t", [dropped], after)
    st = eng.stats()["ingest_cache"]
    assert st["misses"] == misses_before          # no recompile
    assert eng.scheme("t") == gs.without_levels([dropped])

    # the coefficient-only path keeps the ORIGINAL fine grid (that is the
    # point: nothing rebuilt), so compare on the plan's full_levels
    reduced = eng.scheme("t")
    want = ct_transform({ell: after[ell] for ell, _ in reduced.grids},
                        reduced, full_levels=eng.plan("t").full_levels)
    np.testing.assert_array_equal(np.asarray(eng.surplus("t")),
                                  np.asarray(want))


def test_merge_spec_is_part_of_the_signature():
    """Merged and unmerged plans of one scheme are different executables
    (different bucket partition), and both serve bit-identical results."""
    scheme = CombinationScheme(4, 3)
    rng = np.random.default_rng(4)
    grids = _random_grids(scheme, rng)
    eng = CTEngine()
    eng.register("plain", scheme, grids)
    eng.register("merged", scheme, grids, spec=ExecSpec(merge=MergeConfig()))
    assert eng.stats()["ingest_cache"]["misses"] == 2
    np.testing.assert_array_equal(np.asarray(eng.surplus("plain")),
                                  np.asarray(eng.surplus("merged")))


# ---------------------------------------------------------------------------
# Continuous batching: coalescing + split correctness
# ---------------------------------------------------------------------------

def test_same_signature_queries_coalesce_into_one_dispatch():
    scheme = CombinationScheme(2, 4)
    rng = np.random.default_rng(5)
    eng = CTEngine()
    eng.register("a", scheme, _random_grids(scheme, rng))
    eng.register("b", scheme, _random_grids(scheme, rng))
    pts_a = np.random.default_rng(50).random((20, 2))
    pts_b = np.random.default_rng(51).random((29, 2))     # same qpad=32
    fa, fb = eng.submit_query("a", pts_a), eng.submit_query("b", pts_b)
    assert not fa.done() and not fb.done()
    eng.flush()
    ev = eng.stats()["eval"]
    assert ev["batches"] == 1 and ev["queries"] == 2
    assert ev["coalesced_queries"] == 1
    # bit-identical to the per-tenant dispatch
    np.testing.assert_array_equal(fa.result(), eng.query("a", pts_a))
    np.testing.assert_array_equal(fb.result(), eng.query("b", pts_b))


def test_mixed_signature_query_batch_splits_correctly():
    """Queries against tenants with DIFFERENT surplus signatures split
    into one batched dispatch per signature and every request gets its
    own tenant's result, bit-identical to per-tenant dispatch."""
    s_small, s_big, s_3d = (CombinationScheme(2, 3), CombinationScheme(2, 5),
                            CombinationScheme(3, 3))
    rng = np.random.default_rng(6)
    eng = CTEngine()
    tenants = {"small": s_small, "big": s_big, "deep": s_3d,
               "small2": s_small}
    grids = {}
    for name, scheme in tenants.items():
        grids[name] = _random_grids(scheme, rng)
        eng.register(name, scheme, grids[name])
    pts2 = np.random.default_rng(60).random((17, 2))
    pts3 = np.random.default_rng(61).random((17, 3))
    futs = {name: eng.submit_query(name, pts3 if scheme.dim == 3 else pts2)
            for name, scheme in tenants.items()}
    eng.flush()
    ev = eng.stats()["eval"]
    assert ev["batches"] == 3          # small+small2 | big | deep
    assert ev["queries"] == 4 and ev["coalesced_queries"] == 1
    for name, scheme in tenants.items():
        pts = pts3 if scheme.dim == 3 else pts2
        want = eng.query(name, pts)                       # per-tenant
        np.testing.assert_array_equal(futs[name].result(), want)
        oracle = np.asarray(comb.combined_interpolant_points(
            grids[name], scheme, jnp.asarray(pts)))
        np.testing.assert_allclose(futs[name].result(), oracle,
                                   rtol=1e-9, atol=1e-10)


def test_ingest_overlaps_query_in_one_flush():
    """An ingest and a query submitted before one flush: the ingest is
    dispatched first (asynchronously) and the query evaluates against the
    NEW surplus."""
    scheme = CombinationScheme(2, 4)
    rng = np.random.default_rng(7)
    grids = _random_grids(scheme, rng)
    eng = CTEngine()
    eng.register("t", scheme, grids)
    grids2 = {ell: 2.0 * g for ell, g in grids.items()}
    pts = np.random.default_rng(70).random((16, 2))
    before = eng.query("t", pts)
    fi = eng.submit_ingest("t", grids2)
    fq = eng.submit_query("t", pts)
    eng.flush()
    np.testing.assert_array_equal(fq.result(), 2.0 * before)
    np.testing.assert_array_equal(np.asarray(fi.result()),
                                  np.asarray(eng.surplus("t")))


def test_failing_request_resolves_only_its_own_future():
    """One bad request in a flush fails ITS future (the exception
    re-raises from result()); the other queued requests still complete."""
    scheme = CombinationScheme(2, 3)
    rng = np.random.default_rng(77)
    grids = _random_grids(scheme, rng)
    eng = CTEngine()
    eng.register("a", scheme, grids)
    eng.register("b", scheme, _random_grids(scheme, rng))
    bad = dict(grids)
    del bad[next(iter(bad))]                    # ingest will fail
    before = np.asarray(eng.surplus("a"))
    f_bad = eng.submit_ingest("a", bad)
    pts = np.random.default_rng(770).random((8, 2))
    f_ok = eng.submit_query("b", pts)
    eng.flush()
    with pytest.raises(ValueError, match="missing"):
        f_bad.result()
    np.testing.assert_array_equal(np.asarray(eng.surplus("a")), before)
    np.testing.assert_array_equal(f_ok.result(), eng.query("b", pts))
    # a query against a never-ingested tenant fails its own future too
    eng.register("empty", scheme, None)
    f_q = eng.submit_query("empty", pts)
    f_ok2 = eng.submit_query("b", pts)
    eng.flush()
    with pytest.raises(RuntimeError, match="no ingested state"):
        f_q.result()
    np.testing.assert_array_equal(f_ok2.result(), eng.query("b", pts))


def test_queued_requests_resolve_tenant_by_name_at_flush():
    """Work queued before a refit applies to the tenant the engine serves
    AT FLUSH TIME (the post-refit record), and queued work for an
    unregistered name fails its own future instead of running on an
    orphaned tenant object."""
    gs = GeneralScheme.regular(2, 2)
    rng = np.random.default_rng(82)
    grids = _random_grids(gs, rng)
    eng = CTEngine()
    eng.register("t", gs, grids)

    grown = gs.with_levels([(3, 1)])
    grids2 = {ell: jnp.asarray(rng.standard_normal(grid_shape(ell)))
              for ell, _ in grown.grids}
    fut = eng.submit_ingest("t", grids2)        # queued pre-refit
    eng.refit("t", grown, grids2)
    eng.flush()
    # the queued ingest ran against the POST-refit plan and its result is
    # the tenant's served state (not dropped on an orphan)
    np.testing.assert_array_equal(np.asarray(fut.result()),
                                  np.asarray(eng.surplus("t")))
    np.testing.assert_array_equal(np.asarray(eng.surplus("t")),
                                  np.asarray(ct_transform(grids2, grown)))

    f_i = eng.submit_ingest("t", grids2)
    f_q = eng.submit_query("t", np.random.default_rng(820).random((4, 2)))
    eng.unregister("t")
    eng.flush()
    for f in (f_i, f_q):
        with pytest.raises(KeyError, match="unregistered"):
            f.result()


def test_extend_plan_spec_slab_conflict_raises():
    from repro.core.executor import extend_plan, shard_plan
    gs = GeneralScheme.regular(2, 3)
    splan = shard_plan(build_plan(gs), 4)
    with pytest.raises(ValueError, match="sharded for 4"):
        extend_plan(splan, gs.with_levels([(4, 1)]),
                    spec=ExecSpec(n_slabs=8))
    # a spec that does not request sharding extends a sharded plan as-is
    out = extend_plan(splan, gs.with_levels([(4, 1)]), spec=ExecSpec())
    assert out.n_slabs == 4


def test_positional_non_spec_raises_named_type_error():
    """Pre-redesign positional callers (third arg used to be interpret)
    get a named TypeError, not an opaque attribute error."""
    scheme = CombinationScheme(2, 3)
    grids = _random_grids(scheme, np.random.default_rng(78))
    from repro.launch.serve import CTSurrogate
    with pytest.raises(TypeError, match=r"spec=ExecSpec\(interpret"):
        CTSurrogate(scheme, grids, True)
    with pytest.raises(TypeError, match="ExecSpec"):
        ct_transform(grids, scheme, spec=True)
    with pytest.raises(TypeError, match="ExecSpec"):
        build_plan(scheme, spec="merge-me")
    with pytest.raises(TypeError, match="ExecSpec"):
        CTEngine(spec=object())


def test_meshed_spec_on_unsharded_plan_raises():
    """A meshed spec never silently degrades to the single-device path."""
    from repro.core.executor import ct_transform_with_plan

    class FakeMesh:                     # shape-duck-typed; no devices needed
        shape = {"slab": 4}

    spec = ExecSpec(mesh=FakeMesh())
    scheme = CombinationScheme(2, 3)
    grids = _random_grids(scheme, np.random.default_rng(79))
    with pytest.raises(ValueError, match="not slab-sharded"):
        ct_transform_with_plan(grids, build_plan(scheme), spec=spec)


def test_execspec_mesh_nslabs_conflict_raises():
    class FakeMesh:
        shape = {"slab": 8}

    with pytest.raises(ValueError, match="conflicts with mesh axis"):
        ExecSpec(mesh=FakeMesh(), n_slabs=4)
    assert ExecSpec(mesh=FakeMesh(), n_slabs=8).slabs == 8   # consistent OK


def test_ingest_executable_cache_is_lru_bounded():
    import repro.core.engine as engine_mod
    old_max = engine_mod._INGEST_CACHE_MAX
    engine_mod._INGEST_CACHE_MAX = 2
    try:
        eng = CTEngine()
        rng = np.random.default_rng(81)
        for i, scheme in enumerate([CombinationScheme(2, 2),
                                    CombinationScheme(2, 3),
                                    CombinationScheme(3, 2)]):
            eng.register(f"t{i}", scheme, _random_grids(scheme, rng))
        assert len(engine_mod._INGEST_EXECUTABLES) == 2    # oldest evicted
        # the evicted signature's tenant keeps serving (executable still
        # referenced by the tenant); a NEW same-signature tenant recompiles
        pts = np.random.default_rng(810).random((8, 2))
        assert eng.query("t0", pts).shape == (8,)
    finally:
        engine_mod._INGEST_CACHE_MAX = old_max


def test_adaptive_driver_spec_config_conflict_raises():
    from repro.core.adaptive import AdaptiveConfig, AdaptiveDriver
    solver = lambda ell: np.zeros(grid_shape(ell))
    with pytest.raises(ValueError, match="CTEngine instead"):
        AdaptiveDriver(solver, dim=2, spec=ExecSpec(n_slabs=4))
    # a single-device spec is applied; the budget config is separate
    drv = AdaptiveDriver(solver, dim=2, config=AdaptiveConfig(),
                         spec=ExecSpec(merge=MergeConfig()))
    assert drv.spec.merge == MergeConfig()
    assert drv.plan.merge == MergeConfig()


def test_future_result_autoflushes():
    scheme = CombinationScheme(2, 3)
    eng = CTEngine()
    eng.register("t", scheme, _random_grids(scheme, np.random.default_rng(8)))
    pts = np.random.default_rng(80).random((8, 2))
    fut = eng.submit_query("t", pts)
    got = fut.result()                 # no explicit flush
    np.testing.assert_array_equal(got, eng.query("t", pts))


# ---------------------------------------------------------------------------
# Acceptance property test: multi-tenant == per-scheme executor + oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim,steps,seed", cases(
    lambda r: (integers(r, 2, 3), integers(r, 2, 6), seeds(r)), n=6))
def test_multi_tenant_bit_identical_to_per_scheme_transform(dim, steps, seed):
    """Seeded property test (the PR's acceptance gate): a multi-tenant
    engine serving random downward-closed schemes produces surpluses
    BIT-identical to the per-scheme jitted ``ct_transform`` and query
    values matching the dict-oracle interpolant."""
    from repro.launch.steps import make_ct_step
    rng = np.random.default_rng(seed)
    eng = CTEngine()
    schemes, grids = {}, {}
    for i in range(3):
        gs = _random_general_scheme(seed + i, dim, steps)
        name = f"tenant{i}"
        schemes[name], grids[name] = gs, _random_grids(gs, rng)
        eng.register(name, gs, grids[name])
    pts = rng.random((23, dim))
    futs = {name: eng.submit_query(name, pts) for name in schemes}
    eng.flush()
    for name, gs in schemes.items():
        step = make_ct_step(gs)
        np.testing.assert_array_equal(np.asarray(eng.surplus(name)),
                                      np.asarray(step(grids[name])))
        oracle = np.asarray(comb.combined_interpolant_points(
            grids[name], gs, jnp.asarray(pts)))
        np.testing.assert_allclose(futs[name].result(), oracle,
                                   rtol=1e-9, atol=1e-10)


# ---------------------------------------------------------------------------
# Lifecycle: refit / extend / drop_grid / unregister
# ---------------------------------------------------------------------------

def test_engine_extend_routes_through_extend_plan():
    gs = GeneralScheme.regular(2, 2)
    rng = np.random.default_rng(9)
    grids = _random_grids(gs, rng)
    eng = CTEngine()
    eng.register("t", gs, grids)
    plan_before = eng.plan("t")

    grown = gs.with_levels([(3, 1)])
    grids2 = {ell: jnp.asarray(rng.standard_normal(grid_shape(ell)))
              for ell, _ in grown.grids}
    eng.extend("t", [(3, 1)], grids2)
    assert eng.scheme("t") == grown
    want = ct_transform(grids2, grown)
    np.testing.assert_array_equal(np.asarray(eng.surplus("t")),
                                  np.asarray(want))
    assert eng.plan("t") is not plan_before


def test_failed_lifecycle_leaves_tenant_unchanged():
    gs = GeneralScheme.regular(2, 3)
    rng = np.random.default_rng(10)
    grids = _random_grids(gs, rng)
    eng = CTEngine()
    eng.register("t", gs, grids)
    before = np.asarray(eng.surplus("t"))
    with pytest.raises(ValueError, match=r"\(1, 1\)"):
        eng.drop_grid("t", [(2, 2)], grids)    # (1, 1) data not supplied
    assert eng.scheme("t") == gs
    np.testing.assert_array_equal(np.asarray(eng.surplus("t")), before)


def test_register_twice_and_unknown_tenant_raise():
    scheme = CombinationScheme(2, 2)
    eng = CTEngine()
    eng.register("t", scheme,
                 _random_grids(scheme, np.random.default_rng(11)))
    with pytest.raises(ValueError, match="already registered"):
        eng.register("t", scheme, None)
    with pytest.raises(KeyError, match="nope"):
        eng.query("nope", np.zeros((4, 2)))
    eng.unregister("t")
    assert "t" not in eng


# ---------------------------------------------------------------------------
# Query validation (satellite: named errors instead of jit failures)
# ---------------------------------------------------------------------------

def test_query_point_validation_named_errors():
    from repro.launch.serve import CTSurrogate
    scheme = CombinationScheme(2, 3)
    srv = CTSurrogate(scheme,
                      _random_grids(scheme, np.random.default_rng(12)))
    with pytest.raises(ValueError, match=r"\(Q, 2\).*got \(4, 3\)"):
        srv.query(np.zeros((4, 3)))
    with pytest.raises(ValueError, match="2-dimensional"):
        srv.query(np.zeros((4, 3)))
    with pytest.raises(TypeError, match="floating"):
        srv.query(np.zeros((4, 2), np.int32))
    # a bare (d,) point is promoted to one row, not rejected
    assert srv.query(np.full(2, 0.5)).shape == (1,)


# ---------------------------------------------------------------------------
# One spelling: every front door takes its execution policy as spec= only
# ---------------------------------------------------------------------------

def _front_door(name):
    """Entry point ``name`` bound to a small valid input; the returned
    callable forwards its keywords to the call."""
    from jax.sharding import AxisType
    from repro.core.adaptive import AdaptiveConfig
    from repro.core.distributed import ct_transform_sharded
    from repro.core.executor import (ct_scatter, ct_transform_with_plan,
                                     shard_plan)
    from repro.launch.serve import CTSurrogate
    from repro.launch.steps import make_ct_eval_step, make_ct_step
    scheme = CombinationScheme(2, 3)
    grids = _random_grids(scheme, np.random.default_rng(15))
    mesh = jax.make_mesh((1,), ("slab",), devices=jax.devices()[:1],
                         axis_types=(AxisType.Auto,))
    return {
        "ct_transform": lambda **kw: ct_transform(grids, scheme, **kw),
        "ct_scatter": lambda **kw: ct_scatter(
            jnp.zeros(build_plan(scheme).fine_shape), scheme, **kw),
        "ct_transform_with_plan": lambda **kw: ct_transform_with_plan(
            grids, build_plan(scheme), **kw),
        "ct_transform_sharded": lambda **kw: ct_transform_sharded(
            grids, scheme, mesh, "slab", **kw),
        "make_ct_step": lambda **kw: make_ct_step(scheme, **kw),
        "make_ct_eval_step": lambda **kw: make_ct_eval_step(scheme, **kw),
        "CTSurrogate": lambda **kw: CTSurrogate(scheme, grids, **kw),
        "AdaptiveConfig": lambda **kw: AdaptiveConfig(**kw),
    }[name], mesh, shard_plan(build_plan(scheme), 1)


#: (entry point, keyword) pairs whose option lives only in ExecSpec
_SPEC_ONLY = [("ct_transform", "interpret"), ("ct_transform", "merge"),
              ("ct_scatter", "interpret"), ("ct_scatter", "merge"),
              ("ct_transform_with_plan", "interpret"),
              ("ct_transform_sharded", "interpret"),
              ("ct_transform_sharded", "sharded_plan"),
              ("make_ct_step", "interpret"), ("make_ct_step", "merge"),
              ("make_ct_eval_step", "interpret"),
              ("make_ct_eval_step", "merge"),
              ("CTSurrogate", "interpret"), ("CTSurrogate", "mesh"),
              ("CTSurrogate", "axis_name"), ("CTSurrogate", "merge"),
              ("AdaptiveConfig", "interpret"), ("AdaptiveConfig", "merge")]


@pytest.mark.parametrize("entry,kw", _SPEC_ONLY,
                         ids=[f"{e}-{k}" for e, k in _SPEC_ONLY])
def test_execution_keyword_outside_spec_raises(entry, kw):
    """An execution option passed beside ``spec=`` is refused at the call:
    the option exists only as an ``ExecSpec`` field."""
    call, mesh, splan = _front_door(entry)
    value = {"interpret": True, "merge": MergeConfig(), "mesh": mesh,
             "axis_name": "slab", "sharded_plan": splan}[kw]
    with pytest.raises(TypeError,
                       match=f"unexpected keyword argument '{kw}'"):
        call(**{kw: value})


@pytest.mark.multidevice
def test_make_ct_step_honors_meshed_spec():
    """``make_ct_step(spec=ExecSpec(mesh=...))`` binds the slab-sharded
    gather (precedence rule 4), bit-identical to the single-device step."""
    from jax.sharding import AxisType
    from repro.launch.steps import make_ct_step
    mesh = jax.make_mesh((8,), ("slab",), axis_types=(AxisType.Auto,))
    scheme = GeneralScheme.regular(2, 4)
    grids = _random_grids(scheme, np.random.default_rng(18))
    step = make_ct_step(scheme, spec=ExecSpec(mesh=mesh))
    np.testing.assert_array_equal(np.asarray(step(grids)),
                                  np.asarray(make_ct_step(scheme)(grids)))


@pytest.mark.multidevice
def test_meshed_spec_routes_ct_transform_and_engine_shares_executable():
    """``ct_transform(spec=ExecSpec(mesh=...))`` routes the slab-sharded
    gather; two meshed tenants with one signature share one executable
    and match the single-device result bit-for-bit."""
    from jax.sharding import AxisType
    mesh = jax.make_mesh((8,), ("slab",), axis_types=(AxisType.Auto,))
    scheme = GeneralScheme.regular(2, 4)
    rng = np.random.default_rng(16)
    ga, gb = _random_grids(scheme, rng), _random_grids(scheme, rng)
    spec = ExecSpec(mesh=mesh)

    got = ct_transform(ga, scheme, spec=spec)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ct_transform(ga, scheme)))

    eng = CTEngine(spec=spec)
    eng.register("a", scheme, ga)
    eng.register("b", scheme, gb)
    st = eng.stats()["ingest_cache"]
    assert st["misses"] == 1 and st["hits"] == 1
    np.testing.assert_array_equal(np.asarray(eng.surplus("a")),
                                  np.asarray(ct_transform(ga, scheme)))
    np.testing.assert_array_equal(np.asarray(eng.surplus("b")),
                                  np.asarray(ct_transform(gb, scheme)))


# ---------------------------------------------------------------------------
# Surrogates as thin views over a shared engine
# ---------------------------------------------------------------------------

def test_surrogates_share_engine_and_compile_cache():
    from repro.launch.serve import CTSurrogate
    scheme = CombinationScheme(2, 4)
    rng = np.random.default_rng(17)
    eng = CTEngine()
    a = CTSurrogate(scheme, _random_grids(scheme, rng),
                    engine=eng, name="a")
    b = CTSurrogate(scheme, _random_grids(scheme, rng),
                    engine=eng, name="b")
    assert a.engine is b.engine is eng
    st = eng.stats()
    assert st["tenants"] == 2
    assert st["ingest_cache"]["misses"] == 1
    assert st["ingest_cache"]["hits"] == 1


# ---------------------------------------------------------------------------
# Deadline/priority scheduler, backpressure, error routing (PR 6)
# ---------------------------------------------------------------------------

def test_pump_dispatches_on_deadline_or_batch_full():
    """``pump`` is flush-on-deadline-or-batch-full, NOT flush-everything:
    a query inside its latency budget stays queued, an expired one (or a
    full per-tenant batch) dispatches."""
    scheme = CombinationScheme(2, 3)
    eng = CTEngine(max_batch=4, deadline_ms=10_000.0)
    eng.register("t", scheme, _random_grids(scheme, np.random.default_rng(20)))
    pts = np.random.default_rng(200).random((4, 2))

    fut = eng.submit_query("t", pts)
    assert eng.pump() == 0 and not fut.done()      # budget not expired
    assert eng.pump(now=1e18) == 1 and fut.done()  # deadline passed -> due
    np.testing.assert_array_equal(fut.result(), eng.query("t", pts))

    futs = [eng.submit_query("t", pts) for _ in range(4)]
    assert eng.pump() == 4                          # batch-full -> due now
    assert all(f.done() for f in futs)
    sched = eng.stats()["scheduler"]
    assert sched["dispatch_batch_full"] >= 1
    assert sched["dispatch_deadline"] >= 1

    # ingests are ALWAYS due (the pool overlaps them with everything)
    f_i = eng.submit_ingest("t", _random_grids(scheme,
                                               np.random.default_rng(21)))
    assert eng.pump() >= 1
    f_i.result(timeout=30)
    assert f_i.done()


def test_scheduler_thread_serves_without_explicit_flush():
    """A ``start()``-ed engine resolves futures on its own; no caller
    ever invokes flush/result-autoflush (we wait on the raw event)."""
    scheme = CombinationScheme(2, 3)
    eng = CTEngine(deadline_ms=5.0)
    eng.register("t", scheme, _random_grids(scheme, np.random.default_rng(22)))
    pts = np.random.default_rng(220).random((8, 2))
    want = eng.query("t", pts)
    with eng:                                       # start()/close()
        fut = eng.submit_query("t", pts)
        assert fut._event.wait(timeout=30.0)        # scheduler resolved it
    np.testing.assert_array_equal(fut.result(), want)


def test_priority_orders_dispatch_within_a_pump():
    """Higher-priority signature groups dispatch first (observable via
    the futures' completion timestamps)."""
    s_small, s_big = CombinationScheme(2, 3), CombinationScheme(2, 4)
    eng = CTEngine()
    rng = np.random.default_rng(23)
    eng.register("low", s_small, _random_grids(s_small, rng))
    eng.register("high", s_big, _random_grids(s_big, rng))   # distinct group
    pts = np.random.default_rng(230).random((4, 2))
    f_low = eng.submit_query("low", pts, priority=0)
    f_high = eng.submit_query("high", pts, priority=5)
    assert eng.pump(now=1e18) == 2
    assert f_low.done() and f_high.done()
    assert f_high.done_at <= f_low.done_at


def test_backpressure_bounded_queue():
    """Admission control rejects with an ACTIONABLE message: the
    rejected tenant's name, the live queue depth and ``max_pending``
    (satellite: greppable in cluster logs)."""
    from repro.core.engine import EngineSaturated
    scheme = CombinationScheme(2, 3)
    eng = CTEngine(max_pending=2)
    eng.register("t", scheme, _random_grids(scheme, np.random.default_rng(24)))
    pts = np.random.default_rng(240).random((4, 2))
    eng.submit_query("t", pts)
    eng.submit_query("t", pts)
    with pytest.raises(EngineSaturated,
                       match=r"tenant 't'.*depth 2 >= max_pending=2"):
        eng.submit_query("t", pts, block=False)
    with pytest.raises(EngineSaturated,
                       match=r"tenant 't'.*max_pending=2"):
        eng.submit_query("t", pts, block=True, timeout=0.05)
    assert eng.stats()["scheduler"]["rejected"] == 2
    eng.flush()                                     # frees the queue
    f = eng.submit_query("t", pts, block=False)     # admitted again
    np.testing.assert_array_equal(f.result(), eng.query("t", pts))


def test_check_finite_ingest_fails_only_its_own_future():
    """Satellite: a device-side NaN surfacing at block_until_ready inside
    the ingest worker resolves the OWNING future with the error; sibling
    requests in the same flush complete untouched."""
    scheme = CombinationScheme(2, 3)
    rng = np.random.default_rng(25)
    grids = _random_grids(scheme, rng)
    eng = CTEngine(check_finite=True)
    eng.register("a", scheme, grids)
    eng.register("b", scheme, _random_grids(scheme, rng))
    before = np.asarray(eng.surplus("a"))

    bad = {ell: g for ell, g in grids.items()}
    first = next(iter(bad))
    bad[first] = jnp.asarray(np.full(np.shape(bad[first]), np.nan))
    f_bad = eng.submit_ingest("a", bad)
    pts = np.random.default_rng(250).random((8, 2))
    f_q = eng.submit_query("b", pts)
    eng.flush()                                     # must not raise
    with pytest.raises(FloatingPointError, match="non-finite"):
        f_bad.result()
    np.testing.assert_array_equal(np.asarray(eng.surplus("a")), before)
    np.testing.assert_array_equal(f_q.result(), eng.query("b", pts))

    # per-submit override beats the engine default
    f_ok = eng.submit_ingest("a", bad, check_finite=False)
    eng.flush()
    assert not np.all(np.isfinite(np.asarray(f_ok.result())))


def test_future_result_timeout():
    eng = CTEngine()
    fut = E.CTFuture(eng)                       # never resolved
    with pytest.raises(TimeoutError, match="pending"):
        fut.result(timeout=0.05)


def test_rebind_offmesh_reuses_executable_and_surplus():
    """``rebind`` off-mesh: the spec swap re-binds from the shared cache
    (same signature -> a HIT, no recompile) and the served surplus
    carries over without recomputation."""
    scheme = CombinationScheme(2, 4)
    eng = CTEngine()
    eng.register("t", scheme, _random_grids(scheme, np.random.default_rng(26)))
    surp_before = eng.surplus("t")
    misses = eng.stats()["ingest_cache"]["misses"]
    assert eng.rebind("t") == "kept"
    assert eng.rebind("t", axis_name="row") == "rebound"
    assert eng.stats()["ingest_cache"]["misses"] == misses  # hit, not miss
    assert eng.surplus("t") is surp_before
    pts = np.random.default_rng(260).random((8, 2))
    assert eng.query("t", pts).shape == (8,)


@pytest.mark.multidevice
def test_rebalance_engine_onto_and_off_a_mesh():
    """The elastic fast lane end to end: tenants move onto a slab mesh
    and back WITHOUT surplus recomputation, bit-identical serving."""
    from jax.sharding import AxisType
    from repro.runtime.elastic import rebalance_engine
    mesh = jax.make_mesh((8,), ("slab",), axis_types=(AxisType.Auto,))
    scheme = GeneralScheme.regular(2, 4)
    rng = np.random.default_rng(27)
    eng = CTEngine()
    eng.register("a", scheme, _random_grids(scheme, rng))
    eng.register("b", scheme, _random_grids(scheme, rng))
    pts = np.random.default_rng(270).random((16, 2))
    want_a, want_b = eng.query("a", pts), eng.query("b", pts)
    ingests = eng.stats()["ingests"]

    out = rebalance_engine(eng, mesh)
    assert out == {"a": "sharded", "b": "sharded"}
    assert eng.stats()["ingests"] == ingests        # no recompute
    np.testing.assert_array_equal(eng.query("a", pts), want_a)
    np.testing.assert_array_equal(eng.query("b", pts), want_b)
    # the NEXT ingest runs slab-sharded and still matches the oracle
    g2 = _random_grids(scheme, rng)
    eng.update("a", g2)
    np.testing.assert_array_equal(np.asarray(eng.surplus("a")),
                                  np.asarray(ct_transform(g2, scheme)))

    out = rebalance_engine(eng, None)
    assert out == {"a": "unsharded", "b": "unsharded"}
    np.testing.assert_array_equal(eng.query("b", pts), want_b)


def test_plan_cache_contract_and_explicit_clear():
    """Satellite: ``build_plan``'s cache keys/values are host-side only —
    no ExecSpec, no mesh, no ShardedPlan ever enters it — and
    ``clear_plan_cache()`` empties it."""
    from repro.core.executor import _PLAN_CACHE, clear_plan_cache
    clear_plan_cache()
    scheme = CombinationScheme(2, 4)
    p1 = build_plan(scheme)
    assert build_plan(scheme) is p1                 # identity-stable hit
    sp = build_plan(scheme, spec=ExecSpec(n_slabs=4))
    from repro.core.executor import ShardedPlan
    assert isinstance(sp, ShardedPlan)
    for key in _PLAN_CACHE.keys():
        for part in key:
            assert not isinstance(part, ExecSpec)
            assert not hasattr(part, "devices")     # no mesh objects
    assert len(_PLAN_CACHE) >= 1
    clear_plan_cache()
    assert len(_PLAN_CACHE) == 0
    assert build_plan(scheme) is not p1             # genuinely rebuilt


# ---------------------------------------------------------------------------
# Host plumbing, HOL fairness, zero-copy ingest (PR 7)
# ---------------------------------------------------------------------------

def test_hol_oversized_low_priority_backlog_does_not_block_high():
    """Satellite regression: one oversized prio-0 backlog (12 queries,
    max_batch=4) plus one prio-10 query in the SAME pump — the
    high-priority query is promoted and dispatches FIRST, and the pump
    caps the low-priority group at max_batch instead of draining it."""
    scheme = CombinationScheme(2, 3)
    eng = CTEngine(max_batch=4, deadline_ms=10_000.0)
    eng.register("t", scheme, _random_grids(scheme, np.random.default_rng(27)))
    pts = np.random.default_rng(270).random((4, 2))
    want = eng.query("t", pts)

    lows = [eng.submit_query("t", pts, priority=0) for _ in range(12)]
    high = eng.submit_query("t", pts, priority=10)
    n = eng.pump()                              # batch-full -> due now
    assert high.done()                          # promoted into this pump
    assert n <= 1 + eng.stats()["scheduler"]["max_batch"]
    done_lows = [f for f in lows if f.done()]
    assert 0 < len(done_lows) <= 4              # capped, not drained
    assert all(high.done_at <= f.done_at for f in done_lows)
    eng.flush()
    for f in lows + [high]:
        np.testing.assert_array_equal(f.result(), want)

    # cross-tenant promotion: a prio-10 query on ANOTHER tenant, inside
    # its own deadline budget, rides along when prio-0 work dispatches
    eng.register("u", scheme, _random_grids(scheme,
                                            np.random.default_rng(271)))
    lows2 = [eng.submit_query("t", pts, priority=0) for _ in range(4)]
    high2 = eng.submit_query("u", pts, priority=10)
    eng.pump()                                  # "t" batch-full -> due
    assert high2.done()                         # promoted, not expired
    assert all(high2.done_at <= f.done_at for f in lows2 if f.done())
    assert eng.stats()["scheduler"]["promoted"] >= 1


def test_high_priority_never_pads_into_low_priority_chunk():
    """Chunks split at priority boundaries: with both priorities due in
    one pump, the prio-5 group dispatches as its own chunk before any
    prio-0 work (completion order is the observable)."""
    scheme = CombinationScheme(2, 3)
    eng = CTEngine(max_batch=64)
    eng.register("t", scheme, _random_grids(scheme, np.random.default_rng(28)))
    pts = np.random.default_rng(280).random((4, 2))
    f_low = [eng.submit_query("t", pts, priority=0) for _ in range(3)]
    f_high = eng.submit_query("t", pts, priority=5)
    assert eng.pump(now=1e18) == 4
    assert all(f_high.done_at <= f.done_at for f in f_low)


def test_donated_ingest_bit_identical_and_donation_threaded():
    """Satellite: ``ExecSpec(donate=True)`` changes nothing about the
    results (bit-identical surplus and queries) while the donation is
    genuinely handed to XLA — on backends that can alias it the input
    buffers are retired (``is_deleted``); where the backend cannot use
    it, jax's donation warning proves it was requested."""
    scheme = CombinationScheme(2, 4)
    rng = np.random.default_rng(29)
    host_grids = {ell: rng.standard_normal(grid_shape(ell))
                  for ell, _ in scheme.grids}
    e_plain = CTEngine()
    e_plain.register("t", scheme, host_grids)
    want = np.asarray(e_plain.surplus("t"))

    staged = {ell: jnp.asarray(g) for ell, g in host_grids.items()}
    e_don = CTEngine(ExecSpec(donate=True))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        e_don.register("t", scheme, staged)
    np.testing.assert_array_equal(np.asarray(e_don.surplus("t")), want)

    donation_warned = any("donated" in str(w.message).lower()
                          for w in caught)
    buffers_retired = any(getattr(g, "is_deleted", lambda: False)()
                          for g in staged.values())
    assert donation_warned or buffers_retired

    # donate is part of the plan signature: no cache collision with the
    # non-donating executable of the same plan shape
    from repro.core.engine import plan_signature
    assert plan_signature(e_plain.plan("t"), e_plain.spec("t")) \
        != plan_signature(e_don.plan("t"), e_don.spec("t"))

    # numpy inputs are staged fresh per call: always safe to re-ingest
    pts = np.random.default_rng(290).random((8, 2))
    e_don.update("t", host_grids)
    np.testing.assert_array_equal(e_don.query("t", pts),
                                  e_plain.query("t", pts))


def test_heartbeat_and_probe_ride_the_scheduler():
    """Host plumbing for the cluster health monitor: ``heartbeat()``
    reports pump liveness, and ``submit_probe`` resolves ONLY when a
    pump/flush/scheduler pass actually runs (``CTFuture.wait`` never
    drives the engine from the prober's thread)."""
    eng = CTEngine(host_id="h7")
    hb = eng.heartbeat()
    assert hb["host_id"] == "h7" and not hb["scheduler_alive"]
    assert hb["age_s"] >= 0.0 and hb["pending"] == 0

    probe = eng.submit_probe()
    assert not probe.wait(0.05)         # nobody pumps -> must NOT resolve
    assert eng.pump() >= 1
    assert probe.wait(0.0) and probe.result() is True
    assert eng.heartbeat()["age_s"] < eng._deadline_ms  # pump refreshed it

    # saturated-engine errors carry the host prefix
    from repro.core.engine import EngineSaturated
    scheme = CombinationScheme(2, 3)
    eng2 = CTEngine(max_pending=1, host_id="h9")
    eng2.register("t", scheme, _random_grids(scheme,
                                             np.random.default_rng(30)))
    pts = np.random.default_rng(300).random((4, 2))
    eng2.submit_query("t", pts)
    with pytest.raises(EngineSaturated, match=r"engine\[h9\].*tenant 't'"):
        eng2.submit_query("t", pts, block=False)


def test_register_adoption_fast_lane_plan_and_surplus():
    """Cluster failover seam: ``register(plan=, surplus=)`` adopts a
    donor's plan and served state verbatim — no plan rebuild, no
    re-ingest — and queries answer from the adopted surplus at once."""
    scheme = CombinationScheme(2, 4)
    rng = np.random.default_rng(31)
    donor = CTEngine()
    donor.register("t", scheme, _random_grids(scheme, rng))
    pts = np.random.default_rng(310).random((8, 2))
    want = donor.query("t", pts)

    heir = CTEngine()
    heir.register("t", scheme, plan=donor.plan("t"),
                  surplus=donor._tenant("t").surplus)
    assert heir.plan("t") is donor.plan("t")
    np.testing.assert_array_equal(np.asarray(heir.surplus("t")),
                                  np.asarray(donor.surplus("t")))
    np.testing.assert_array_equal(heir.query("t", pts), want)
    with pytest.raises(ValueError, match="surplus"):
        CTEngine().register("u", scheme,
                            _random_grids(scheme, rng),
                            surplus=donor._tenant("t").surplus)
