"""A query chunk is evaluated once per distinct surplus in it.

``CTEngine._eval_chunk`` groups a chunk's rows by the surplus they read
and runs one eval per group over the points of all its rows, padded to
a power of two of rows; no surplus is copied.  These tests mix tenants
inside one chunk and repeat them, then check the answers against a
per-row eval and against the direct combination of the nodal grids, the
engine's ``surplus_evals`` counter, and that a new row count compiles
nothing once a surplus shape has been seen.
"""

import jax
import jax.monitoring
import numpy as np
import pytest

from repro.core import combination as comb
from repro.core.engine import CTEngine
from repro.core.interpolation import interpolate_hierarchical
from repro.core.levels import CombinationScheme, grid_shape

LEVEL = 4
TENANTS = ("a", "b", "c")
#: the tenant of each query, in submission order: with ``max_batch`` 8
#: the first chunk holds all three tenants, "a" four times, and the
#: second chunk "b" twice and "c" once
ORDER = ("a", "b", "a", "c", "a", "b", "a", "c", "b", "c", "b")
MAX_BATCH = 8
POINTS = 13         # padded to 16 a row

#: f32 answers against the float64 reference.  The surplus comes from
#: f32 hierarchization and a weighted gather over the scheme's grids, and
#: each answer is a contraction over up to 15 hats an axis: a few dozen
#: roundings of unit 6e-8, each on a term of the answer's size.  It reads
#: 2.2e-7 at most on these cases; 1e-5 leaves a factor of forty.
F32_RTOL = 1e-5
#: float64 throughout: the same arithmetic at unit 1.1e-16
F64_RTOL = 1e-12


def _grids(scheme, seed, dtype):
    rng = np.random.default_rng(seed)
    return {ell: rng.standard_normal(grid_shape(ell)).astype(dtype)
            for ell, _ in scheme.grids}


def _serve(dim, dtype):
    """Three tenants, then ``ORDER``'s queries in one flush; returns the
    engine, each tenant's grids, each query's points and answer."""
    scheme = CombinationScheme(dim, LEVEL)
    eng = CTEngine(max_batch=MAX_BATCH)
    grids = {name: _grids(scheme, k, dtype) for k, name in enumerate(TENANTS)}
    for name in TENANTS:
        eng.register(name, scheme, grids[name])
    rng = np.random.default_rng(100 + dim)
    points = [rng.random((POINTS, dim)).astype(dtype) for _ in ORDER]
    futs = [eng.submit_query(name, p) for name, p in zip(ORDER, points)]
    eng.flush()
    return eng, scheme, grids, points, [np.asarray(f.result()) for f in futs]


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("dim", [2, 3])
@pytest.mark.parametrize("x64", [True, False], ids=["f64", "f32"])
def test_grouped_answers_match_per_row_eval_and_reference(dim, x64):
    dtype = np.float64 if x64 else np.float32
    rtol = F64_RTOL if x64 else F32_RTOL
    with jax.enable_x64(x64):
        eng, scheme, grids, points, answers = _serve(dim, dtype)
        per_row = [np.asarray(interpolate_hierarchical(eng.surplus(name), p))
                   for name, p in zip(ORDER, points)]
        eng.close()
    for name, pts, got, row in zip(ORDER, points, answers, per_row):
        assert got.shape == (POINTS,) and got.dtype == dtype
        assert _rel(got, row) <= rtol
        want = np.asarray(comb.combined_interpolant_points(
            {ell: np.asarray(g, np.float64) for ell, g in grids[name].items()},
            scheme, np.asarray(pts, np.float64)))
        assert want.dtype == np.float64
        assert _rel(got, want) <= rtol


@pytest.mark.parametrize("dim", [2, 3])
def test_surplus_evals_count_the_distinct_tenants_of_each_chunk(dim):
    eng, *_ = _serve(dim, np.float64)
    chunks = [ORDER[i:i + MAX_BATCH] for i in range(0, len(ORDER), MAX_BATCH)]
    ev = eng.stats()["eval"]
    assert ev["batches"] == len(chunks) == 2
    assert ev["queries"] == len(ORDER)
    assert ev["surplus_evals"] == sum(len(set(c)) for c in chunks) == 5
    eng.close()


@pytest.mark.parametrize("dim", [2, 3])
def test_a_new_row_count_compiles_nothing_after_the_first_eval(dim):
    """The first eval of a surplus shape compiles every row padding a
    chunk of ``max_batch`` rows can reach; chunks that then put 5, 8
    and 3 rows on one tenant (row paddings 8, 8 and 4, where the
    engine's own queries so far reached 4 at most) compile nothing."""
    eng, *_ = _serve(dim, np.float64)
    compiles = []

    def on(name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(name)
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        rng = np.random.default_rng(7)
        for rows in (5, MAX_BATCH, 3):
            futs = [eng.submit_query("a", rng.random((POINTS, dim)))
                    for _ in range(rows)]
            eng.flush()
            for f in futs:
                f.result()
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert compiles == []
    assert eng.stats()["eval"]["surplus_evals"] == 5 + 3
    eng.close()
