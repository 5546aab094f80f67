"""The prod_3d configuration and its two cells: their files, the plain
reference at d = 3, and the two readers of the query cell's eval."""

from types import SimpleNamespace

import numpy as np
import pytest

from conftest import REPO
from harness import scheme, traffic
from harness import trace as trc
from harness.layout import Layout

CELLS = ("prod_3d.refresh", "prod_3d.query")
E = trc.Event
US = 1000.0   # ns


@pytest.fixture(scope="module")
def lay():
    return Layout(REPO)


@pytest.mark.parametrize("cell", CELLS)
def test_the_layout_finds_every_file_of_the_cell(lay, cell):
    entry = lay.workload(cell)
    assert entry["config"] == "prod_3d" and entry["chips"] == 1
    cfg = lay.config("prod_3d")
    assert lay.config_entry("prod_3d")["reduced"] == cfg["reduced"] == []
    grids = scheme.scheme_grids(cfg["scheme"])
    fine = scheme.fine_levels(grids)
    sizes = cfg["sizes"]
    assert sizes["grids"] == len(grids) == 109
    assert sizes["component_points_per_tenant"] == sum(
        scheme.num_points(ell) for ell, _ in grids) == 73915
    assert sizes["component_bytes_per_tenant"] == 4 * 73915
    assert tuple(sizes["fine_shape"]) == scheme.grid_shape(fine) == (511,) * 3
    assert sizes["surplus_bytes_per_tenant"] == 4 * 511 ** 3
    assert sizes["served_surplus_bytes"] == \
        cfg["tenants"] * sizes["surplus_bytes_per_tenant"]
    assert cfg["engine"]["max_batch"] == 32
    mix = lay.traffic(entry["traffic"])
    assert traffic.block_of(mix) == cell.split(".")[1]
    limit = lay.checks(cell)["answer_gap"]
    assert limit["lower_reading"] < limit["limit"] < limit["upper_reading"]
    assert hasattr(lay.reference(cfg["reference"]), "reference_values")
    reported = {m["name"] for m in lay.end_to_end(cell)}
    assert "setup_s" in reported and len(reported) >= 2
    metrics = lay.per_layer(cell)
    assert metrics
    for m in metrics:
        assert m["moves"] in reported
        assert hasattr(lay.metric_reader(m["name"]), "read")


def _trilinear(grid, y):
    """A grid's multilinear interpolant at one point, corner by corner
    (zero on the boundary)."""
    padded = np.pad(grid, 1)
    idx, frac = [], []
    for k, n in enumerate(grid.shape):
        t = y[k] * (n + 1)
        i = min(int(np.floor(t)), n)
        idx.append(i)
        frac.append(t - i)
    out = 0.0
    for corner in np.ndindex(*(2,) * grid.ndim):
        w = np.prod([f if c else 1 - f for c, f in zip(corner, frac)])
        out += w * padded[tuple(i + c for i, c in zip(idx, corner))]
    return out


def test_the_reference_is_the_direct_float64_combination_at_d3(lay):
    ref = lay.reference("combination_multilinear")
    rng = np.random.default_rng(3)
    grids = [(ell, c, rng.standard_normal(scheme.grid_shape(ell)))
             for ell, c in scheme.combination_grids(3, 4)]
    ys = rng.random((40, 3))
    want = np.array([sum(c * _trilinear(g, y) for _, c, g in grids)
                     for y in ys])
    got = ref.reference_values(grids, ys)
    assert got.dtype == np.float64
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-12 * np.max(np.abs(want)))


def _ctx(modules, queries, batches):
    devices = {"/device:TPU:0": {trc.OPS_LINE: [E("fusion", 0, 1 * US)],
                                 trc.MODULES_LINE: list(modules)}}
    t = trc.Trace(devices=devices, host={}, window=(0.0, 100 * US))
    return SimpleNamespace(trace=t, counters_trace={
        "eval.queries": queries, "eval.batches": batches})


def test_the_two_eval_readers_on_a_synthetic_trace(lay):
    # five eval runs in the slice (the last one's midpoint lies past it)
    # and an ingest between them; the engine counted 2 chunks, 8 queries
    modules = [E("jit_interpolate_hierarchical(7)", 10 * US, 4 * US),
               E("jit_interpolate_hierarchical(7)", 15 * US, 6 * US),
               E("jit_ingest_packed(3)", 30 * US, 8 * US),
               E("jit_interpolate_hierarchical(7)", 40 * US, 5 * US),
               E("jit_interpolate_hierarchical(9)", 46 * US, 2 * US),
               E("jit_interpolate_hierarchical(7)", 50 * US, 3 * US),
               E("jit_interpolate_hierarchical(7)", 96 * US, 10 * US)]
    ctx = _ctx(modules, queries=8, batches=2)
    read = lambda name: lay.metric_reader(name).read(ctx)
    assert read("surplus_evals_per_batch") == pytest.approx(5 / 2)
    # 20 us of eval program over 8 queries
    assert read("surplus_eval_ms_per_query") == pytest.approx(0.020 / 8)
    # nothing to read: no chunk or query counted, no eval run, no trace
    empty = _ctx(modules, queries=0, batches=0)
    for name in ("surplus_evals_per_batch", "surplus_eval_ms_per_query"):
        assert lay.metric_reader(name).read(empty) is None
        assert lay.metric_reader(name).read(
            _ctx(modules[2:3], queries=8, batches=2)) is None
    ctx.trace = None
    assert read("surplus_evals_per_batch") is None
    assert read("surplus_eval_ms_per_query") is None
