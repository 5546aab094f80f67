"""The plain reference and its control, against independent arithmetic."""

import numpy as np
import pytest

from conftest import BENCH
from harness import scheme
from harness.layout import Layout


@pytest.fixture(scope="module")
def ref():
    return Layout(BENCH.parent).reference("combination_multilinear")


def _hat_interp(vals, ys):
    """1-D piecewise-linear interpolant with zero boundary values."""
    n = len(vals)
    xs = np.arange(n + 2) / (n + 1)
    return np.interp(ys, xs, np.concatenate([[0.0], vals, [0.0]]))


def test_one_grid_is_its_multilinear_interpolant(ref):
    rng = np.random.default_rng(0)
    f, g = rng.standard_normal(7), rng.standard_normal(3)   # levels (3, 2)
    grid = np.outer(f, g).astype(np.float32)
    ys = rng.random((50, 2))
    want = _hat_interp(f, ys[:, 0]) * _hat_interp(g, ys[:, 1])
    got = ref.reference_values([((3, 2), 1, grid)], ys)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_combination_of_a_multilinear_field_is_exact(ref):
    """A field that is multilinear between the coarsest nodes is
    reproduced by every grid, and the coefficients sum to one."""
    grids = []
    for ell, c in scheme.combination_grids(3, 4):
        axes = [np.arange(1, 2 ** l) / 2 ** l for l in ell]
        tent = [1 - np.abs(2 * a - 1) for a in axes]     # hat of level 1
        vals = tent[0][:, None, None] * tent[1][None, :, None] * tent[2][None, None, :]
        grids.append((ell, c, vals.astype(np.float32)))
    ys = np.random.default_rng(1).random((64, 3))
    want = np.prod(1 - np.abs(2 * ys - 1), axis=1)
    np.testing.assert_allclose(ref.reference_values(grids, ys), want,
                               rtol=0, atol=1e-6)


def test_the_control_is_high_precision_not_exact(ref):
    rng = np.random.default_rng(2)
    grids = [((5, 5), 1, rng.random((31, 31)).astype(np.float32))]
    ys = rng.random((256, 2))
    exact = ref.reference_values(grids, ys)
    ctl = ref.reference_values(grids, ys, control=True)
    gap = np.max(np.abs(ctl - exact)) / np.max(np.abs(exact))
    # three bfloat16 passes: well below bfloat16's 4e-3, well above
    # float32's rounding
    assert 1e-7 < gap < 1e-4
