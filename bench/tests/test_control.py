"""The control (the reference at Precision.HIGH in the program's place)
comes out not correct under the cell's limit, and the program does not,
over a few seeds at a size a test run holds."""

import pytest

from conftest import TINY_LIMIT
from harness.layout import Layout


@pytest.mark.parametrize("workload", ["tiny_2d.refresh", "tiny_2d.query"])
def test_control_fails_where_the_program_passes(tiny_root, workload):
    import control
    lay = Layout(tiny_root)
    rows = [control.readings(lay, workload, seed, 1.0) for seed in (21, 22, 23)]
    for r in rows:
        assert r["missing"] == 0 and r["compared"] > 0
        assert r["program_gap"] <= TINY_LIMIT, r
        assert r["control_gap"] > TINY_LIMIT, r
