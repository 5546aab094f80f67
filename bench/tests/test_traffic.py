"""The traffic generator: fixed work per seed, seeded order."""

import numpy as np
import pytest

from harness import data, traffic


def test_arrivals_are_poisson_with_a_count_fixed_by_rate_and_window():
    a = traffic.arrival_offsets(200, 10.0, np.random.default_rng(1))
    b = traffic.arrival_offsets(200, 10.0, np.random.default_rng(2**31 + 5))
    assert len(a) == len(b) == 2000
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 10.0
    assert not np.allclose(a, b)
    np.testing.assert_array_equal(
        a, traffic.arrival_offsets(200, 10.0, np.random.default_rng(1)))


def test_arrivals_swing_as_a_poisson_stream_does():
    """Counts in 0.2 s bins have a variance near their mean (index of
    dispersion 1), and gaps an exponential tail: no evening out."""
    off = traffic.arrival_offsets(320, 50.0, np.random.default_rng(9))
    counts = np.bincount((off / 0.2).astype(int), minlength=250)
    assert counts.var() / counts.mean() == pytest.approx(1.0, abs=0.25)
    gaps = np.diff(off) * 320
    # P(gap > 3 / rate) = e^-3 for exponential gaps
    assert np.mean(gaps > 3) == pytest.approx(np.exp(-3), rel=0.25)


def test_a_mix_holds_one_known_block_with_known_keys():
    assert traffic.block_of({"refresh": {"clients": 2, "ring": 2}}) == "refresh"
    assert traffic.block_of({"query": {"rate_per_s": 1, "points": 4}}) == "query"
    with pytest.raises(ValueError, match="one block"):
        traffic.block_of({"refresh": {"clients": 1, "ring": 1},
                          "query": {"rate_per_s": 1, "points": 4}})
    with pytest.raises(ValueError, match="unknown query keys"):
        traffic.block_of({"query": {"rate_per_s": 1, "points": 4,
                                    "bursts": None}})


def test_client_blocks_cover_every_tenant_once():
    blocks = traffic.client_blocks(256, 8)
    assert [len(b) for b in blocks] == [32] * 8
    assert sorted(t for b in blocks for t in b) == list(range(256))
    with pytest.raises(ValueError):
        traffic.client_blocks(4, 8)


def test_fields_vanish_on_the_boundary_and_are_seeded():
    levels = [(3, 1), (1, 3), (2, 2)]
    p1 = data.field_params(np.random.default_rng(7), 2, 2,
                           {"kind": "poly_sine", "max_freq": 8})
    p2 = data.field_params(np.random.default_rng(7), 2, 2,
                           {"kind": "poly_sine", "max_freq": 8})
    g1, g2 = data.make_grids(levels, p1), data.make_grids(levels, p2)
    for ell in levels:
        assert g1[ell].dtype == np.float32
        assert g1[ell].shape == (2,) + tuple(2 ** l - 1 for l in ell)
        np.testing.assert_array_equal(g1[ell], g2[ell])
    assert not np.array_equal(g1[(2, 2)][0], g1[(2, 2)][1])
