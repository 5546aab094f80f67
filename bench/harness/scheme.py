"""Combination-scheme and byte arithmetic of the benchmark's own.

A copy of the level-vector enumeration the program uses (so the
yardstick does not move when the program changes), plus the least bytes
an ingest and a hierarchization bucket must move, computed from level
vectors alone: they read the same whichever path implements the work.
"""

from __future__ import annotations

import math
from typing import Iterator, List, Sequence, Tuple

LevelVector = Tuple[int, ...]


def grid_shape(levels: Sequence[int]) -> Tuple[int, ...]:
    """Interior nodes per axis of a level vector (no boundary nodes)."""
    return tuple((1 << int(l)) - 1 for l in levels)


def num_points(levels: Sequence[int]) -> int:
    return math.prod(grid_shape(levels))


def _with_sum(dim: int, total: int) -> Iterator[LevelVector]:
    if dim == 1:
        if total >= 1:
            yield (total,)
        return
    for first in range(1, total - (dim - 1) + 1):
        for rest in _with_sum(dim - 1, total - first):
            yield (first,) + rest


def combination_grids(dim: int, level: int) -> List[Tuple[LevelVector, int]]:
    """(level vector, coefficient) of the regular combination technique:
    the diagonals ``|l|_1 = level + dim - 1 - q`` with coefficient
    ``(-1)^q * C(dim - 1, q)``, ``q = 0 .. dim - 1``."""
    if level < 1:
        raise ValueError(f"sparse-grid level must be >= 1, got {level}")
    out = []
    for q in range(min(dim, level)):
        coeff = (-1) ** q * math.comb(dim - 1, q)
        out += [(ell, coeff) for ell in _with_sum(dim, level + dim - 1 - q)]
    return out


def scheme_grids(scheme: dict) -> List[Tuple[LevelVector, int]]:
    """The grids of a configuration's ``scheme`` block."""
    if scheme.get("kind") != "regular":
        raise ValueError(f"unknown scheme kind {scheme.get('kind')!r}")
    return combination_grids(int(scheme["dim"]), int(scheme["level"]))


def fine_levels(grids: Sequence[Tuple[LevelVector, int]]) -> LevelVector:
    dim = len(grids[0][0])
    return tuple(max(ell[k] for ell, _ in grids) for k in range(dim))


def least_ingest_bytes(grids, itemsize: int = 4) -> int:
    """Every component grid read once plus the fine-grid surplus written
    once: the bytes no implementation of an ingest can avoid."""
    read = sum(num_points(ell) for ell, _ in grids)
    return (read + num_points(fine_levels(grids))) * itemsize


def hierarchize_bytes(member_levels: Sequence[Sequence[int]],
                      itemsize: int = 4) -> int:
    """Each member grid read once and written once, unpadded: the least
    traffic of hierarchizing those grids in place."""
    return 2 * sum(num_points(l) for l in member_levels) * itemsize

