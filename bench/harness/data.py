"""Seeded solver output: the nodal component grids each tenant ingests.

A field is a smooth part plus an oscillating part, both products of
one-dimensional factors that vanish on the boundary of [0, 1]^d (the
grids carry no boundary nodes)::

    u(x) = prod_k 4 x_k (1 - x_k) (1 + b_k x_k) + a * prod_k sin(pi m_k x_k)

with ``b_k ~ U(0, 1)``, ``a ~ U(0.25, 0.5)`` and integer ``m_k`` in
``[1, max_freq]`` drawn per field from the seed.  The oscillating part
keeps the fine-level surpluses of order one, so a fault in any bucket
shows in the served answers.  The grids are host float32 arrays: a
solver hands its output to the service from the host.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from .scheme import grid_shape


def field_params(rng: np.random.Generator, n: int, dim: int,
                 spec: dict) -> Dict[str, np.ndarray]:
    if spec.get("kind") != "poly_sine":
        raise ValueError(f"unknown field kind {spec.get('kind')!r}")
    return {"b": rng.uniform(0.0, 1.0, (n, dim)),
            "a": rng.uniform(0.25, 0.5, n),
            "m": rng.integers(1, int(spec["max_freq"]) + 1, (n, dim))}


def _outer(factors: Sequence[np.ndarray]) -> np.ndarray:
    """(n, n_1) x ... x (n, n_d) -> (n, n_1, ..., n_d), row by row."""
    acc = factors[0]
    for f in factors[1:]:
        acc = acc[..., None] * f.reshape((f.shape[0],) + (1,) * (acc.ndim - 1)
                                         + (f.shape[1],))
    return acc


def make_grids(levels: Sequence[Tuple[int, ...]],
               params: Dict[str, np.ndarray]) -> Dict[Tuple[int, ...], np.ndarray]:
    """``{level vector: (n, *grid_shape) float32}`` for ``n`` fields."""
    b, a, m = params["b"], params["a"], params["m"]
    out = {}
    for ell in levels:
        poly, wave = [], []
        for k, n_k in enumerate(grid_shape(ell)):
            x = np.arange(1, n_k + 1) / (n_k + 1.0)
            poly.append(4 * x * (1 - x) * (1 + b[:, k, None] * x))
            wave.append(np.sin(np.pi * m[:, k, None] * x))
        vals = _outer(poly) + a.reshape((-1,) + (1,) * len(ell)) * _outer(wave)
        out[ell] = vals.astype(np.float32)
    return out


def split(stacked: Dict[Tuple[int, ...], np.ndarray],
          n: int) -> List[Dict[Tuple[int, ...], np.ndarray]]:
    """Per-field ``{level vector: grid}`` views of ``make_grids``."""
    return [{ell: arr[i] for ell, arr in stacked.items()} for i in range(n)]
