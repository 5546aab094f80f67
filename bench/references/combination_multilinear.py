"""Plain reference of the combination technique, from the nodal grids.

The combined interpolant at a point is the coefficient-weighted sum,
over the scheme's component grids, of each grid's multilinear
interpolant (zero on the boundary).  Each grid's interpolant is written
as a tensor contraction with the nodal hat functions of its axes::

    u_l(y) = sum_j u_l[j] prod_k phi_{l_k, j_k}(y_k)

No hierarchization, no fine grid, no batching: it shares nothing with
the program but the mathematics.  It runs on the host in numpy:

* ``control=False`` — float64 throughout, the yardstick;
* ``control=True``  — float32 with every product of the contraction
  taken as Precision.HIGH takes it on a TPU (three bfloat16 passes,
  ``hi*hi + hi*lo + lo*hi``, accumulated in float32), written out so it
  reads the same on any backend.  This is the control: the step below
  the float32-at-HIGHEST the configuration states.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import ml_dtypes
import numpy as np

_BF16 = ml_dtypes.bfloat16
_BLOCK = 4096


def _basis(n: int, ys: np.ndarray, dtype) -> np.ndarray:
    """(P, n) nodal hat functions of an axis with n interior nodes."""
    centers = np.arange(1, n + 1, dtype=dtype) / dtype(n + 1)
    return np.maximum(0, 1 - np.abs(ys[:, None].astype(dtype)
                                    - centers[None, :]) * dtype(n + 1))


def _split(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    hi = x.astype(_BF16).astype(np.float32)
    lo = (x - hi).astype(_BF16).astype(np.float32)
    return hi, lo


def _matmul(a, b, control):
    if not control:
        return a @ b
    (ah, al), (bh, bl) = _split(a), _split(b)
    return ah @ bh + (ah @ bl + al @ bh)


def _rowdot(b, acc, control):
    """sum_n b[p, n] * acc[p, n, r] -> (P, r)."""
    if not control:
        return np.einsum("pn,pnr->pr", b, acc)
    (bh, bl), (ah, al) = _split(b), _split(acc)
    f = lambda x, y: np.einsum("pn,pnr->pr", x, y)
    return f(bh, ah) + (f(bh, al) + f(bl, ah))


def interpolant(grid: np.ndarray, ys: np.ndarray, control: bool) -> np.ndarray:
    """One grid's multilinear interpolant at ``ys`` (P, d): its largest
    axis is contracted first, as one matrix product."""
    dtype = np.float32 if control else np.float64
    order = sorted(range(grid.ndim), key=lambda k: -grid.shape[k])
    g = np.transpose(grid, order).astype(dtype)
    ys = ys[:, order]
    p = ys.shape[0]
    acc = _matmul(_basis(g.shape[0], ys[:, 0], dtype),
                  g.reshape(g.shape[0], -1), control)
    for k in range(1, g.ndim):
        acc = _rowdot(_basis(g.shape[k], ys[:, k], dtype),
                      acc.reshape(p, g.shape[k], -1), control)
    return acc.reshape(p)


def reference_values(grids: Sequence[Tuple[Tuple[int, ...], float, np.ndarray]],
                     points: np.ndarray, *, control: bool = False) -> np.ndarray:
    """Combined interpolant at ``points`` (P, d) from ``(level vector,
    coefficient, nodal grid)`` triples; float64 out."""
    dtype = np.float32 if control else np.float64
    points = np.asarray(points)
    out = np.empty(points.shape[0], np.float64)
    for s in range(0, points.shape[0], _BLOCK):
        ys = points[s:s + _BLOCK]
        acc = np.zeros(ys.shape[0], dtype)
        for _, coeff, grid in grids:
            acc = acc + dtype(coeff) * interpolant(grid, ys, control)
        out[s:s + _BLOCK] = acc
    return out
