"""Interpolation on combination grids.

Two equivalent evaluations used to validate hierarchization end-to-end:

* ``interpolate_nodal``        — d-multilinear interpolation of nodal values
  (what the PDE solver's grid function means), zero Dirichlet boundary.
* ``interpolate_hierarchical`` — hat-basis tensor contraction of hierarchical
  surpluses.

``interpolate_hierarchical(hierarchize(u), y) == interpolate_nodal(u, y)``
for every grid function u and point y in [0,1]^d — this is the property test
anchoring the whole transform stack.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ref import level_of_position

__all__ = ["interpolate_nodal", "interpolate_hierarchical", "sample_function"]


def _axis_level(n: int) -> int:
    level = int(np.log2(n + 1))
    assert (1 << level) - 1 == n
    return level


def sample_function(fn, levels: Sequence[int]) -> jnp.ndarray:
    """Sample ``fn`` (vectorized over a meshgrid tuple) on the nodal grid."""
    axes = [jnp.arange(1, (1 << l)) * (2.0 ** -l) for l in levels]
    mesh = jnp.meshgrid(*axes, indexing="ij")
    return fn(*mesh)


def interpolate_nodal(u: jnp.ndarray, points: jnp.ndarray) -> jnp.ndarray:
    """Multilinear interpolation of nodal grid values at ``points`` (B, d).

    The grid has no boundary points; the function is 0 on the boundary.
    """
    points = jnp.atleast_2d(points)
    b, d = points.shape
    assert d == u.ndim
    # pad with the zero boundary so every cell has both corners
    up = jnp.pad(u, [(1, 1)] * d)
    idxs, weights = [], []
    for ax in range(d):
        level = _axis_level(u.shape[ax])
        h = 2.0 ** -level
        t = jnp.clip(points[:, ax] / h, 0.0, (1 << level) - 1e-9)
        i0 = jnp.floor(t).astype(jnp.int32)        # cell index in padded coords
        w1 = t - i0
        idxs.append(i0)
        weights.append(w1)
    out = jnp.zeros((b,), u.dtype)
    for corner in range(1 << d):
        w = jnp.ones((b,), u.dtype)
        gather_idx = []
        for ax in range(d):
            bit = (corner >> ax) & 1
            gather_idx.append(idxs[ax] + bit)
            w = w * jnp.where(bit, weights[ax], 1.0 - weights[ax]).astype(u.dtype)
        out = out + w * up[tuple(gather_idx)]
    return out


def _hat_basis_matrix(level: int, ys: jnp.ndarray) -> jnp.ndarray:
    """(B, N) matrix of phi_{lam,p}(y) for all N nodes of a level-l pole."""
    n = (1 << level) - 1
    p = np.arange(1, n + 1)
    lam = np.array([level_of_position(int(pi), level) for pi in p])
    centers = jnp.asarray(p * (2.0 ** -level))
    inv_supp = jnp.asarray(2.0 ** lam.astype(np.float64))
    return jnp.maximum(0.0, 1.0 - jnp.abs(ys[:, None] - centers[None, :]) * inv_supp[None, :])


def interpolate_hierarchical(alpha: jnp.ndarray, points: jnp.ndarray) -> jnp.ndarray:
    """Evaluate the hierarchical interpolant sum_v alpha_v * prod_i phi(y_i)."""
    points = jnp.atleast_2d(points)
    b, d = points.shape
    assert d == alpha.ndim
    acc = alpha.astype(jnp.result_type(alpha.dtype, jnp.float32))
    # full-precision contractions: the TPU's default f32 matmul is one
    # bf16 pass, ~1e-3 relative error on a served answer
    hi = jax.lax.Precision.HIGHEST
    # contract one axis at a time: acc starts (N1..Nd), ends (B,)
    for ax in range(d):
        level = _axis_level(alpha.shape[ax])
        basis = _hat_basis_matrix(level, points[:, ax]).astype(acc.dtype)  # (B, N)
        if ax == 0:
            acc = jnp.tensordot(basis, acc, axes=[[1], [0]],
                                precision=hi)  # (B, N2..Nd)
        else:
            # acc is (B, N_ax, rest...); contract per-row
            acc = jnp.einsum("bn,bn...->b...", basis, acc, precision=hi)
    return acc
