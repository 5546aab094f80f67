"""The combination technique communication phase.

Hierarchization makes this phase pure coefficient algebra (the paper's
raison d'être): in the hierarchical basis a combination grid ``ell`` carries
exactly the subspaces ``m <= ell`` and *implicitly zero surplus everywhere
else*, so

  * ``gather``  — the sparse grid surplus on subspace ``m`` is the
    coefficient-weighted sum over all combination grids containing ``m``;
  * ``scatter`` — projecting the sparse grid solution back onto a
    combination grid truncates to the subspaces ``m <= ell`` (plain copy).

Two realizations:

  * subspace-keyed (dict of blocks) — memory-proportional to the sparse
    grid, what a production multi-node run exchanges (one reduce per block);
  * embedded (common fine grid)    — each grid scattered into a level-L
    buffer so gather is ONE dense sum (slab-sharded across devices in
    ``repro.core.distributed``).

Both realizations here are Python dict loops — one dispatch per grid (per
subspace, even) — and serve as the readable oracle.  The PRODUCTION path
is ``repro.core.executor.ct_transform``: the same embedded gather as
``combine_full`` but bucket-batched and expressed as a precomputed static
index plan, end-to-end jittable.  ``tests/test_executor.py`` pins the two
paths together at 1e-12.

Every function is duck-typed over the scheme (``.dim`` + ``.grids``): the
classical ``CombinationScheme`` and the downward-closed ``GeneralScheme``
(adaptive / fault-reduced index sets) both work, so this module doubles as
the oracle for ``tests/test_adaptive.py``'s generalized-scheme round trips.
"""

from __future__ import annotations

from typing import Dict, Mapping, Sequence, Tuple

import jax.numpy as jnp

from repro.core.levels import (LevelVector, SchemeLike, fine_levels,
                               grid_shape, subspace_slices,
                               subspaces_of_grid)

__all__ = [
    "gather_subspaces", "scatter_subspaces",
    "embed_to_full", "extract_from_full",
    "combine_full", "combined_interpolant_points",
]


# ---------------------------------------------------------------------------
# Subspace-keyed communication phase
# ---------------------------------------------------------------------------

def gather_subspaces(hier_grids: Mapping[LevelVector, jnp.ndarray],
                     scheme: SchemeLike) -> Dict[LevelVector, jnp.ndarray]:
    """Gather step: combined surplus per sparse-grid subspace."""
    combined: Dict[LevelVector, jnp.ndarray] = {}
    coeffs = dict(scheme.grids)
    for ell, alpha in hier_grids.items():
        c = coeffs[ell]
        for m in subspaces_of_grid(ell):
            block = c * alpha[subspace_slices(m, ell)]
            if m in combined:
                combined[m] = combined[m] + block
            else:
                combined[m] = block
    return combined


def scatter_subspaces(combined: Mapping[LevelVector, jnp.ndarray],
                      scheme: SchemeLike) -> Dict[LevelVector, jnp.ndarray]:
    """Scatter step: project the sparse-grid surplus onto every grid."""
    out: Dict[LevelVector, jnp.ndarray] = {}
    for ell, _ in scheme.grids:
        alpha = jnp.zeros(grid_shape(ell))
        for m in subspaces_of_grid(ell):
            alpha = alpha.at[subspace_slices(m, ell)].set(combined[m])
        out[ell] = alpha
    return out


# ---------------------------------------------------------------------------
# Embedded (common-fine-grid) communication phase
# ---------------------------------------------------------------------------

def embed_to_full(alpha: jnp.ndarray, ell: Sequence[int],
                  full_levels: Sequence[int]) -> jnp.ndarray:
    """Scatter grid-``ell`` surpluses into the level-``full_levels`` buffer.

    Node position p (1-based) of grid ell maps to position p * 2**(L-l) of
    the fine grid — a single strided write per grid, no per-subspace loop.
    """
    full = jnp.zeros(grid_shape(full_levels), alpha.dtype)
    slices = tuple(slice((1 << (L - l)) - 1, None, 1 << (L - l))
                   for l, L in zip(ell, full_levels))
    return full.at[slices].set(alpha)


def extract_from_full(full: jnp.ndarray, ell: Sequence[int],
                      full_levels: Sequence[int]) -> jnp.ndarray:
    """Truncating projection: read back the nodes grid ``ell`` owns."""
    slices = tuple(slice((1 << (L - l)) - 1, None, 1 << (L - l))
                   for l, L in zip(ell, full_levels))
    return full[slices]


def combine_full(hier_grids: Mapping[LevelVector, jnp.ndarray],
                 scheme: SchemeLike,
                 full_levels: Sequence[int] | None = None
                 ) -> Tuple[jnp.ndarray, Tuple[int, ...]]:
    """One-buffer gather: sum of coefficient-weighted embedded surpluses.

    NOTE the sparse-grid surpluses of subspaces NOT in the sparse grid are
    zero by construction, so the buffer holds exactly the sparse grid
    interpolant expressed on the fine grid.
    """
    if full_levels is None:
        full_levels = fine_levels(scheme)
    acc = None
    for ell, c in scheme.grids:
        emb = c * embed_to_full(hier_grids[ell], ell, full_levels)
        acc = emb if acc is None else acc + emb
    return acc, tuple(full_levels)


def combined_interpolant_points(nodal_grids: Mapping[LevelVector, jnp.ndarray],
                                scheme: SchemeLike,
                                points: jnp.ndarray) -> jnp.ndarray:
    """Direct (no hierarchization) evaluation of the combination solution:
    weighted sum of multilinear interpolants.  Used as the gold standard the
    hierarchical communication phase must reproduce."""
    from repro.core.interpolation import interpolate_nodal
    acc = 0.0
    for ell, c in scheme.grids:
        acc = acc + c * interpolate_nodal(nodal_grids[ell], points)
    return acc
