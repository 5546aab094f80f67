"""Pallas TPU kernels for (de)hierarchization.

TPU adaptation of the paper's BFS-OverVectorized kernel (DESIGN.md Sect. 2).
Every kernel of this module except the MXU matmul shares ONE in-kernel
axis pass (``_hier_pass`` / ``_dehier_pass``): the transformed axis lives
on sublanes or lanes, all other dimensions ride along
("over-vectorization" with a 128-wide VREG instead of a 4-wide AVX
register), and the hierarchical predecessors of every node are fetched
with static rotations of the VMEM-resident block (``pltpu.roll``) plus a
per-level select — no gather, no strided update, so the pass lowers
through Mosaic at any extent.

* ``pole``   — the paper-faithful kernel: one (N, lane_tile) pole bundle
  per grid step, the whole level loop in VMEM (one HBM round trip).

* ``matmul`` — the beyond-paper MXU formulation: 1-D hierarchization is a
  constant linear operator H with <=3 nonzeros per row, so the whole pole
  transform is one (N x N) @ (N x lanes) matmul.  For N <= ~1900 the dense
  matmul is still HBM-bound on v5e (2*N^2*B flops vs 16*N*B bytes crosses
  the 197 TFLOP/s / 819 GB/s ridge at N ~ 1924).

* ``batched`` — the CT executor's bucket kernels (one launch per bucket,
  member index on the leading Pallas grid dimension): tail axes fused
  while tiling axis 0, then axis 0 while tiling the lanes — 2 HBM round
  trips for any d.  Each member's level vector arrives as scalar-prefetch
  data (SMEM), so members below the bucket target are masked exactly as
  their unpadded selves, and the level vectors may be runtime (sharded)
  arrays.  ``fused`` (``hierarchize_nd_fused``) is this path at G = 1.

All kernels are validated in ``interpret=True`` mode against
``repro.kernels.ref`` on the CPU; ``tests/test_tpu_compile.py`` compiles
them for a described v5e.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

__all__ = [
    "hier_pole_pallas",
    "dehier_pole_pallas",
    "apply_axis_matmul_pallas",
    "hier_fused_tail_pallas",
    "hier_axis0_pallas",
    "hierarchize_nd_fused",
    "dehierarchize_nd_fused",
    "hier_tail_batched_pallas",
    "hier_axis0_batched_pallas",
    "hierarchize_batched",
    "hierarchize_batched_jnp",
    "member_level_array",
    "dehierarchize_batched",
    "count_launches",
    "pad_blowup",
    "tile_volume",
    "batched_method",
    "hier_flops",
]

_LANE = 128
_SUBLANE = 8
#: per-buffer VMEM budget of one axis-0 block; in + out are double-
#: buffered and the pass keeps a few block-sized temporaries live, so 1 MiB
#: blocks stay well inside v5e's 16 MiB scoped-VMEM default
_AXIS0_BLOCK_BYTES = 1 << 20

# --- kernel-dispatch accounting (benchmarks / merge cost-model validation) --
#
# Counters are bumped at TRACE time, so inside jit they count the dispatches
# the compiled executable will issue per call (each pallas_call is one kernel
# launch; each per-axis pass of the jnp path is one fused XLA dispatch).
# ``count_launches()`` scopes the accounting.

_LAUNCHES = {"pallas": 0, "einsum": 0}


@contextlib.contextmanager
def count_launches():
    """Count kernel dispatches traced inside the block.

    Yields a dict, filled when the block EXITS, with keys ``pallas``
    (pallas_call launches) and ``einsum`` (per-axis dispatches of the jnp
    path)."""
    saved = dict(_LAUNCHES)
    _LAUNCHES["pallas"] = _LAUNCHES["einsum"] = 0
    result: dict = {}
    try:
        yield result
    finally:
        result.update(_LAUNCHES)
        _LAUNCHES.update({k: saved[k] + result[k] for k in saved})


def _count(kind: str) -> None:
    _LAUNCHES[kind] += 1


def _pallas_call(*args, **kwargs):
    _count("pallas")
    return pl.pallas_call(*args, **kwargs)


def interpret_default() -> bool:
    """THE interpret-mode default: Pallas kernels run in interpret mode
    everywhere except on real TPU.  Single resolution site for the whole
    repo (kernels, executor, ``repro.core.engine.ExecSpec``) — an
    ``interpret=None`` anywhere means "ask this helper at execution
    time", so the decision is never frozen into a config object."""
    return jax.default_backend() != "tpu"


def _level_of(n: int) -> int:
    level = int(np.log2(n + 1))
    if (1 << level) - 1 != n:
        raise ValueError(f"axis length {n} is not 2**l - 1")
    return level


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _padded_operator(level: int, dtype, inverse: bool = False,
                     npad: int | None = None) -> np.ndarray:
    """(npad, npad) operator with identity on the padding rows/cols."""
    n = (1 << level) - 1
    if npad is None:
        npad = _round_up(n, _SUBLANE)
    h = ref.dehier_operator_matrix(level) if inverse else ref.operator_matrix(level)
    out = np.eye(npad)
    out[:n, :n] = h
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# The shared axis pass (3-term predecessor form)
# ---------------------------------------------------------------------------
#
# FORWARD: ``alpha_j = u_j - u_{j-s}/2 - u_{j+s}/2`` with ``s = lowbit(j)``
# (1-based j), boundary ancestors zero — H has <= 3 nonzeros per row.  The
# neighbour offsets ``+-lowbit(j)`` depend only on the position, never on
# the pole's level; the level only decides which neighbours are REAL, so
# a pole of ``n = 2**l - 1`` nodes embedded at the head of a longer
# (padded) axis is handled by masks alone: an ancestor outside ``1..n``
# and every pad position get a False mask and contribute an exact
# ``+0.0``.  The result is therefore bitwise independent of the padded
# extent — what makes a merged super-bucket bit-identical to its unmerged
# buckets, and the Pallas path bit-identical to the jnp path.
#
# INVERSE: the same neighbours, coarse-to-fine (children need their
# parents' final nodal values), one select per level.

def _hier3(x: jnp.ndarray, xl: jnp.ndarray, xr: jnp.ndarray,
           lm: jnp.ndarray, rm: jnp.ndarray) -> jnp.ndarray:
    """THE forward update, shared by every batched path (pallas tail,
    pallas axis 0, jnp) so they all agree bitwise: fixed evaluation
    order, elementwise only.  Masked ancestors (boundary / zero-padding)
    contribute an exact ``+0.0`` regardless of the gathered value."""
    half = jnp.asarray(0.5, x.dtype)
    zero = jnp.zeros((), x.dtype)
    return x - half * jnp.where(lm, xl, zero) - half * jnp.where(rm, xr, zero)


def _dehier3(a: jnp.ndarray, ul: jnp.ndarray, ur: jnp.ndarray,
             lm: jnp.ndarray, rm: jnp.ndarray) -> jnp.ndarray:
    """THE inverse update (nodal value from surplus + final ancestors)."""
    half = jnp.asarray(0.5, a.dtype)
    zero = jnp.zeros((), a.dtype)
    return a + half * jnp.where(lm, ul, zero) + half * jnp.where(rm, ur, zero)


def _pred_masks(j, n):
    """Lowbit class and left/right ancestor masks of 1-based positions
    ``j`` in a pole of ``n`` real nodes (``n`` may be traced)."""
    s = j & -j
    real = j <= n
    return s, real & (j > s), real & (j + s <= n)


def _strides(n_max: int):
    """Lowbit classes that can hold an INTERIOR node of a pole of at most
    ``n_max`` real nodes (the axis' true, unpadded extent)."""
    k = 1
    while 2 * k <= n_max:
        yield k
        k *= 2


def _shift(x: jnp.ndarray, k: int, axis: int) -> jnp.ndarray:
    """``out[p] = x[(p - k) mod extent]`` along ``axis`` (k >= 0): a
    sublane/lane rotation on the two minor axes, slices otherwise."""
    if axis >= x.ndim - 2:
        return pltpu.roll(x, k, axis)
    return jnp.roll(x, k, axis)


def _axis_masks(x: jnp.ndarray, axis: int, n):
    j = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis) + 1
    return _pred_masks(j, n)


def _hier_pass(x: jnp.ndarray, axis: int, n, n_max: int, *,
               reduced_op: bool = False) -> jnp.ndarray:
    """Forward transform of a VMEM value along ``axis`` for a pole of
    ``n <= n_max`` real nodes (``n_max`` static, the axis' unpadded
    extent).  ``reduced_op`` is the paper's fused ``x - (xl + xr) / 2``
    spelling (pole kernel only)."""
    ext = x.shape[axis]
    s, lm, rm = _axis_masks(x, axis, n)
    xl = xr = x
    for k in _strides(n_max):
        cls = s == k
        xl = jnp.where(cls, _shift(x, k, axis), xl)
        xr = jnp.where(cls, _shift(x, ext - k, axis), xr)
    if reduced_op:
        half = jnp.asarray(0.5, x.dtype)
        zero = jnp.zeros((), x.dtype)
        return x - half * (jnp.where(lm, xl, zero) + jnp.where(rm, xr, zero))
    return _hier3(x, xl, xr, lm, rm)


def _dehier_pass(a: jnp.ndarray, axis: int, n, n_max: int) -> jnp.ndarray:
    """Inverse of ``_hier_pass`` (coarse-to-fine, one select per level)."""
    ext = a.shape[axis]
    s, lm, rm = _axis_masks(a, axis, n)
    u = a
    for k in reversed(list(_strides(n_max))):
        upd = _dehier3(a, _shift(u, k, axis), _shift(u, ext - k, axis),
                       lm, rm)
        u = jnp.where(s == k, upd, u)
    return u


def _axis_pass(x, axis, n, n_max, inverse):
    if inverse:
        return _dehier_pass(x, axis, n, n_max)
    return _hier_pass(x, axis, n, n_max)


def _real_nodes(level):
    """Real node count ``2**level - 1`` of a level (numpy, or traced);
    level 0 (a padding member) has none."""
    return (1 << level) - 1


# ---------------------------------------------------------------------------
# Pole kernel (paper-faithful: over-vectorization across lanes)
# ---------------------------------------------------------------------------

def _pole_kernel(x_ref, o_ref, *, n: int, inverse: bool, reduced_op: bool):
    if inverse:
        o_ref[...] = _dehier_pass(x_ref[...], 0, n, n)
    else:
        o_ref[...] = _hier_pass(x_ref[...], 0, n, n, reduced_op=reduced_op)


def _pole_call(x, *, inverse: bool, lane_tile: int, reduced_op: bool,
               interpret: bool | None):
    if interpret is None:
        interpret = interpret_default()
    n, b = x.shape
    if _level_of(n) == 1:
        return x
    npad = _round_up(n, _SUBLANE)
    bpad = _round_up(b, lane_tile)
    xp = jnp.pad(x, ((0, npad - n), (0, bpad - b)))
    kernel = functools.partial(_pole_kernel, n=n, inverse=inverse,
                               reduced_op=reduced_op)
    out = _pallas_call(
        kernel,
        grid=(bpad // lane_tile,),
        in_specs=[pl.BlockSpec((npad, lane_tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((npad, lane_tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((npad, bpad), x.dtype),
        interpret=interpret,
    )(xp)
    return out[:n, :b]


def hier_pole_pallas(x: jnp.ndarray, *, lane_tile: int = _LANE,
                     reduced_op: bool = True,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Hierarchize along axis 0 of a (N, B) pole bundle.

    N = 2**l - 1 pole points (sublanes), B poles (lanes).  One grid step
    stages a (Npad, lane_tile) block HBM->VMEM, runs all levels, writes back:
    exactly one HBM round trip, the paper's flat-performance property.
    """
    return _pole_call(x, inverse=False, lane_tile=lane_tile,
                      reduced_op=reduced_op, interpret=interpret)


def dehier_pole_pallas(a: jnp.ndarray, *, lane_tile: int = _LANE,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Dehierarchize along axis 0 of a (N, B) pole bundle (inverse of
    ``hier_pole_pallas``; same tiling, same single round trip).  Unlike
    hierarchization the inverse is sequential in LEVEL, but still fully
    lane-parallel across poles."""
    return _pole_call(a, inverse=True, lane_tile=lane_tile, reduced_op=False,
                      interpret=interpret)


# ---------------------------------------------------------------------------
# Matmul (MXU) kernel: one axis per call
# ---------------------------------------------------------------------------

def _matmul_kernel(h_ref, x_ref, o_ref):
    o_ref[...] = jnp.dot(h_ref[...], x_ref[...],
                         preferred_element_type=o_ref.dtype)


def apply_axis_matmul_pallas(x: jnp.ndarray, *, inverse: bool = False,
                             lane_tile: int = 512,
                             interpret: bool | None = None) -> jnp.ndarray:
    """(De)hierarchize along axis 0 of a (N, B) bundle via one MXU matmul."""
    if interpret is None:
        interpret = interpret_default()
    n, b = x.shape
    level = _level_of(n)
    if level == 1:
        return x
    npad = _round_up(n, _SUBLANE)
    lane_tile = min(lane_tile, _round_up(b, _LANE))
    bpad = _round_up(b, lane_tile)
    hmat = jnp.asarray(_padded_operator(level, np.float32, inverse=inverse),
                       dtype=x.dtype if x.dtype != jnp.bfloat16 else jnp.float32)
    xp = jnp.pad(x, ((0, npad - n), (0, bpad - b)))
    out = _pallas_call(
        _matmul_kernel,
        grid=(bpad // lane_tile,),
        in_specs=[
            pl.BlockSpec((npad, npad), lambda i: (0, 0)),
            pl.BlockSpec((npad, lane_tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((npad, lane_tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((npad, bpad), x.dtype),
        interpret=interpret,
    )(hmat, xp)
    return out[:n, :b]


def hier_axis0_pallas(x: jnp.ndarray, *, inverse: bool = False,
                      lane_tile: int = 512,
                      interpret: bool | None = None) -> jnp.ndarray:
    """(De)hierarchize axis 0 only (MXU matmul), tiling the flattened
    trailing axes."""
    shape = x.shape
    flat = x.reshape(shape[0], -1)
    out = apply_axis_matmul_pallas(flat, inverse=inverse, lane_tile=lane_tile,
                                   interpret=interpret)
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# Batched kernels: one bucket of same-shape grids per launch (CT executor)
# ---------------------------------------------------------------------------
#
# The combination technique dispatches one hierarchization per component
# grid; the executor (repro.core.executor) buckets grids that share a
# canonical shape and launches ONE Pallas call per bucket with the grid
# index as the leading Pallas grid dimension.  Members may sit at a level
# BELOW the bucket target (cost-driven bucket merging): they are
# zero-padded to the target extents and their own level vector (scalar
# prefetch) masks them, so padded members transform exactly as their
# unpadded selves.

def member_level_array(member_levels) -> np.ndarray:
    """Per-member level vectors of a bucket stack as ONE ``(G, d)`` int32
    array — the only per-member data the batched transforms need.  As an
    array it can be SHARDED along G: the 2-D sharded ingest passes it to
    ``hierarchize_batched`` inside its shard_map, where each device
    transforms only its member shard and the member set therefore cannot
    be a trace constant.  A row of zeros is a padding member (no real
    nodes: its rows pass through unchanged)."""
    return np.asarray([tuple(ml) for ml in member_levels],
                      np.int32).reshape(len(member_levels), -1)


def _level_table(levels):
    """The ``(G, d)`` int32 level table: a numpy constant when the member
    levels are static (so masks and operands stay host constants), the
    array itself when it is a runtime (traced/sharded) one."""
    if isinstance(levels, jax.Array):
        return levels.astype(jnp.int32)
    return member_level_array(levels)


def _batched_tail_kernel(lv_ref, x_ref, o_ref, *, extents, inverse: bool):
    """Tail transform of a (1, R, N2..Nd) block: every tail axis in turn
    while the block stays VMEM-resident (the fusion the paper's CPU
    caches could not hold)."""
    gi = pl.program_id(0)
    x = x_ref[0]
    ntail = len(extents)
    for k, n_max in enumerate(extents):
        x = _axis_pass(x, 1 + k, _real_nodes(lv_ref[gi * ntail + k]),
                       n_max, inverse)
    o_ref[0] = x


def hier_tail_batched_pallas(x: jnp.ndarray, member_levels, *,
                             inverse: bool = False,
                             row_tile: int | None = None,
                             vmem_budget_bytes: int = 4 * 1024 * 1024,
                             interpret: bool | None = None) -> jnp.ndarray:
    """(De)hierarchize grid axes 1..d-1 of a (G, N1, ..., Nd) bucket.

    ``member_levels`` is the ``(G, d)`` member level table — level
    vectors in bucket axis order, or a (possibly traced/sharded) int32
    array as ``member_level_array`` builds it."""
    if interpret is None:
        interpret = interpret_default()
    if x.ndim < 3:
        raise ValueError("need (G, N1, N2, ...); use the axis-0 kernel for 1-D")
    g = x.shape[0]
    shape = x.shape[1:]
    nd = len(shape)
    pads = [_round_up(s, _SUBLANE if i < nd - 1 else _LANE)
            for i, s in enumerate(shape)]
    tail_elems = int(np.prod(pads[1:]))
    itemsize = jnp.dtype(x.dtype).itemsize
    if row_tile is None:
        row_tile = max(1, vmem_budget_bytes // max(1, tail_elems * itemsize * 2))
        row_tile = min(max(_SUBLANE, _round_up(row_tile, _SUBLANE)), pads[0])
    rpad = _round_up(pads[0], row_tile)
    xp = jnp.pad(x, [(0, 0), (0, rpad - shape[0])] +
                 [(0, p - s) for p, s in zip(pads[1:], shape[1:])])
    lv = _level_table(member_levels)[:, 1:].reshape(-1)

    def x_index(gi, i, lv_ref):
        return (gi, i) + (0,) * (nd - 1)

    block = pl.BlockSpec((1, row_tile) + tuple(pads[1:]), x_index)
    out = _pallas_call(
        functools.partial(_batched_tail_kernel, extents=tuple(shape[1:]),
                          inverse=inverse),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(g, rpad // row_tile),
            in_specs=[block], out_specs=block),
        out_shape=jax.ShapeDtypeStruct((g, rpad) + tuple(pads[1:]), x.dtype),
        interpret=interpret,
    )(lv, xp)
    return out[(slice(None),) + tuple(slice(0, s) for s in shape)]


def _batched_axis0_kernel(lv_ref, x_ref, o_ref, *, n_max: int,
                          inverse: bool):
    """Axis-0 transform of a (1, Npad, T) block."""
    o_ref[0] = _axis_pass(x_ref[0], 0, _real_nodes(lv_ref[pl.program_id(0)]),
                          n_max, inverse)


def hier_axis0_batched_pallas(x: jnp.ndarray, levels0, *,
                              inverse: bool = False, lane_tile: int = 512,
                              interpret: bool | None = None) -> jnp.ndarray:
    """(De)hierarchize grid axis 0 of a (G, N, B) bucket.

    ``levels0[g]`` is member g's level along the transformed axis (a
    sequence or a possibly traced/sharded int array)."""
    if interpret is None:
        interpret = interpret_default()
    g, n, b = x.shape
    npad = _round_up(n, _SUBLANE)
    itemsize = jnp.dtype(x.dtype).itemsize
    fit = max(_LANE, _AXIS0_BLOCK_BYTES // (npad * itemsize) // _LANE * _LANE)
    lane_tile = min(lane_tile, fit, _round_up(b, _LANE))
    bpad = _round_up(b, lane_tile)
    xp = jnp.pad(x, ((0, 0), (0, npad - n), (0, bpad - b)))
    lv = (levels0.astype(jnp.int32) if isinstance(levels0, jax.Array)
          else np.asarray(levels0, np.int32)).reshape(g)
    block = pl.BlockSpec((1, npad, lane_tile), lambda gi, i, lv_ref: (gi, 0, i))
    out = _pallas_call(
        functools.partial(_batched_axis0_kernel, n_max=n, inverse=inverse),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(g, bpad // lane_tile),
            in_specs=[block], out_specs=block),
        out_shape=jax.ShapeDtypeStruct((g, npad, bpad), x.dtype),
        interpret=interpret,
    )(lv, xp)
    return out[:, :n, :b]


def hierarchize_batched_jnp(x: jnp.ndarray, member_levels, *,
                            inverse: bool = False) -> jnp.ndarray:
    """Batched (de)hierarchization as per-axis stacked dispatches: two
    static neighbour gathers + the shared masked update per axis.

    No tile padding at all — the path of choice for high-d grids with
    tiny axis extents (a 3^10 grid would pad to 8^9 x 128 under the TPU
    sublane/lane tiling, a ~36000x blowup) and the interpret-mode oracle
    for the Pallas kernels.  Forward results are BITWISE equal to the
    Pallas path (same neighbours, same masks, same ``_hier3``), so method
    choice never changes results.  ``member_levels`` as in
    ``hier_tail_batched_pallas``."""
    lv = _level_table(member_levels)
    d = x.ndim - 1
    # the Pallas path's axis order (tail axes, then axis 0): per-axis
    # rounding then happens in the same sequence on both paths
    for k in [*range(1, d), 0]:
        _count("einsum")
        ext = x.shape[k + 1]
        bshape = [1] * (d + 1)
        bshape[0], bshape[k + 1] = x.shape[0], ext
        j = np.arange(1, ext + 1, dtype=np.int32)
        s, lm, rm = _pred_masks(j[None, :], _real_nodes(lv[:, k])[:, None])
        lm, rm = lm.reshape(bshape), rm.reshape(bshape)
        # level-independent neighbour positions; out-of-range ones are
        # masked, so any in-range stand-in (self) will do
        sj = j & -j
        left = np.where(j - sj >= 1, j - sj, j) - 1
        right = np.where(j + sj <= ext, j + sj, j) - 1
        if not inverse:
            x = _hier3(x, jnp.take(x, left, axis=k + 1),
                       jnp.take(x, right, axis=k + 1), lm, rm)
            continue
        cls = sj.reshape([ext if i == k + 1 else 1 for i in range(d + 1)])
        u = x
        for stride in reversed(list(_strides(ext))):
            upd = _dehier3(x, jnp.take(u, left, axis=k + 1),
                           jnp.take(u, right, axis=k + 1), lm, rm)
            u = jnp.where(cls == stride, upd, u)
        x = u
    return x


def tile_volume(shape: Sequence[int]) -> int:
    """Padded-tile element count of one grid under the TPU sublane/lane
    tiling — the volume the batched Pallas kernels actually move through
    HBM (the executor's merge cost model prices super-buckets with it)."""
    pads = [_round_up(s, _SUBLANE if i < len(shape) - 1 else _LANE)
            for i, s in enumerate(shape)]
    return int(np.prod(pads, dtype=np.int64))


def pad_blowup(shape: Sequence[int]) -> float:
    """Padded-tile volume over true volume for the batched Pallas path."""
    return float(tile_volume(shape)) / max(1.0, float(np.prod(shape)))


_PALLAS_MAX_BLOWUP = 8.0


def batched_method(shape: Sequence[int]) -> str:
    """The ``method="auto"`` rule of ``hierarchize_batched``, exposed so the
    executor's cost model and launch accounting price buckets the same way
    the kernels will actually run them."""
    return ("jnp" if pad_blowup(shape) > _PALLAS_MAX_BLOWUP
            or max(shape) > 2047 else "pallas")


def hierarchize_batched(x: jnp.ndarray, member_levels, *,
                        inverse: bool = False,
                        interpret: bool | None = None,
                        method: str = "auto") -> jnp.ndarray:
    """Full d-dim (de)hierarchization of a (G, *bucket_shape) bucket.

    ``member_levels`` is the ``(G, d)`` member level table: level vectors
    in bucket axis order, or a (possibly traced/sharded) int32 array as
    ``member_level_array`` builds it.  Every member's blocks are computed
    independently of the rest of the batch, so any G-slice of (stack,
    table) yields the same per-member bits as the full stack — what the
    2-D member-sharded ingest relies on.

    ``method="pallas"``: tail axes fused while tiling axis 1, then axis 1
    while tiling the lanes — 2 HBM round trips, ONE kernel launch pair per
    bucket.  ``"jnp"``: stacked per-axis dispatches, no tile padding
    (bitwise equal to the pallas path forward).  ``"auto"`` picks pallas
    unless sublane/lane padding would inflate the block volume by more
    than ~8x (high-d tiny-extent grids); see ``batched_method``."""
    if method == "auto":
        method = batched_method(x.shape[1:])
    if method == "jnp":
        return hierarchize_batched_jnp(x, member_levels, inverse=inverse)
    if method != "pallas":
        raise ValueError(f"unknown method {method!r}")
    lv = _level_table(member_levels)
    if x.ndim == 2:
        out = hier_axis0_batched_pallas(x[..., None], lv[:, 0],
                                        inverse=inverse, interpret=interpret)
        return out[..., 0]
    y = hier_tail_batched_pallas(x, lv, inverse=inverse, interpret=interpret)
    g = y.shape[0]
    shape = y.shape[1:]
    flat = y.reshape(g, shape[0], -1)
    flat = hier_axis0_batched_pallas(flat, lv[:, 0], inverse=inverse,
                                     interpret=interpret)
    return flat.reshape((g,) + shape)


def hier_flops(shape: Sequence[int], g: int = 1) -> int:
    """Forward-hierarchization flop count of a ``(g, *shape)`` bucket
    stack: the 3-term update does 4 flops per point per axis (two
    halvings, two subtracts), and every axis sweeps every point once.
    The 2-D sharded ingest's per-device accounting is priced with this
    (``repro.core.executor.plan_ingest_stats``)."""
    return 4 * g * len(shape) * int(np.prod(shape, dtype=np.int64))


def dehierarchize_batched(a: jnp.ndarray, member_levels, *,
                          interpret: bool | None = None,
                          method: str = "auto") -> jnp.ndarray:
    return hierarchize_batched(a, member_levels, inverse=True,
                               interpret=interpret, method=method)


# ---------------------------------------------------------------------------
# Single grids through the batched kernels (G = 1)
# ---------------------------------------------------------------------------

def _single(x, inverse, interpret):
    levels = [tuple(_level_of(s) for s in x.shape)]
    if x.ndim == 1:
        return hier_axis0_batched_pallas(x[None, :, None], [levels[0][0]],
                                         inverse=inverse,
                                         interpret=interpret)[0, :, 0]
    return hierarchize_batched(x[None], levels, inverse=inverse,
                               interpret=interpret, method="pallas")[0]


def hier_fused_tail_pallas(x: jnp.ndarray, *, inverse: bool = False,
                           row_tile: int | None = None,
                           vmem_budget_bytes: int = 4 * 1024 * 1024,
                           interpret: bool | None = None) -> jnp.ndarray:
    """(De)hierarchize axes 1..d-1 of one grid in ONE pass, tiling over
    axis 0 (the batched tail kernel at G = 1)."""
    if x.ndim < 2:
        raise ValueError("need >= 2 dims; use apply_axis_matmul_pallas for 1-D")
    levels = [tuple(_level_of(s) for s in x.shape)]
    return hier_tail_batched_pallas(x[None], levels, inverse=inverse,
                                    row_tile=row_tile,
                                    vmem_budget_bytes=vmem_budget_bytes,
                                    interpret=interpret)[0]


def hierarchize_nd_fused(x: jnp.ndarray, *, interpret: bool | None = None) -> jnp.ndarray:
    """Full d-dim hierarchization in 2 HBM round trips (d>=2), 1 if d==1."""
    return _single(x, False, interpret)


def dehierarchize_nd_fused(a: jnp.ndarray, *, interpret: bool | None = None) -> jnp.ndarray:
    return _single(a, True, interpret)
