"""Public, jit-friendly entry points for (de)hierarchization.

``method``:
  * ``"func"``      — numpy brute force (the paper's `Func`/SGpp-like baseline;
                      NOT jit-able, benchmark/oracle use only)
  * ``"ref"``       — jnp unrolled level loop (`Ind` layout analog)
  * ``"gather"``    — one-shot linear-operator gather (jnp)
  * ``"pole"``      — Pallas pole kernel (paper-faithful over-vectorization)
  * ``"matmul"``    — Pallas per-axis MXU operator matmul
  * ``"fused"``     — Pallas batched kernels at G = 1, 2 HBM round trips
                      for any d
  * ``"auto"``      — the executor's bucket rule at G = 1
                      (``hierarchize.batched_method``): the ``"fused"``
                      kernels unless tile padding would blow the grid up,
                      else the padding-free jnp path
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels import hierarchize as hk
from repro.kernels import ref

__all__ = ["hierarchize", "dehierarchize"]


def _axis_to_pole_bundle(x, axis):
    moved = jnp.moveaxis(x, axis, 0)
    return moved, moved.shape


def _per_axis(x, fn):
    for axis in range(x.ndim):
        moved, shape = _axis_to_pole_bundle(x, axis)
        flat = moved.reshape(shape[0], -1)
        flat = fn(flat)
        x = jnp.moveaxis(flat.reshape(shape), 0, axis)
    return x


def _auto(x, inverse, interpret):
    levels = [tuple(hk._level_of(s) for s in x.shape)]
    return hk.hierarchize_batched(x[None], levels, inverse=inverse,
                                  interpret=interpret)[0]


def hierarchize(x: jnp.ndarray, method: str = "auto", *,
                interpret: bool | None = None,
                reduced_op: bool = True) -> jnp.ndarray:
    """d-dimensional nodal -> hierarchical base change."""
    if method == "auto":
        return _auto(x, False, interpret)
    if method == "func":
        out = np.asarray(x)
        for axis in range(out.ndim):
            out = ref.hierarchize_1d_bruteforce(out, axis)
        return jnp.asarray(out, dtype=x.dtype)
    if method == "ref":
        return ref.hierarchize_nd_ref(x, reduced_op=reduced_op)
    if method == "gather":
        for axis in range(x.ndim):
            x = ref.hierarchize_1d_gather(x, axis)
        return x
    if method == "pole":
        return _per_axis(x, lambda f: hk.hier_pole_pallas(
            f, reduced_op=reduced_op, interpret=interpret))
    if method == "matmul":
        return _per_axis(x, lambda f: hk.apply_axis_matmul_pallas(
            f, interpret=interpret))
    if method == "fused":
        return hk.hierarchize_nd_fused(x, interpret=interpret)
    raise ValueError(f"unknown method {method!r}")


def dehierarchize(a: jnp.ndarray, method: str = "auto", *,
                  interpret: bool | None = None) -> jnp.ndarray:
    """d-dimensional hierarchical -> nodal base change (inverse)."""
    if method == "auto":
        return _auto(a, True, interpret)
    if method == "func":
        out = np.asarray(a)
        for axis in range(out.ndim):
            out = ref.dehierarchize_1d_bruteforce(out, axis)
        return jnp.asarray(out, dtype=a.dtype)
    if method == "ref":
        return ref.dehierarchize_nd_ref(a)
    if method == "pole":
        return _per_axis(a, lambda f: hk.dehier_pole_pallas(
            f, interpret=interpret))
    if method == "matmul":
        return _per_axis(a, lambda f: hk.apply_axis_matmul_pallas(
            f, inverse=True, interpret=interpret))
    if method == "fused":
        return hk.dehierarchize_nd_fused(a, interpret=interpret)
    raise ValueError(f"unknown method {method!r}")
