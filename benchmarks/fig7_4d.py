"""Paper Fig. 7: hierarchizing 4-D grids (isotropic sweep).

Adds the fused 2-round-trip schedule (beyond-paper) against the d-pass
reference: on a bandwidth-bound transform the pass count is the first-order
cost, visible even on the CPU container.
"""

from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp

from benchmarks.common import BenchRow, emit_csv, time_call
from repro.core.levels import flops_eq1, flops_exact, grid_shape
from repro.kernels import ref
from repro.kernels.hierarchize import hierarchize_nd_fused


def _fused_jnp(x):
    """The fused schedule expressed in pure jnp (tensordot per tail axis on
    a VMEM-sized block is emulated by whole-array tensordots on CPU)."""
    d = x.ndim
    for axis in range(1, d):
        h = jnp.asarray(ref.operator_matrix(int(np.log2(x.shape[axis] + 1))),
                        x.dtype)
        x = jnp.moveaxis(jnp.tensordot(h, x, axes=[[1], [axis]]), 0, axis)
    h0 = jnp.asarray(ref.operator_matrix(int(np.log2(x.shape[0] + 1))),
                     x.dtype)
    return jnp.tensordot(h0, x, axes=[[1], [0]])


def run(levels_list=((4, 4, 4, 4), (5, 5, 5, 5), (6, 6, 6, 6),
                     (7, 6, 6, 6)), reps: int = 3):
    rows = []
    methods = {
        "ref": jax.jit(ref.hierarchize_nd_ref),
        "gather": jax.jit(lambda x: _gather_nd(x)),
        "fused_matmul": jax.jit(_fused_jnp),
    }
    for lv in levels_list:
        x = jnp.asarray(np.random.default_rng(sum(lv)).standard_normal(
            grid_shape(lv)))
        fe1, fex = flops_eq1(lv), flops_exact(lv)
        for name, fn in methods.items():
            secs = time_call(fn, x, reps=reps, warmup=1)
            rows.append(BenchRow("fig7_4d", f"l={lv}", name,
                                 x.size * x.dtype.itemsize, secs, fe1, fex))
    return rows


def _gather_nd(x):
    for axis in range(x.ndim):
        x = ref.hierarchize_1d_gather(x, axis)
    return x


def main():
    print(emit_csv(run()))


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
