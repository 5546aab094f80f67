"""Paper Fig. 8: 10-dimensional anisotropic grids.

First dimension refined (l1 sweep), the other nine fixed at level ~1.6
(paper: 3 points per axis -> level 2 every other axis to keep sizes sane:
we use (l1, 2, 2, 2, 1, 2, 1, 2, 1, 2) ~ the paper's 3-point axes).
Includes the reduced-op ablation (paper: no runtime change)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import BenchRow, emit_csv, time_call
from repro.core.levels import flops_eq1, flops_exact, grid_shape
from repro.kernels import ref

TAIL = (2, 2, 2, 1, 2, 1, 2, 1, 2)   # nine more dims, 3 or 1 points each


def run(l1_values=(6, 8, 10, 12, 14), reps: int = 3):
    rows = []
    methods = {
        "ref": jax.jit(ref.hierarchize_nd_ref),
        "ref_unreduced": jax.jit(
            lambda x: ref.hierarchize_nd_ref(x, reduced_op=False)),
        "gather": jax.jit(lambda x: _gather_nd(x)),
    }
    for l1 in l1_values:
        lv = (l1,) + TAIL
        x = jnp.asarray(np.random.default_rng(l1).standard_normal(
            grid_shape(lv)))
        fe1, fex = flops_eq1(lv), flops_exact(lv)
        for name, fn in methods.items():
            secs = time_call(fn, x, reps=reps, warmup=1)
            rows.append(BenchRow("fig8_10d", f"l1={l1}", name,
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
