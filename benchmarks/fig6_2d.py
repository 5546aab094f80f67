"""Paper Fig. 5/6: 2-D grids — measured vs calculated performance.

Reproduces the paper's methodological point: dividing by the flops an
implementation EXECUTES (meas_gflops; the unreduced 2-multiply form,
flops_exact) reports higher numbers than dividing by the theoretical
Eq. (1) count (calc_gflops) for exactly the same wall time.  Only the
calculated number ranks implementations by wall time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import BenchRow, emit_csv, time_call
from repro.core.levels import flops_eq1, flops_exact, grid_shape, num_points
from repro.kernels import ref

FUNC_MAX_POINTS = 1 << 15


def _methods():
    return {
        "func": lambda x: ref.hierarchize_1d_bruteforce(
            ref.hierarchize_1d_bruteforce(np.asarray(x), 0), 1),
        "ref": jax.jit(ref.hierarchize_nd_ref),
        "ref_unreduced": jax.jit(
            lambda x: ref.hierarchize_nd_ref(x, reduced_op=False)),
        "gather": jax.jit(lambda x: ref.hierarchize_1d_gather(
            ref.hierarchize_1d_gather(x, 0), 1)),
    }


def run(level_pairs=((6, 6), (8, 8), (10, 10), (11, 11), (12, 10)),
        reps: int = 3):
    rows = []
    methods = _methods()
    for lv in level_pairs:
        x = jnp.asarray(np.random.default_rng(sum(lv)).standard_normal(
            grid_shape(lv)))
        fe1, fex = flops_eq1(lv), flops_exact(lv)
        for name, fn in methods.items():
            if name == "func" and num_points(lv) > FUNC_MAX_POINTS:
                continue
            secs = time_call(fn, x, reps=reps, warmup=1)
            rows.append(BenchRow("fig6_2d", f"l={lv}", name,
                                 x.size * x.dtype.itemsize, secs, fe1, fex))
    return rows


def main():
    print(emit_csv(run()))


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
