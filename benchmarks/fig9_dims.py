"""Paper Fig. 9: the best implementation across dimensions 1..5 at roughly
matched data-set sizes — performance should be similar for d in 2..5 and
lower for d=1 (fewer poles to batch over)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import BenchRow, emit_csv, time_call
from repro.core.levels import flops_eq1, flops_exact, grid_shape
from repro.kernels import ref

# ~matched sizes (2^20-ish points)
CASES = {
    1: (20,),
    2: (10, 10),
    3: (7, 7, 6),
    4: (5, 5, 5, 5),
    5: (4, 4, 4, 4, 4),
}


def run(reps: int = 3):
    rows = []
    best = jax.jit(ref.hierarchize_nd_ref)
    for d, lv in CASES.items():
        x = jnp.asarray(np.random.default_rng(d).standard_normal(
            grid_shape(lv)))
        secs = time_call(best, x, reps=reps, warmup=1)
        rows.append(BenchRow("fig9_dims", f"d={d}", "ref",
                             x.size * x.dtype.itemsize, secs,
                             flops_eq1(lv), flops_exact(lv)))
    return rows


def main():
    print(emit_csv(run()))


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
