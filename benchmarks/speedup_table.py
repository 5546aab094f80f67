"""Headline speedup table (paper Sect. 5): optimized vs Func baseline.

The paper reports 10-30x for BFS-OverVectorized vs Func and another
2-10x of Func over SGpp.  Matched sizes, wall time only."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import BenchRow, emit_csv, time_call
from repro.core.levels import flops_eq1, flops_exact, grid_shape
from repro.kernels import ref

CASES = [(12,), (14,), (8, 8), (5, 5, 5)]


def run(reps: int = 3):
    rows = []
    opt = jax.jit(ref.hierarchize_nd_ref)
    gather = jax.jit(lambda x: _gather_nd(x))
    for lv in CASES:
        x = jnp.asarray(np.random.default_rng(sum(lv)).standard_normal(
            grid_shape(lv)))
        fe1, fex = flops_eq1(lv), flops_exact(lv)
        nbytes = x.size * x.dtype.itemsize
        t_func = time_call(lambda a: _func_nd(np.asarray(a)), x,
                           reps=1, warmup=0)
        t_opt = time_call(opt, x, reps=reps, warmup=1)
        t_gather = time_call(gather, x, reps=reps, warmup=1)
        rows.append(BenchRow("speedup", f"l={lv}", "func", nbytes, t_func,
                             fe1, fex))
        rows.append(BenchRow("speedup", f"l={lv}", "ref", nbytes, t_opt,
                             fe1, fex))
        rows.append(BenchRow("speedup", f"l={lv}", "gather", nbytes,
                             t_gather, fe1, fex))
        print(f"# {lv}: speedup ref vs func = {t_func / t_opt:7.1f}x, "
              f"gather vs func = {t_func / t_gather:7.1f}x")
    return rows


def _func_nd(x):
    for axis in range(x.ndim):
        x = ref.hierarchize_1d_bruteforce(x, axis)
    return x


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
