"""Paper Fig. 4: hierarchizing a 1-D grid — data layout / navigation study.

Methods (paper name -> this repo):
  SGpp/Func -> ``func``   numpy node-by-node with level-index navigation
  Ind       -> ``ref``    jit'd strided level loop, no level-index vector
  (one-shot)-> ``gather`` jit'd linear-operator gather
  BFS       -> ``bfs``    jit'd level-major layout
  BFS-Rev   -> ``bfs_rev``

The paper's observations to reproduce: Func is slowest (navigation
overhead); Ind wins at moderate sizes; BFS performance stays flat as data
grows; Reverse-BFS is slower than BFS.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import BenchRow, emit_csv, time_call
from repro.core.hierarchize import hierarchize_1d_bfs, to_bfs
from repro.core.levels import flops_eq1, flops_exact
from repro.kernels import ref

FUNC_MAX_LEVEL = 15     # python-loop baseline; larger sizes time out


def _methods():
    h_ref = jax.jit(lambda x: ref.hierarchize_1d_ref(x, axis=0))
    h_gather = jax.jit(lambda x: ref.hierarchize_1d_gather(x, axis=0))
    h_bfs = jax.jit(functools.partial(hierarchize_1d_bfs, axis=0))
    h_bfs_rev = jax.jit(functools.partial(hierarchize_1d_bfs, axis=0,
                                          reverse=True))
    return {
        "func": lambda x: ref.hierarchize_1d_bruteforce(np.asarray(x), 0),
        "ref": h_ref,
        "gather": h_gather,
        "bfs": h_bfs,
        "bfs_rev": h_bfs_rev,
    }


def run(levels=(10, 14, 18, 20, 22), reps: int = 3):
    rows = []
    methods = _methods()
    for level in levels:
        n = (1 << level) - 1
        x = jnp.asarray(np.random.default_rng(level).standard_normal(n))
        xb = to_bfs(x, 0)
        fe1, fex = flops_eq1((level,)), flops_exact((level,))
        for name, fn in methods.items():
            if name == "func" and level > FUNC_MAX_LEVEL:
                continue
            arg = xb if name.startswith("bfs") else x
            secs = time_call(fn, arg, reps=reps, warmup=1)
            rows.append(BenchRow("fig4_1d", f"l={level}", name,
                                 n * x.dtype.itemsize, secs, fe1, fex))
    return rows


def main():
    print(emit_csv(run()))


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
