"""Quickstart: the paper's pipeline behind the unified front door.

1. Build the combination scheme for a 2-D sparse grid.
2. Sample functions on every combination grid (the "solver" output).
3. ``ExecSpec`` — ONE config object for the whole execution stack —
   drives the batched gather (``ct_transform``): hierarchize every grid
   in bucket-batched Pallas kernels + one static-index scatter-add.
4. ``CTEngine`` — serve SEVERAL surrogates multi-tenant: equal plan
   shape-signatures share one compiled ingest executable, and queries
   submitted together coalesce into one batched eval dispatch.
5. Scatter back (``ct_scatter``) for the iterated-CT round trip.
6. Bucket merging is one more ``ExecSpec`` field: fewer kernel
   launches, the same surplus bit for bit.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

from repro.core.engine import CTEngine, ExecSpec
from repro.core.executor import MergeConfig, ct_scatter, ct_transform
from repro.core.interpolation import sample_function
from repro.core.levels import CombinationScheme, grid_shape


def f(x, y):
    return jnp.sin(jnp.pi * x) * y * (1 - y)


def g(x, y):
    return x * (1 - x) * jnp.sin(jnp.pi * y)


def main():
    scheme = CombinationScheme(dim=2, level=5)
    print(f"sparse grid level {scheme.level}: {len(scheme.grids)} combination "
          f"grids, {scheme.total_points()} grid points total "
          f"(vs {(2 ** 5 - 1) ** 2} for the full grid)")

    # --- compute phase (black-box solver; here: sampling f and g) ---
    nodal_f = {ell: sample_function(f, ell) for ell, _ in scheme.grids}
    nodal_g = {ell: sample_function(g, ell) for ell, _ in scheme.grids}

    # --- one ExecSpec drives every execution knob (all defaults here:
    #     no merging, single device, backend-default interpret mode) ---
    spec = ExecSpec()
    full = ct_transform(nodal_f, scheme, spec=spec)
    print(f"combined surplus buffer: {full.shape}")

    # --- multi-tenant serving: two surrogates, ONE compiled ingest ---
    engine = CTEngine(spec=spec)
    engine.register("f", scheme, nodal_f)
    engine.register("g", scheme, nodal_g)   # same shape-signature: cache hit
    cache = engine.stats()["ingest_cache"]
    print(f"ingest executables: {cache['misses']} compiled, "
          f"{cache['hits']} shared (2 tenants)")
    assert cache["misses"] == 1 and cache["hits"] == 1

    # --- continuous batching: both queries in ONE batched dispatch ---
    pts = np.random.default_rng(0).random((512, 2))
    fut_f = engine.submit_query("f", pts)
    fut_g = engine.submit_query("g", pts)
    engine.flush()
    err_f = float(np.max(np.abs(fut_f.result()
                                - np.asarray(f(pts[:, 0], pts[:, 1])))))
    err_g = float(np.max(np.abs(fut_g.result()
                                - np.asarray(g(pts[:, 0], pts[:, 1])))))
    ev = engine.stats()["eval"]
    print(f"max interpolation error at 512 random points: "
          f"f {err_f:.2e}, g {err_g:.2e} "
          f"({ev['queries']} queries in {ev['batches']} batched dispatch)")
    assert err_f < 5e-3 and err_g < 5e-3 and ev["batches"] == 1

    # --- scatter back (iterated-CT round trip): the combined interpolant
    #     reproduces consistent component-grid values at their own nodes ---
    back = ct_scatter(engine.surplus("f"), scheme, spec=spec)
    drift = max(float(jnp.max(jnp.abs(back[ell] - nodal_f[ell])))
                for ell, _ in scheme.grids)
    print(f"round-trip drift on consistent grids: {drift:.2e}")

    # --- bucket merging: one more ExecSpec field, same surplus ---
    merged = ct_transform(nodal_f, scheme,
                          spec=ExecSpec(merge=MergeConfig()))
    assert np.array_equal(np.asarray(merged), np.asarray(full))
    print("ExecSpec(merge=MergeConfig()): same result bit-for-bit")
    print("OK")


if __name__ == "__main__":
    main()
