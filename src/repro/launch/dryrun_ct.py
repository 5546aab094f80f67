import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

"""Dry-run of the paper's OWN workload on the production mesh: the
distributed communication phase (hierarchize every combination grid +
psum-gather into the common fine buffer) lowered and compiled for the
256-chip pod (and 512-chip 2-pod) mesh.

Parallel layout (DESIGN.md Sect. 4, "CT parallelism"):
  * grid axis  — combination grids round-robin over device groups (the
    paper's coarse parallelism); realized here as a stacked, padded
    (G, ...) batch sharded over the FLATTENED mesh.
  * hierarchization is pole-parallel: each grid's transform needs no
    cross-grid communication; the gather step is ONE weighted psum.

  python -m repro.launch.dryrun_ct --config prod_6d --mesh single
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.sparse_grid import CT_CONFIGS, get_ct_config
from repro.core.levels import grid_shape
from repro.launch.analysis import TPU_V5E
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import make_production_mesh


def build_comm_phase(ct, mesh):
    """Lowerable communication phase over ShapeDtypeStruct inputs.

    Inputs: each combination grid's solution represented on the COMMON
    fine grid (G, *fine_shape) — its multilinear interpolant sampled at
    the fine nodes.  Hierarchizing that representation yields surplus 0 at
    every node the coarse grid does not own (the sparse-grid property), so

        hierarchize -> coefficient-weighted reduce over G -> broadcast
        -> dehierarchize

    is exactly the gather/scatter phase, with uniform shapes that stack.
    Distribution: grid axis (the paper's coarse parallelism) over
    ``data``(+``pod``); fine axis 0 over ``model`` (the pole-parallel
    in-grid sharding — only the axis-0 transform communicates, one
    all-gather, cf. core/distributed.py).
    """
    scheme = ct.scheme
    grids = list(scheme.grids)
    g = len(grids)
    fine = tuple(max(ell[i] for ell, _ in grids) for i in range(ct.dim))
    fine_shape = grid_shape(fine)

    from repro.kernels.hierarchize import _padded_operator
    from repro.kernels.ref import dehier_operator_matrix, operator_matrix

    # axis 0 is padded to 2**l so it shards over the model axis (2**l - 1
    # is never divisible by a power of two); the operator is identity on
    # the pad rows
    n0_pad = 1 << fine[0]
    ops = [jnp.asarray(_padded_operator(fine[0], np.float32, npad=n0_pad))]
    ops += [jnp.asarray(operator_matrix(l), jnp.float32) for l in fine[1:]]
    inv_ops = [jnp.asarray(_padded_operator(fine[0], np.float32,
                                            inverse=True, npad=n0_pad))]
    inv_ops += [jnp.asarray(dehier_operator_matrix(l), jnp.float32)
                for l in fine[1:]]
    fine_shape = (n0_pad,) + fine_shape[1:]

    def apply_ops(x, mats):
        # x: (G, *fine_shape); contract each grid axis with its operator
        for ax, h in enumerate(mats):
            x = jnp.moveaxis(jnp.tensordot(h, x, axes=[[1], [ax + 1]]),
                             0, ax + 1)
        return x

    def comm_phase(embedded, coeffs):
        hier = apply_ops(embedded, ops)            # hierarchize (all grids)
        combined = jnp.tensordot(coeffs, hier, axes=[[0], [0]])  # gather
        scattered = jnp.broadcast_to(combined[None], hier.shape)  # scatter
        return apply_ops(scattered, inv_ops)       # dehierarchize

    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    nb = 1
    for a in baxes:
        nb *= mesh.shape[a]
    g_pad = -(-g // nb) * nb                       # pad with coeff-0 grids
    emb_sds = jax.ShapeDtypeStruct((g_pad,) + fine_shape, jnp.float32)
    coef_sds = jax.ShapeDtypeStruct((g_pad,), jnp.float32)
    gspec = P(baxes, "model")                      # grids x pole-parallel
    in_sh = (NamedSharding(mesh, gspec), NamedSharding(mesh, P()))
    out_sh = NamedSharding(mesh, gspec)
    fn = jax.jit(comm_phase, in_shardings=in_sh, out_shardings=out_sh)
    return fn, (emb_sds, coef_sds), g_pad, fine


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="prod_3d", choices=sorted(CT_CONFIGS))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default="results/dryrun_ct")
    args = ap.parse_args(argv)
    kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    ct = get_ct_config(args.config)
    for kind in kinds:
        mesh = make_production_mesh(multi_pod=(kind == "multi"))
        t0 = time.time()
        fn, sds, g, fine = build_comm_phase(ct, mesh)
        with mesh:
            compiled = fn.lower(*sds).compile()
        hc = analyze_hlo(compiled.as_text())
        mem = compiled.memory_analysis()
        rec = {
            "cell": f"ct_{args.config}__{kind}",
            "chips": int(mesh.devices.size),
            "num_grids": g, "fine_levels": list(fine),
            "compile_s": time.time() - t0,
            "flops_per_device": hc.flops,
            "bytes_per_device": hc.traffic_bytes,
            "collective_bytes": {k: int(v)
                                 for k, v in hc.collective_bytes.items()},
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "compute_s": hc.flops / TPU_V5E.peak_flops,
            "memory_s": hc.traffic_bytes / TPU_V5E.hbm_bw,
            "collective_s": sum(hc.collective_bytes.values()) / TPU_V5E.link_bw,
        }
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, rec["cell"] + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
        print(f"[ok] {rec['cell']}: {g} grids, fine={fine}, "
              f"compile={rec['compile_s']:.1f}s "
              f"mem_s={rec['memory_s']:.2e} coll_s={rec['collective_s']:.2e}",
              flush=True)


if __name__ == "__main__":
    main()
