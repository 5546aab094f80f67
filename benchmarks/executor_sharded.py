"""Memory scaling of the slab-sharded distributed CT gather.

The slab-sharded gather (``ct_transform_sharded``) replicates only the
COMPACT surpluses (the scheme's point count) and scatter-adds into a
``ceil(fine_shape[0] / n) * row_size`` slab per device — embedded memory
scales with ``1 / n_groups``.

For each (scheme, n_groups) this benchmark

  * asserts the sharded gather matches single-device ``ct_transform``
    (fp64 here; the multidevice test tier covers fp32 at 1e-6),
  * records the PER-DEVICE embedded-buffer bytes — derived from the plan
    (the slab buffer is ``slab_size + 1`` elements) — beside the one
    device's full fine buffer and, when XLA exposes it, the compiled
    peak temp bytes (``memory_analysis``),
  * times the gather end to end on the fake-device mesh (8 host CPU
    devices; wall time on one physical CPU is a smoke signal, the memory
    accounting is the point).

``--mesh-2d`` adds the fully distributed section: the 2-D
(member x slab) mesh ingest (``ct_transform_sharded(member_axis=...)``),
where the HIERARCHIZATION itself is compute-sharded — each device
transforms only its ``ceil(G_b / n_groups)`` member shard of every
compact stack and ships surpluses to slab owners.  Those rows carry the
plan-derived PER-DEVICE ingest FLOPs and bytes (``plan_ingest_stats``);
CI asserts both shrink strictly as the slab axis grows 1 -> 2 -> 4 (no
device ever materializes the full compact surplus stack).

Emits ``BENCH_executor_sharded.json`` (``--json-out`` overrides, empty
string disables).

  PYTHONPATH=src python benchmarks/executor_sharded.py [--mesh-2d]
"""

from __future__ import annotations

import argparse
import json
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = \
        f"{_flags} --xla_force_host_platform_device_count=8".strip()

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

from common import peak_temp_bytes, time_call  # noqa: E402

from jax.sharding import AxisType  # noqa: E402
from repro.core.distributed import ct_transform_sharded  # noqa: E402
from repro.core.executor import (build_plan, ct_transform,  # noqa: E402
                                 plan_ingest_stats, shard_plan)
from repro.core.levels import (CombinationScheme, grid_shape,  # noqa: E402
                               scheme_total_points)

SCHEMES = [(2, 7), (3, 5), (4, 4)]
GROUPS = [1, 2, 4, 8]
#: 2-D section configs: (members, slabs).  The (1, s) series over
#: s = 1, 2, 4 is the one CI asserts strict per-device scaling on.
MESH2D = [(1, 1), (1, 2), (1, 4), (2, 2), (2, 4)]
DTYPE = np.float64


def _mesh(n):
    return jax.make_mesh((n,), ("slab",), devices=np.array(jax.devices()[:n]),
                         axis_types=(AxisType.Auto,))


def _mesh2d(m, s):
    return jax.make_mesh((m, s), ("member", "slab"),
                         devices=np.array(jax.devices()[:m * s]),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--mesh-2d", action="store_true",
                    help="also run the 2-D (member x slab) compute-"
                         "sharded ingest section")
    ap.add_argument("--json-out", default="BENCH_executor_sharded.json",
                    help="machine-readable results path ('' disables)")
    args = ap.parse_args(argv)

    itemsize = np.dtype(DTYPE).itemsize
    rows = []
    print(f"{'scheme':>8} {'groups':>6} {'fine_MB':>8} "
          f"{'slab_dev_MB':>12} {'mem_ratio':>9} {'slab_ms':>9}")
    for dim, level in SCHEMES:
        scheme = CombinationScheme(dim, level)
        plan = build_plan(scheme)
        g = plan.num_grids
        rng = np.random.default_rng(dim * 100 + level)
        grids = {ell: jnp.asarray(rng.standard_normal(grid_shape(ell)),
                                  DTYPE)
                 for ell, _ in scheme.grids}
        want = np.asarray(ct_transform(grids, scheme))

        for n in GROUPS:
            mesh = _mesh(n)
            splan = shard_plan(plan, n)
            f_slab = jax.jit(lambda gr, m=mesh, sp=splan:
                             ct_transform_sharded(gr, scheme, m, "slab",
                                                  plan=sp))
            np.testing.assert_allclose(np.asarray(f_slab(grids)), want,
                                       rtol=1e-12, atol=1e-12)

            # per-device EMBEDDED buffer bytes: the slab scatter target is
            # slab_size + 1 elements, against the one device's full fine
            # buffer
            fine_dev = plan.fine_size * itemsize
            slab_dev = (splan.slab_size + 1) * itemsize
            # acceptance bound from the GEOMETRY (not the measured buffer):
            # a perfect 1/n split of the leading axis plus at most one
            # ragged fine row of overhang plus the dump slot
            max_elems = ((plan.fine_shape[0] + n - 1) / n * splan.row_size
                         + 1)
            assert slab_dev <= max_elems * itemsize + 1e-9, \
                (slab_dev, max_elems * itemsize)
            slack = max_elems * n / plan.fine_size - 1

            t_slab = time_call(f_slab, grids, reps=args.reps)
            peak_slab = peak_temp_bytes(f_slab, grids)

            print(f"{f'd={dim} n={level}':>8} {n:>6} "
                  f"{fine_dev / 2**20:>8.2f} {slab_dev / 2**20:>12.3f} "
                  f"{fine_dev / slab_dev:>8.1f}x {t_slab * 1e3:>9.2f}")
            rows.append({
                "mode": "1d",
                "dim": dim, "level": level, "grids": g,
                "points": scheme_total_points(scheme),
                "fine_size": plan.fine_size, "n_groups": n,
                "slab_rows": splan.slab_rows, "slab_size": splan.slab_size,
                "dtype_bytes": itemsize,
                "fine_bytes": fine_dev,
                "sharded_per_device_embedded_bytes": slab_dev,
                "embedded_bytes_ratio": fine_dev / slab_dev,
                "ragged_slack": slack,
                "compiled_peak_temp_bytes_sharded": peak_slab,
                "sharded_s": t_slab,
            })

    if args.mesh_2d:
        print(f"\n{'scheme':>8} {'mesh':>8} {'groups':>6} "
              f"{'dev_GFLOP':>10} {'dev_MB':>8} {'stack_MB':>9} "
              f"{'ship_MB':>8} {'t_ms':>9}")
        for dim, level in SCHEMES:
            scheme = CombinationScheme(dim, level)
            plan = build_plan(scheme)
            rng = np.random.default_rng(dim * 100 + level)
            grids = {ell: jnp.asarray(rng.standard_normal(grid_shape(ell)),
                                      DTYPE)
                     for ell, _ in scheme.grids}
            want = np.asarray(ct_transform(grids, scheme))
            for m, s in MESH2D:
                mesh = _mesh2d(m, s)
                splan = shard_plan(plan, s, n_groups=m * s)
                f_2d = jax.jit(lambda gr, ms=mesh, sp=splan:
                               ct_transform_sharded(
                                   gr, scheme, ms, "slab",
                                   member_axis="member", plan=sp))
                got = np.asarray(f_2d(grids))
                # the tentpole's acceptance bar: BIT-identical to the
                # single-device transform, not merely close
                np.testing.assert_array_equal(got, want)
                st = plan_ingest_stats(splan,
                                       dtype_bytes=np.dtype(DTYPE).itemsize)
                t_2d = time_call(f_2d, grids, reps=args.reps)
                print(f"{f'd={dim} n={level}':>8} {f'{m}x{s}':>8} "
                      f"{m * s:>6} {st['ingest_flops'] / 1e9:>10.4f} "
                      f"{st['ingest_bytes'] / 2**20:>8.3f} "
                      f"{st['stack_bytes'] / 2**20:>9.3f} "
                      f"{st['ship_bytes'] / 2**20:>8.3f} "
                      f"{t_2d * 1e3:>9.2f}")
                rows.append({
                    "mode": "2d",
                    "dim": dim, "level": level,
                    "grids": plan.num_grids,
                    "points": scheme_total_points(scheme),
                    "members": m, "slabs": s, "n_groups": m * s,
                    "dtype_bytes": np.dtype(DTYPE).itemsize,
                    "per_device_ingest_flops": st["ingest_flops"],
                    "per_device_ingest_bytes": st["ingest_bytes"],
                    "per_device_stack_bytes": st["stack_bytes"],
                    "per_device_ship_bytes": st["ship_bytes"],
                    "per_device_out_bytes": st["out_bytes"],
                    "sharded_2d_s": t_2d,
                })

    if args.json_out:
        payload = {"bench": "executor_sharded", "reps": args.reps,
                   "backend": jax.default_backend(),
                   "devices": jax.device_count(), "rows": rows}
        with open(args.json_out, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"wrote {args.json_out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
