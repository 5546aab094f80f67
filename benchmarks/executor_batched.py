"""Dict-loop vs batched-executor CT communication phase, plus the
bucket-merge accounting.

The repo's first multi-grid throughput number: for each scheme, time

  * ``dict``    — the oracle path: one ``hierarchize(..., "ref")`` dispatch
    per component grid + ``combine_full``'s per-grid embed loop, the whole
    thing wrapped in ONE jit (so the comparison is dispatch structure, not
    python overhead);
  * ``batched`` — ``repro.core.executor.ct_transform``: bucket-batched
    hierarchization + static-index-plan scatter-add, also one jit.

Both paths produce the sparse-grid surplus on the common fine grid; the
benchmark asserts they agree to 1e-12 before timing.

The second table prices bucket merging on the batched path itself
(``build_plan(..., merge=MergeConfig())``): launch counts (plan-derived
AND the dispatches actually traced,
``repro.kernels.hierarchize.count_launches``) and gather-phase HBM bytes
with the cost-model partition against the exact-canonical one, plus the
compiled peak temp bytes when XLA reports them (``memory_analysis``).
The merged path is asserted against the unmerged one before timing
(eager execution is bit-identical — pinned by
``tests/test_merge_plan.py``; compiled graphs are held to 1e-12 since XLA
may FMA a scatter combiner, and the observed bitwise fraction is
recorded).  Off the TPU the Pallas kernels run in interpret mode, so
times and peaks here price the CPU, not the chip: the plan-derived
bytes/launches are the tracked metrics.

Emits machine-readable ``BENCH_executor_batched.json`` and
``BENCH_bucket_merge.json`` next to the tables (``--json-out`` /
``--merge-json-out`` override, empty string disables) so the perf
trajectory is tracked across PRs.

  PYTHONPATH=src python benchmarks/executor_batched.py
"""

from __future__ import annotations

import argparse
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

from common import peak_temp_bytes, time_call  # noqa: E402

from repro.core import combination as comb  # noqa: E402
from repro.core.executor import (MergeConfig, build_plan,  # noqa: E402
                                 ct_transform, ct_transform_with_plan,
                                 plan_launch_stats)
from repro.core.levels import (CombinationScheme, GeneralScheme,  # noqa: E402
                               grid_shape)
from repro.kernels.hierarchize import count_launches  # noqa: E402
from repro.kernels.ops import hierarchize  # noqa: E402

# (dim, sparse-grid level): d=10 stays at level 2 — the common fine grid
# at (d=10, n=3) is 7^10 = 282M points, beyond any embedded representation
SCHEMES = [(2, 5), (2, 7), (4, 4), (4, 5), (10, 2)]

# merge table: the d=10 wide diagonal is the launch-bound shape the
# merge planner exists for; the near-square d=2 set keeps most of its
# volume on the Pallas path
MERGE_SCHEMES = [
    ("d=10 n=2", CombinationScheme(10, 2)),
    ("d=4 n=4", CombinationScheme(4, 4)),
    ("d=2 n=7", CombinationScheme(2, 7)),
    ("sq d=2", GeneralScheme.from_levels([(8, 6), (7, 7), (6, 8)],
                                         close=True)),
]


def dict_path(scheme):
    def run(nodal_grids):
        hier = {ell: hierarchize(u, "ref") for ell, u in nodal_grids.items()}
        full, _ = comb.combine_full(hier, scheme)
        return full
    return jax.jit(run)


def batched_path(scheme):
    return jax.jit(functools.partial(ct_transform, scheme=scheme))


def _traced_launches(plan, grids):
    """Kernel dispatches one compiled gather will issue: counted while
    tracing (pallas_call launches + jnp-path stacked-operator dispatches
    + the plan's standalone XLA scatters)."""
    with count_launches() as counts:
        jax.jit(lambda g: ct_transform_with_plan(g, plan)).lower(grids)
    return (counts["pallas"] + counts["einsum"]
            + plan_launch_stats(plan)["scatter_dispatches"])


def bench_merge(reps, json_out):
    rows = []
    print(f"\n{'scheme':>8} {'grids':>6} {'buckets':>8} {'launches':>13} "
          f"{'stack_KB':>13} {'peak_MB':>13} {'base_ms':>8} {'merged_ms':>10}")
    for case_i, (name, scheme) in enumerate(MERGE_SCHEMES):
        plain = build_plan(scheme)
        merged = build_plan(scheme, merge=MergeConfig())
        rng = np.random.default_rng(1000 + case_i)
        grids = {ell: jnp.asarray(rng.standard_normal(grid_shape(ell)))
                 for ell, _ in scheme.grids}

        f_base = jax.jit(lambda g: ct_transform_with_plan(g, plain))
        f_merged = jax.jit(lambda g: ct_transform_with_plan(g, merged))
        # eager results are bit-identical (pinned by
        # tests/test_merge_plan.py); under jit XLA may fuse a scatter
        # combiner (observed: one FMA'd slot, 1 ulp), so the compiled
        # paths are held to 1e-12 and the bitwise fraction is recorded
        want = np.asarray(f_base(grids))
        got_merged = np.asarray(f_merged(grids))
        np.testing.assert_allclose(got_merged, want, rtol=1e-12, atol=1e-12)
        err = float(np.max(np.abs(got_merged - want)))
        bitwise = bool((got_merged == want).all())

        s_plain = plan_launch_stats(plain)
        s_merged = plan_launch_stats(merged)
        traced_plain = _traced_launches(plain, grids)
        traced_merged = _traced_launches(merged, grids)
        t_base = time_call(f_base, grids, reps=reps)
        t_merged = time_call(f_merged, grids, reps=reps)
        peak_base = peak_temp_bytes(f_base, grids)
        peak_merged = peak_temp_bytes(f_merged, grids)

        fmt_peak = (f"{(peak_base or 0) / 2**20:>6.2f}"
                    f"->{(peak_merged or 0) / 2**20:<6.2f}"
                    if peak_base is not None else f"{'n/a':>13}")
        print(f"{name:>8} {plain.num_grids:>6} "
              f"{len(plain.buckets):>3}->{len(merged.buckets):<4} "
              f"{s_plain['launches']:>6}->{s_merged['launches']:<6} "
              f"{s_plain['stack_bytes'] / 1024:>6.1f}"
              f"->{s_merged['stack_bytes'] / 1024:<6.1f} "
              f"{fmt_peak} {t_base * 1e3:>8.2f} {t_merged * 1e3:>10.2f}")
        rows.append({
            "scheme": name, "grids": plain.num_grids,
            "buckets_unmerged": len(plain.buckets),
            "buckets_merged": len(merged.buckets),
            "launches_unmerged": s_plain["launches"],
            "launches_merged": s_merged["launches"],
            "launches_traced_unmerged": traced_plain,
            "launches_traced_merged": traced_merged,
            "launch_ratio": s_plain["launches"] / s_merged["launches"],
            "stack_bytes_unmerged": s_plain["stack_bytes"],
            "stack_bytes_merged": s_merged["stack_bytes"],
            "transform_bytes_unmerged": s_plain["transform_bytes"],
            "transform_bytes_merged": s_merged["transform_bytes"],
            "compiled_peak_temp_bytes_unmerged": peak_base,
            "compiled_peak_temp_bytes_merged": peak_merged,
            "unmerged_s": t_base, "merged_s": t_merged,
            "max_abs_err": err,
            "bitwise_equal_compiled": bitwise,
        })
    wide = next(r for r in rows if r["scheme"] == "d=10 n=2")
    assert wide["launches_unmerged"] >= 2 * wide["launches_merged"], wide
    if json_out:
        payload = {"bench": "bucket_merge", "reps": reps,
                   "backend": jax.default_backend(), "rows": rows}
        with open(json_out, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"wrote {json_out}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--json-out", default="BENCH_executor_batched.json",
                    help="machine-readable results path ('' disables)")
    ap.add_argument("--merge-json-out", default="BENCH_bucket_merge.json",
                    help="bucket-merge results path ('' disables)")
    ap.add_argument("--skip-dict", action="store_true",
                    help="only run the merge table")
    args = ap.parse_args(argv)
    if args.skip_dict:
        bench_merge(args.reps, args.merge_json_out)
        return

    rows = []
    print(f"{'scheme':>10} {'grids':>6} {'buckets':>8} {'points':>10} "
          f"{'dict_ms':>9} {'batched_ms':>11} {'speedup':>8}")
    for dim, level in SCHEMES:
        scheme = CombinationScheme(dim, level)
        plan = build_plan(scheme)
        rng = np.random.default_rng(dim * 100 + level)
        grids = {ell: jnp.asarray(rng.standard_normal(grid_shape(ell)))
                 for ell, _ in scheme.grids}

        f_dict = dict_path(scheme)
        f_batched = batched_path(scheme)
        err = float(jnp.max(jnp.abs(f_dict(grids) - f_batched(grids))))
        assert err < 1e-12, (dim, level, err)

        t_dict = time_call(f_dict, grids, reps=args.reps)
        t_batched = time_call(f_batched, grids, reps=args.reps)
        print(f"{f'd={dim} n={level}':>10} {plan.num_grids:>6} "
              f"{len(plan.buckets):>8} {scheme.total_points():>10} "
              f"{t_dict * 1e3:>9.2f} {t_batched * 1e3:>11.2f} "
              f"{t_dict / t_batched:>7.2f}x")
        rows.append({"dim": dim, "level": level, "grids": plan.num_grids,
                     "buckets": len(plan.buckets),
                     "points": scheme.total_points(),
                     "max_abs_err": err, "dict_s": t_dict,
                     "batched_s": t_batched,
                     "speedup": t_dict / t_batched})
    if args.json_out:
        payload = {"bench": "executor_batched", "reps": args.reps,
                   "backend": jax.default_backend(), "rows": rows}
        with open(args.json_out, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"wrote {args.json_out}")
    bench_merge(args.reps, args.merge_json_out)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
