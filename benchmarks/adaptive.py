"""Dimension-adaptive refinement benchmark: points-to-error + plan reuse.

Two measurements on the anisotropic reference targets
(``repro.configs.sparse_grid.CT_ADAPTIVE_CONFIGS``):

  * **points-to-error** — combination-grid points the regular scheme needs
    for a given max-norm interpolation error vs the dimension-adaptive
    scheme's trajectory (the headline: >= 3x fewer at the acceptance bar);
  * **plan-update cost** — wall time of the incremental ``extend_plan``
    against a from-scratch ``build_plan`` for each expansion once the fine
    grid stabilizes, plus how many buckets were reused by identity.

Emits machine-readable ``BENCH_adaptive.json`` (``--json-out`` overrides,
empty string disables).

  PYTHONPATH=src python benchmarks/adaptive.py
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

from repro.configs.sparse_grid import CT_ADAPTIVE_CONFIGS  # noqa: E402
from repro.core.adaptive import (AdaptiveConfig, AdaptiveDriver,  # noqa: E402
                                 interpolation_error,
                                 make_anisotropic_target, nodal_sampler)
from repro.core.executor import build_plan, ct_transform  # noqa: E402
from repro.core.levels import CombinationScheme  # noqa: E402


def run_case(cfg, reps: int):
    f = make_anisotropic_target(cfg.dim, cfg.decay)
    sample = nodal_sampler(f)
    pts = jnp.asarray(np.random.default_rng(cfg.eval_seed)
                      .random((cfg.eval_points, cfg.dim)))

    reg = CombinationScheme(cfg.dim, cfg.baseline_level)
    nodal = {ell: sample(ell) for ell, _ in reg.grids}
    err_reg = interpolation_error(ct_transform(nodal, reg), f, pts)

    drv = AdaptiveDriver(nodal_sampler(f), dim=cfg.dim,
                         config=AdaptiveConfig(max_points=cfg.max_points,
                                               max_level=cfg.max_level))
    traj, matched = [], None
    while True:
        err = interpolation_error(drv.surplus, f, pts)
        traj.append({"iteration": len(drv.history),
                     "points": drv.scheme.total_points(),
                     "solved_points": drv.solved_points(),
                     "grids": len(drv.scheme.grids),
                     "max_err": err})
        if matched is None and err <= err_reg:
            matched = traj[-1]
        if matched is not None or drv.step() is None:
            break

    # plan-update cost on a stable fine grid: replay the final expansion
    from repro.core.executor import clear_plan_cache, extend_plan
    plan_t = {}
    if len(drv.scheme.grids) > 1:
        prev = drv.scheme.without_levels([drv.history[-1].added[0]]) \
            if drv.history and drv.history[-1].added else None
    else:
        prev = None
    if prev is not None:
        base = build_plan(prev, full_levels=drv.plan.full_levels)
        t0 = time.perf_counter()
        for _ in range(reps):
            inc = extend_plan(base, drv.scheme,
                              full_levels=drv.plan.full_levels)
        plan_t["extend_s"] = (time.perf_counter() - t0) / reps
        t0 = time.perf_counter()
        for _ in range(reps):
            clear_plan_cache()
            scratch = build_plan(drv.scheme,
                                 full_levels=drv.plan.full_levels)
        plan_t["scratch_s"] = (time.perf_counter() - t0) / reps
        plan_t["buckets"] = len(inc.buckets)
        plan_t["buckets_reused"] = sum(
            1 for b in inc.buckets if any(b is ob for ob in base.buckets))
        assert all(np.array_equal(a.index, b.index) and
                   np.array_equal(a.coeffs, b.coeffs)
                   for a, b in zip(inc.buckets, scratch.buckets))

    return {"case": cfg.name, "dim": cfg.dim, "decay": cfg.decay,
            "regular_level": cfg.baseline_level,
            "regular_points": reg.total_points(),
            "regular_grids": len(reg.grids), "regular_max_err": err_reg,
            "trajectory": traj, "matched": matched,
            "point_ratio": (reg.total_points() / matched["points"]
                            if matched else None),
            "stop_reason": drv.stop_reason, "plan_update": plan_t}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cases", nargs="*",
                    default=["aniso_6d_smoke", "aniso_6d"])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--json-out", default="BENCH_adaptive.json",
                    help="machine-readable results path ('' disables)")
    args = ap.parse_args(argv)

    results = []
    print(f"{'case':>16} {'reg pts':>8} {'reg err':>10} {'adapt pts':>10} "
          f"{'ratio':>7} {'extend_ms':>10} {'scratch_ms':>11} {'reused':>7}")
    for name in args.cases:
        cfg = CT_ADAPTIVE_CONFIGS[name]
        r = run_case(cfg, args.reps)
        results.append(r)
        m, p = r["matched"], r["plan_update"]
        ratio = f"{r['point_ratio']:.2f}x" if r["point_ratio"] else "-"
        print(f"{name:>16} {r['regular_points']:>8} "
              f"{r['regular_max_err']:>10.3e} "
              f"{(m['points'] if m else -1):>10} {ratio:>7} "
              f"{p.get('extend_s', 0) * 1e3:>10.3f} "
              f"{p.get('scratch_s', 0) * 1e3:>11.3f} "
              f"{p.get('buckets_reused', 0):>3}/{p.get('buckets', 0):<3}")
    if args.json_out:
        payload = {"bench": "adaptive", "backend": jax.default_backend(),
                   "results": results}
        with open(args.json_out, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"wrote {args.json_out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
