"""Smoke run of the served CT path on a TPU: register -> ingest -> query.

    python chip_smoke.py               # one chip: prod_3d, then fig6_2d
    python chip_smoke.py --four-chips  # prod_3d on a 2x2 (member x slab)
                                       # mesh vs. one device, nothing else

Drives ``CTEngine`` through its user entry points (``register``,
``submit_ingest``, ``submit_query``, ``flush``) in f32 with every Pallas
kernel compiled for the chip (``ExecSpec(interpret=False)``), and checks
every answer against the dict-path reference
``core.combination.combined_interpolant_points``.  ``fig6_2d`` also
proves the Pallas kernels ran: launches counted while the ingest traced,
``tpu_custom_call`` in the compiled ingest program, and answers equal to
the same tenant transformed on the jnp path.  Refuses to run (named
error, exit status 1) unless JAX's first device is a TPU; prints
per-phase numbers, then one JSON line ``{"ok": true, "device": ...}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

#: f32 tolerance of every served answer against the dict-path reference,
#: relative to the answers' max magnitude: the two paths round
#: differently (hierarchize + embed + hat-basis contraction over up to
#: 511 nodes per axis vs. 109 weighted multilinear interpolants), each
#: within a few hundred f32 ulps of O(1) values
REF_RTOL = 1e-4
#: Pallas path vs. jnp path (fig6_2d) and 2x2 mesh vs. one device: the
#: same elementwise update on the same values, so bitwise in principle;
#: the bound only admits a differently fused scatter on the chip
SAME_PATH_RTOL = 1e-6
QUERY_BATCHES, QUERY_POINTS = 4, 64


class NoTPUError(RuntimeError):
    """JAX found no TPU: this smoke never falls back to another backend."""


def _device_or_fail(count: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoTPUError(f"chip_smoke needs a TPU; JAX's first device is "
                         f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < count:
        raise NoTPUError(f"chip_smoke needs {count} TPU device(s), found "
                         f"{len(devs)}")
    return devs


def _log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _field(seed: int, dim: int):
    """Seeded smooth field vanishing on the boundary of [0,1]^d (the
    grids carry no boundary nodes, so the interpolant vanishes there)."""
    b = np.random.default_rng(seed).uniform(0.0, 1.0, dim)

    def u(*xs):
        out = 1.0
        for x, bi in zip(xs, b):
            out = out * 4.0 * x * (1.0 - x) * (1.0 + float(bi) * x)
        return out

    return u


def _grids(scheme, seed: int):
    from repro.core.interpolation import sample_function
    sample = jax.jit(sample_function, static_argnums=(0, 1))
    u = _field(seed, scheme.dim)
    return {ell: sample(u, ell) for ell, _ in scheme.grids}


def _points(seed: int, dim: int) -> list:
    rng = np.random.default_rng(seed + 1)
    return [rng.random((QUERY_POINTS, dim)).astype(np.float32)
            for _ in range(QUERY_BATCHES)]


def _rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(1.0, np.max(np.abs(want))))


def _check(what: str, err: float, tol: float) -> None:
    if not err <= tol:
        raise AssertionError(f"{what}: relative error {err:.3e} exceeds "
                             f"{tol:.0e}")


def _query_round(engine, name: str, points):
    t0 = time.perf_counter()
    futs = [engine.submit_query(name, p) for p in points]
    engine.flush()
    answers = [f.result() for f in futs]
    return time.perf_counter() - t0, answers


def _serve(engine, name: str, scheme, grids, spec, points, dev) -> dict:
    """register -> one refresh ingest -> query batches -> flush, timed;
    the query round runs twice (the first one compiles the eval), and
    both rounds' answers are returned."""
    t0 = time.perf_counter()
    engine.register(name, scheme, grids, spec=spec)
    jax.block_until_ready(engine.surplus(name))
    register_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    jax.block_until_ready(engine.submit_ingest(name, grids).result())
    ingest_s = time.perf_counter() - t0

    first_s, first = _query_round(engine, name, points)
    query_s, again = _query_round(engine, name, points)
    return {"register_incl_compile_s": register_s, "ingest_s": ingest_s,
            "query_incl_compile_s": first_s, "query_s": query_s,
            "answers": first + again,
            "peak_bytes_in_use": _peak_bytes(dev)}


def _reference(grids, scheme, points) -> list:
    from repro.core.combination import combined_interpolant_points
    ref = jax.jit(lambda g, p: combined_interpolant_points(g, scheme, p))
    return [np.asarray(ref(grids, p)) for p in points]


def _jnp_path_surplus(grids, plan):
    """The tenant's surplus with every bucket on the jnp method."""
    import jax.numpy as jnp
    from repro.core.executor import bucket_nodal_stacks
    from repro.kernels.hierarchize import hierarchize_batched

    def transform(g):
        full = jnp.zeros(plan.fine_size + 1, jnp.float32)
        for b, x in zip(plan.buckets, bucket_nodal_stacks(g, plan)):
            alpha = hierarchize_batched(x.reshape((len(b.ells),) + b.shape),
                                        b.levels, method="jnp")
            full = full.at[b.index].add(
                jnp.asarray(b.coeffs, jnp.float32)[:, None]
                * alpha.reshape(len(b.ells), -1))
        return full[:-1].reshape(plan.fine_shape)

    return jax.jit(transform)(grids)


def _compiled_ingest(grids, plan, spec):
    """The tenant's ingest program compiled on its own (the engine
    compiled its own copy at register): ``(seconds, holds a Mosaic
    kernel, memory analysis)``."""
    from repro.core.executor import ct_transform_with_plan
    t0 = time.perf_counter()
    compiled = jax.jit(
        lambda g: ct_transform_with_plan(g, plan, spec=spec)
    ).lower(grids).compile()
    return (time.perf_counter() - t0, "tpu_custom_call" in compiled.as_text(),
            compiled.memory_analysis())


def phase_one_chip(config: str, seed: int, dev, *, prove_kernels: bool):
    from repro.configs.sparse_grid import get_ct_config
    from repro.core.engine import CTEngine, ExecSpec
    from repro.core.interpolation import interpolate_hierarchical
    from repro.kernels.hierarchize import count_launches

    cfg = get_ct_config(config)
    scheme = cfg.scheme
    spec = ExecSpec(interpret=False, dtype="float32")
    t0 = time.perf_counter()
    grids = jax.block_until_ready(_grids(scheme, seed))
    points = _points(seed, scheme.dim)
    setup_s = time.perf_counter() - t0

    engine = CTEngine(spec, ingest_workers=0)
    try:
        with count_launches() as launches:
            out = _serve(engine, config, scheme, grids, spec, points, dev)
        plan = engine.plan(config)
        want = _reference(grids, scheme, points) * 2   # both query rounds
        err = max(_rel_err(a, w) for a, w in zip(out["answers"], want))
        _check(f"{config} answers vs dict-path reference", err, REF_RTOL)

        compile_s, mosaic, mem = _compiled_ingest(grids, plan, spec)

        extra = {}
        if prove_kernels:
            if launches["pallas"] < 1:
                raise AssertionError(f"{config}: no Pallas launch traced "
                                     f"in the ingest ({launches})")
            if not mosaic:
                raise AssertionError(f"{config}: compiled ingest holds no "
                                     f"tpu_custom_call")
            alt = _jnp_path_surplus(grids, plan)
            surplus = engine.surplus(config)
            ev = jax.jit(interpolate_hierarchical)
            alt_err = max(_rel_err(ev(surplus, p), ev(alt, p))
                          for p in points)
            _check(f"{config} Pallas vs jnp-path answers", alt_err,
                   SAME_PATH_RTOL)
            extra = {"pallas_vs_jnp_rel_err": alt_err,
                     "pallas_vs_jnp_surplus_bitwise": bool(
                         np.array_equal(np.asarray(surplus),
                                        np.asarray(alt)))}
    finally:
        engine.close()
    _log(config, device_kind=dev.device_kind,
         grids=len(scheme.grids), fine_shape=plan.fine_shape,
         setup_s=f"{setup_s:.3f}",
         compile_s=f"{compile_s:.3f}",
         register_incl_compile_s=f"{out['register_incl_compile_s']:.3f}",
         ingest_s=f"{out['ingest_s']:.4f}",
         query_incl_compile_s=f"{out['query_incl_compile_s']:.3f}",
         query_s=f"{out['query_s']:.4f}",
         queries=f"{QUERY_BATCHES}x{QUERY_POINTS}",
         ingest_temp_bytes=mem.temp_size_in_bytes,
         ingest_out_bytes=mem.output_size_in_bytes,
         peak_bytes_in_use=out["peak_bytes_in_use"],
         pallas_launches=launches["pallas"], jnp_passes=launches["einsum"],
         tpu_custom_call=mosaic, max_rel_err=f"{err:.3e}",
         **{k: (f"{v:.3e}" if isinstance(v, float) else v)
            for k, v in extra.items()})


def phase_four_chips(seed: int, devs) -> None:
    """prod_3d on a 2x2 (member x slab) mesh vs. one device, same grids."""
    from jax.sharding import AxisType
    from repro.configs.sparse_grid import get_ct_config
    from repro.core.engine import CTEngine, ExecSpec

    scheme = get_ct_config("prod_3d").scheme
    mesh = jax.make_mesh((2, 2), ("member", "slab"),
                         devices=np.array(devs[:4]),
                         axis_types=(AxisType.Auto, AxisType.Auto))
    meshed = ExecSpec(mesh=mesh, axis_name="slab", member_axis="member",
                      interpret=False, dtype="float32")
    single = ExecSpec(interpret=False, dtype="float32")
    grids = jax.block_until_ready(_grids(scheme, seed))
    points = _points(seed, scheme.dim)

    engine = CTEngine(single, ingest_workers=0)
    try:
        one = _serve(engine, "one", scheme, grids, single, points, devs[0])
        mesh_out = _serve(engine, "mesh", scheme, grids, meshed, points,
                          devs[0])
        s_one, s_mesh = engine.surplus("one"), engine.surplus("mesh")
        holders = {sh.device for sh in s_mesh.addressable_shards}
        if len(holders) != 4:
            raise AssertionError(f"meshed surplus sits on {len(holders)} "
                                 f"device(s), expected 4")
        diff = _rel_err(s_mesh, s_one)
        _check("2x2 mesh vs one-device surplus", diff, SAME_PATH_RTOL)
        want = _reference(grids, scheme, points) * 2   # both query rounds
        err = max(_rel_err(a, w) for a, w in zip(mesh_out["answers"], want))
        _check("2x2 mesh answers vs dict-path reference", err, REF_RTOL)
        q_diff = max(_rel_err(a, b) for a, b in
                     zip(mesh_out["answers"], one["answers"]))
    finally:
        engine.close()
    _log("prod_3d_2x2", device_kind=devs[0].device_kind,
         mesh="2x2(member,slab)", surplus_devices=len(holders),
         one_register_incl_compile_s=f"{one['register_incl_compile_s']:.3f}",
         one_ingest_s=f"{one['ingest_s']:.4f}",
         mesh_register_incl_compile_s=(
             f"{mesh_out['register_incl_compile_s']:.3f}"),
         mesh_ingest_s=f"{mesh_out['ingest_s']:.4f}",
         one_query_s=f"{one['query_s']:.4f}",
         mesh_query_s=f"{mesh_out['query_s']:.4f}",
         mesh_vs_one_rel_err=f"{diff:.3e}",
         mesh_vs_one_bitwise=bool(np.array_equal(np.asarray(s_mesh),
                                                 np.asarray(s_one))),
         mesh_vs_one_query_rel_err=f"{q_diff:.3e}",
         max_rel_err=f"{err:.3e}",
         peak_bytes_in_use_dev0=_peak_bytes(devs[0]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh prod_3d ingest and its "
                         "one-device comparison")
    args = ap.parse_args(argv)

    devs = _device_or_fail(4 if args.four_chips else 1)
    if jax.config.jax_enable_x64:
        raise RuntimeError("chip_smoke runs f32: x64 must be off")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import use_compile_cache
    _log("setup", jax=jax.__version__, devices=len(devs),
         device_kind=devs[0].device_kind, compile_cache=use_compile_cache())

    if args.four_chips:
        phase_four_chips(args.seed, devs)
    else:
        phase_one_chip("prod_3d", args.seed, devs[0], prove_kernels=False)
        phase_one_chip("fig6_2d", args.seed, devs[0], prove_kernels=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
