import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede every other import (jax locks the device count on first
# init).  512 placeholder host devices back both production meshes:
# single-pod (16, 16) = 256 chips and multi-pod (2, 16, 16) = 512 chips.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell the step function is jit'd with explicit in/out shardings,
``.lower()``ed against ShapeDtypeStruct inputs (no allocation anywhere —
the 235B config never materializes) and ``.compile()``d.  Success proves
the sharding config is coherent (no mismatched collectives, no replication
explosions); the compiled artifact yields

  * ``memory_analysis()``  — per-device bytes (proves the cell fits),
  * ``cost_analysis()``    — per-device FLOPs / bytes for §Roofline,
  * optimized HLO text     — collective operand bytes for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch smollm_360m --shape train_4k
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""

import argparse
import functools
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from jax.sharding import AxisType, set_mesh
from repro.configs import ARCH_IDS, SHAPE_BY_NAME, get_config, shape_cells
from repro.launch import sharding as rules
from repro.launch.analysis import collective_bytes, roofline_from_artifacts
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.launch.steps import make_train_step
from repro.models import model as M
from repro.models.config import ModelConfig, ShapeConfig, model_flops
from repro.models.transformer import init_params
from repro.optim.adamw import adamw_init
from repro.optim.schedule import warmup_cosine


def _named(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               grad_accum: int = 1):
    """Returns (jitted_fn, abstract_args) for one cell."""
    params_sds = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    p_specs = rules.param_specs(params_sds, mesh)
    b_specs = rules.batch_specs(cfg, shape, mesh)
    batch_sds = M.input_specs(cfg, shape)
    baxes = batch_axes(mesh)
    nb = 1
    for a in baxes:
        nb *= mesh.shape[a]
    bspec = baxes if (nb > 1 and shape.global_batch % nb == 0) else None
    logits_spec = P(bspec, None, "model")

    if shape.kind == "train":
        opt_sds = jax.eval_shape(adamw_init, params_sds)
        o_specs = rules.opt_state_specs(params_sds, mesh)
        lr_fn = warmup_cosine(3e-4, 100, 10000)
        step = make_train_step(cfg, lr_fn, grad_accum=grad_accum)
        metrics_spec = {"loss": P(), "grad_norm": P(), "lr": P()}
        fn = jax.jit(step,
                     in_shardings=(_named(mesh, p_specs),
                                   _named(mesh, o_specs),
                                   _named(mesh, b_specs)),
                     out_shardings=(_named(mesh, p_specs),
                                    _named(mesh, o_specs),
                                    _named(mesh, metrics_spec)),
                     donate_argnums=(0, 1))
        return fn, (params_sds, opt_sds, batch_sds)

    if shape.kind == "prefill":
        step = lambda params, batch: M.prefill_step(params, cfg, batch)
        fn = jax.jit(step,
                     in_shardings=(_named(mesh, p_specs),
                                   _named(mesh, b_specs)),
                     out_shardings=_named(mesh, logits_spec))
        return fn, (params_sds, batch_sds)

    # decode: one new token against a seq_len-deep cache
    cache_sds = M.decode_cache_specs(cfg, shape.global_batch, shape.seq_len)
    c_specs = rules.cache_specs(cfg, cache_sds, shape, mesh)

    def step(params, cache, batch):
        return M.serve_step(params, cfg, cache, batch)

    fn = jax.jit(step,
                 in_shardings=(_named(mesh, p_specs),
                               _named(mesh, c_specs),
                               _named(mesh, b_specs)),
                 out_shardings=(_named(mesh, logits_spec),
                                _named(mesh, c_specs)),
                 donate_argnums=(1,))
    return fn, (params_sds, cache_sds, batch_sds)


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             hlo_dir: str | None = None, variant: dict | None = None) -> dict:
    """``variant``: ModelConfig overrides for §Perf experiments (act_shard,
    remat_policy, moe_impl, attn_chunk, grad_accum, mesh_shape="32x8" for
    an alternative same-chip-count factorization); non-empty variants get a
    suffixed cell name so they never overwrite the baseline artifact."""
    cfg = get_config(arch)
    shape = SHAPE_BY_NAME[shape_name]
    grad_accum = 1
    mesh_shape = None
    if variant:
        variant = dict(variant)
        grad_accum = int(variant.pop("grad_accum", 1))
        mesh_shape = variant.pop("mesh_shape", None)
        cfg = cfg.replace(**variant)
        if grad_accum != 1:
            variant["grad_accum"] = grad_accum
        if mesh_shape:
            variant["mesh_shape"] = mesh_shape
    if mesh_shape:
        dims = tuple(int(x) for x in mesh_shape.split("x"))
        names = ("data", "model") if len(dims) == 2 else \
            ("pod", "data", "model")
        mesh = jax.make_mesh(dims, names,
                             axis_types=(AxisType.Auto,) * len(dims))
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    cfg = cfg.replace(batch_axes=batch_axes(mesh),
                      model_axis_size=int(mesh.shape["model"]))
    chips = mesh.devices.size
    cell = f"{arch}__{shape_name}__{mesh_kind}"
    if variant:
        cell += "__" + "-".join(f"{k}={v}" for k, v in sorted(variant.items()))
    rec = {"cell": cell, "arch": arch, "shape": shape_name,
           "mesh": mesh_kind, "chips": int(chips), "ok": False,
           "variant": variant or {}}
    t0 = time.time()
    try:
        fn, args = build_cell(cfg, shape, mesh, grad_accum=grad_accum)
        with set_mesh(mesh):                # abstract-mesh context: needed
            lowered = fn.lower(*args)       # by shard_act / moe_ffn_ep
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        # scan-aware accounting (repro.launch.hlo_cost): XLA's cost_analysis
        # counts while bodies ONCE; our programs scan over layers/chunks, so
        # the corrected walk is the number that feeds §Roofline.  The raw
        # cost_analysis values are kept for reference.
        hc = analyze_hlo(hlo)
        coll = {k: int(v) for k, v in hc.collective_bytes.items()}
        if hlo_dir:
            os.makedirs(hlo_dir, exist_ok=True)
            with open(os.path.join(hlo_dir, cell + ".hlo.txt"), "w") as f:
                f.write(hlo)
        rec.update({
            "ok": True,
            "lower_s": t_lower - t0,
            "compile_s": t_compile - t_lower,
            "flops_per_device": hc.flops,
            "bytes_per_device": hc.traffic_bytes,
            "collective_bytes": coll,
            "raw_cost_analysis": {
                "flops": float(cost.get("flops", 0.0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
                "collective_bytes_once": collective_bytes(hlo),
            },
            "while_trips": {k: int(v) for k, v in
                            sorted(hc.while_trips.items())[:32]},
            "model_flops": model_flops(cfg, shape),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
            },
            "param_count": cfg.param_count(),
            "active_param_count": cfg.active_param_count(),
        })
        rl = roofline_from_artifacts(cell, chips,
                                     {"flops": hc.flops,
                                      "bytes accessed": hc.traffic_bytes},
                                     coll, rec["model_flops"])
        rec["roofline"] = rl.row()
    except Exception as e:  # a failed cell is a bug; record it loudly
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = time.time() - t0
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, cell + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def all_cells(mesh_kinds):
    for arch in ARCH_IDS:
        for shape in shape_cells(arch):
            for mk in mesh_kinds:
                yield arch, shape.name, mk


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=sorted(SHAPE_BY_NAME))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true",
                    help="run every assigned (arch x shape) cell")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--hlo-dir", default=None,
                    help="also dump optimized HLO text per cell")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--act-shard", choices=["none", "tp", "sp"], default=None)
    ap.add_argument("--remat-policy", choices=["full", "dots", "none"],
                    default=None)
    ap.add_argument("--moe-impl", choices=["ragged", "grouped", "ep"],
                    default=None)
    ap.add_argument("--attn-chunk", type=int, default=None)
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--capacity-factor", type=float, default=None)
    ap.add_argument("--mesh-shape", default=None,
                    help="alternative factorization, e.g. 32x8 (data x model)")
    ap.add_argument("--tuned", action="store_true",
                    help="apply the measured-best per-arch variant "
                         "(configs/launch_defaults.py, §Perf winners)")
    args = ap.parse_args(argv)
    variant = {}
    if args.act_shard is not None:
        variant["act_shard"] = args.act_shard
    if args.remat_policy is not None:
        variant["remat_policy"] = args.remat_policy
    if args.moe_impl is not None:
        variant["moe_impl"] = args.moe_impl
    if args.attn_chunk is not None:
        variant["attn_chunk"] = args.attn_chunk
    if args.grad_accum is not None:
        variant["grad_accum"] = args.grad_accum
    if args.capacity_factor is not None:
        variant["capacity_factor"] = args.capacity_factor
    if args.mesh_shape is not None:
        variant["mesh_shape"] = args.mesh_shape

    mesh_kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = list(all_cells(mesh_kinds))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape, mk) for mk in mesh_kinds]

    failures = 0
    for arch, shape_name, mk in cells:
        cell_variant = dict(variant)
        if args.tuned:
            from repro.configs.launch_defaults import tuned_variant
            tv = tuned_variant(arch, SHAPE_BY_NAME[shape_name].kind)
            if mk == "multi":
                tv.pop("mesh_shape", None)   # pod layout is fixed
            cell_variant = {**tv, **cell_variant}
        suffix = ("__" + "-".join(f"{k}={v}" for k, v in
                                  sorted(cell_variant.items()))
                  ) if cell_variant else ""
        path = os.path.join(args.out,
                            f"{arch}__{shape_name}__{mk}{suffix}.json")
        if args.skip_existing and os.path.exists(path):
            with open(path) as f:
                if json.load(f).get("ok"):
                    print(f"[skip] {arch} {shape_name} {mk}")
                    continue
        rec = run_cell(arch, shape_name, mk, args.out, args.hlo_dir,
                       variant=cell_variant)
        if rec["ok"]:
            rl = rec["roofline"]
            print(f"[ok]   {rec['cell']:56s} compile={rec['compile_s']:6.1f}s "
                  f"flops/dev={rec['flops_per_device']:.3e} "
                  f"coll/dev={sum(rec['collective_bytes'].values()):.3e}B "
                  f"bottleneck={rl['bottleneck']}", flush=True)
        else:
            failures += 1
            print(f"[FAIL] {rec['cell']}: {rec['error']}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
