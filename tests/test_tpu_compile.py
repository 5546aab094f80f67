"""Compile-only checks against a DESCRIBED TPU v5e (no chip attached).

The TPU compiler ships with the installed jax, so the forward/inverse
batched kernels, the single-grid ``ops`` dispatcher and the served
ingest programs are compiled here exactly as the chip's compiler would
compile them: a kernel Mosaic cannot lower, a block shape that breaks
the (8, 128) tiling rule, or an ingest that outgrows one chip's 16 GB
fails here at no chip time.  Nothing runs, so these tests say nothing
about results or times (the CPU interpret-mode tests pin results).

The topology is described inside a module-scoped fixture — never at
import — and every test of this file depends on it, so the file is
skipped as a whole where no v5e topology can be described.  Compiles run
with x64 off and Pallas interpret mode off, as on the chip.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.configs.sparse_grid import get_ct_config
from repro.core import engine as E
from repro.core.executor import ShardedPlan, build_plan
from repro.core.levels import grid_shape
from repro.kernels import hierarchize as hk
from repro.kernels import ops

#: one v5e chip's HBM
HBM_BYTES = 16 * 10 ** 9

#: (bucket shape, member level vectors) of fig6_2d's Pallas-path buckets
#: (d=2, level 11: the three near-square canonical shapes)
FIG6_BUCKETS = [((127, 31), ((7, 5), (7, 5))),
                ((63, 63), ((6, 6),)),
                ((63, 31), ((6, 5), (6, 5)))]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", saved)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    with jax.enable_x64(False):
        return jax.jit(fn).lower(*args).compile()


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _on_chip_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.output_size_in_bytes
            + m.argument_size_in_bytes)


# ---------------------------------------------------------------------------
# kernels at fig6_2d bucket widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("inverse", [False, True], ids=["fwd", "inv"])
@pytest.mark.parametrize("shape,levels", FIG6_BUCKETS,
                         ids=[f"{s[0]}x{s[1]}" for s, _ in FIG6_BUCKETS])
def test_axis0_kernel_compiles(one_chip, shape, levels, inverse):
    c = _compile(lambda x: hk.hier_axis0_batched_pallas(
        x, [lv[0] for lv in levels], inverse=inverse, interpret=False),
        _f32((len(levels),) + shape, one_chip))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("inverse", [False, True], ids=["fwd", "inv"])
@pytest.mark.parametrize("shape,levels", FIG6_BUCKETS,
                         ids=[f"{s[0]}x{s[1]}" for s, _ in FIG6_BUCKETS])
def test_tail_kernel_compiles(one_chip, shape, levels, inverse):
    c = _compile(lambda x: hk.hier_tail_batched_pallas(
        x, levels, inverse=inverse, interpret=False),
        _f32((len(levels),) + shape, one_chip))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("shape,levels", FIG6_BUCKETS,
                         ids=[f"{s[0]}x{s[1]}" for s, _ in FIG6_BUCKETS])
def test_runtime_level_table_compiles(one_chip, shape, levels):
    """The 2-D ingest's spelling: the level table is a runtime array."""
    lv = jax.ShapeDtypeStruct((len(levels), 2), jnp.int32, sharding=one_chip)
    c = _compile(lambda x, t: hk.hierarchize_batched(
        x, t, interpret=False, method="pallas"),
        _f32((len(levels),) + shape, one_chip), lv)
    assert c.as_text().count("tpu_custom_call") >= 2     # tail + axis 0


@pytest.mark.parametrize("shape", [(63, 127), (15, 31, 63), (7, 7, 15, 127)])
@pytest.mark.parametrize("inverse", [False, True], ids=["fwd", "inv"])
def test_ops_auto_and_pole_compile(one_chip, shape, inverse):
    """``ops.(de)hierarchize(method="auto")`` — the dispatcher the
    iterated CT uses — and the paper's pole kernel."""
    fn = ops.dehierarchize if inverse else ops.hierarchize
    c = _compile(lambda x: fn(x, "auto", interpret=False),
                 _f32(shape, one_chip))
    assert "tpu_custom_call" in c.as_text()
    pole = hk.dehier_pole_pallas if inverse else hk.hier_pole_pallas
    c = _compile(lambda x: pole(x, interpret=False),
                 _f32((shape[0], 256), one_chip))
    assert "tpu_custom_call" in c.as_text()


# ---------------------------------------------------------------------------
# the served ingest programs, at full size
# ---------------------------------------------------------------------------

def _ingest_args(plan, sharding):
    """Shapes of the engine executable's ``(parts, idxs, coeffs)``; on
    one device ``idxs`` is ``(compact maps, fine map)``."""
    base = plan.plan if isinstance(plan, ShardedPlan) else plan

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    parts = tuple(sds(grid_shape(ell), jnp.float32)
                  for b in base.buckets for ell in b.ells)
    if isinstance(plan, ShardedPlan):
        idxs = tuple((sds(sb.ship_src.shape, jnp.int32),
                      sds(sb.ship_idx.shape, jnp.int32))
                     for sb in plan.slab_buckets)
    else:
        # the compact gather's bucket maps and its fine map
        idxs = (tuple(sds(m.shape, jnp.int32)
                      for m in plan.compact.buckets),
                sds(plan.compact.fine.shape, jnp.int32))
    coeffs = tuple(sds(b.coeffs.shape, jnp.float32) for b in base.buckets)
    return parts, idxs, coeffs


def _scatter_outputs(text):
    """Length of every f32 buffer a scatter in the compiled program
    writes."""
    return [int(n) for n in
            re.findall(r"= f32\[(\d+)\]\S* scatter\(", text)]


def _assert_one_fine_scatter(text, plan):
    """The compact gather: one scatter writes the flat fine grid (the
    expansion ``scatter_ms_per_ingest`` reads); every other scatter
    writes the compact vector of N points and the dump slot."""
    outs = _scatter_outputs(text)
    assert outs.count(plan.fine_size) == 1
    rest = [n for n in outs if n != plan.fine_size]
    assert rest and set(rest) == {plan.compact.size + 1}


def _ingest(plan, spec, sharding):
    fn = E._build_ingest_executable(plan, spec)
    with jax.enable_x64(False):
        return fn.lower(*_ingest_args(plan, sharding)).compile()


@pytest.mark.parametrize("config,pallas", [("prod_3d", False),
                                           ("fig6_2d", True)])
def test_one_chip_ingest_compiles(one_chip, config, pallas):
    """prod_3d runs every bucket on the jnp path; fig6_2d has three
    Pallas buckets, which must reach the chip as Mosaic kernels.  Both
    take the compact gather, with one scatter into the fine grid."""
    spec = E.ExecSpec(interpret=False, dtype="float32")
    plan = build_plan(get_ct_config(config).scheme, spec=spec)
    c = _ingest(plan, spec, one_chip)
    assert ("tpu_custom_call" in c.as_text()) == pallas
    _assert_one_fine_scatter(c.as_text(), plan)
    assert _on_chip_bytes(c) < HBM_BYTES


@pytest.mark.parametrize("config,pallas", [("fig6_2d", True),
                                           ("prod_3d", False)])
def test_one_chip_packed_ingest_compiles(one_chip, config, pallas):
    """The packed feed's executable: one flat f32 buffer of every grid,
    cut back into the parts on the chip before the same body."""
    spec = E.ExecSpec(interpret=False, dtype="float32")
    plan = build_plan(get_ct_config(config).scheme, spec=spec)
    exe = E._IngestExecutable(plan, spec)
    _, idxs, coeffs = _ingest_args(plan, one_chip)
    flat = _f32((exe.packed_size,), one_chip)
    with jax.enable_x64(False):
        c = exe.packed.lower(flat, idxs, coeffs).compile()
    assert ("tpu_custom_call" in c.as_text()) == pallas
    _assert_one_fine_scatter(c.as_text(), plan)
    assert _on_chip_bytes(c) < HBM_BYTES


def test_2x2_mesh_prod_3d_ingest_compiles(topo):
    """The (member x slab) mesh ingest over the host's four chips:
    hierarchization compute-sharded, surpluses shipped by collectives."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("member", "slab"),
                axis_types=(AxisType.Auto, AxisType.Auto))
    spec = E.ExecSpec(mesh=mesh, axis_name="slab", member_axis="member",
                      interpret=False, dtype="float32")
    plan = build_plan(get_ct_config("prod_3d").scheme, spec=spec)
    assert plan.n_groups == 4
    c = _ingest(plan, spec, NamedSharding(mesh, PartitionSpec()))
    text = c.as_text()
    assert "all-to-all" in text and "all-gather" in text
    assert _on_chip_bytes(c) < HBM_BYTES


def test_served_eval_compiles_full_precision(one_chip):
    """The served eval at prod_3d size: its hat-basis contractions must
    stay full f32 precision on the chip (the TPU's default f32 matmul is
    one bf16 pass, which a CPU run can never show)."""
    c = _compile(E._EVAL_BATCHED,
                 _f32((511, 511, 511), one_chip), _f32((64, 3), one_chip))
    text = c.as_text()
    assert "operand_precision={highest,highest}" in text
    assert "operand_precision={default" not in text
    assert _on_chip_bytes(c) < HBM_BYTES


def test_prod_3d_eval_at_its_largest_padding_fits_beside_8_tenants(one_chip):
    """One surplus evaluated at the most points a chunk can give it: all
    ``max_batch`` rows of the engine's default (32) on one tenant, 64
    points each.  With the other seven tenants' surpluses resident, the
    chip's 16 GB must hold it, so no group has to be split."""
    fine = grid_shape(tuple(
        max(ell[k] for ell, _ in get_ct_config("prod_3d").scheme.grids)
        for k in range(3)))
    assert fine == (511, 511, 511)
    ppad = 32 * E._qpad(64)
    c = _compile(E._EVAL_BATCHED, _f32(fine, one_chip),
                 _f32((ppad, 3), one_chip))
    surplus_bytes = 4 * int(np.prod(fine))
    assert _on_chip_bytes(c) + 7 * surplus_bytes < HBM_BYTES
