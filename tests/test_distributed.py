"""Multi-device behaviour, in process on the 8 fake host devices that
``conftest.py`` configures via XLA_FLAGS before jax initializes (the old
subprocess-per-test harness respawned python + jax for every case; the
``multidevice`` marker now gates the whole tier instead)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from jax.sharding import AxisType, set_mesh
from repro.core import combination as comb
from repro.core.distributed import ct_transform_sharded
from repro.core.executor import (build_plan, ct_scatter_with_plan,
                                 ct_transform, shard_plan)
from repro.core.levels import (CombinationScheme, GeneralScheme, grid_shape)
from repro.kernels.ops import dehierarchize, hierarchize

pytestmark = pytest.mark.multidevice


@pytest.fixture
def no_x64():
    """Model-path tests ran WITHOUT x64 under the old subprocess harness
    (conftest enables it globally for the CT oracles); the transformer
    decode path also miscompiles with 64-bit index types.  Scoping the
    flag per-test keeps both worlds in one process."""
    disable = getattr(jax.experimental, "disable_x64", None)
    if disable is not None:
        with disable():
            yield
        return
    jax.config.update("jax_enable_x64", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", True)


def _mesh8():
    return jax.make_mesh((8,), ("grid",), axis_types=(AxisType.Auto,))


def test_comm_phase_slab_sharded_matches_serial():
    """The whole comm phase on the mesh: the slab-sharded gather
    (``ct_transform_sharded``, no ``(G, *fine_shape)`` stack) then the
    plan scatter == the serial dict comm phase, grid by grid."""
    mesh = _mesh8()
    scheme = CombinationScheme(2, 5)
    rng = np.random.default_rng(1)
    grids = {ell: jnp.asarray(rng.standard_normal(grid_shape(ell)))
             for ell, _ in scheme.grids}
    hier = {ell: hierarchize(g, "ref") for ell, g in grids.items()}
    combined = comb.gather_subspaces(hier, scheme)
    want = comb.scatter_subspaces(combined, scheme)
    splan = shard_plan(build_plan(scheme), 8)
    full = ct_transform_sharded(grids, scheme, mesh, "grid", plan=splan)
    got = ct_scatter_with_plan(full, splan)
    assert set(got) == set(want)
    for ell in got:
        np.testing.assert_allclose(np.asarray(got[ell]),
                                   np.asarray(dehierarchize(want[ell], "ref")),
                                   rtol=1e-8, atol=1e-9)


def test_ct_transform_psum_general_scheme():
    """The distributed gather accepts a GeneralScheme (adaptive index set)
    unchanged: slab-sharded path == single-process executor path,
    bit for bit."""
    mesh = _mesh8()
    scheme = GeneralScheme.from_levels(
        [(5, 1, 1), (3, 3, 1), (2, 2, 2), (1, 4, 1)], close=True)
    rng = np.random.default_rng(3)
    grids = {ell: jnp.asarray(rng.standard_normal(grid_shape(ell)))
             for ell, _ in scheme.grids}
    want = ct_transform(grids, scheme)
    got = ct_transform_sharded(grids, scheme, mesh, "grid")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ct_transform_sharded_keeps_sharding():
    """``gather=False``: the result stays slab-sharded under a
    NamedSharding, leading axis padded to ``n_slabs * slab_rows``."""
    mesh = _mesh8()
    scheme = CombinationScheme(2, 5)
    rng = np.random.default_rng(4)
    grids = {ell: jnp.asarray(rng.standard_normal(grid_shape(ell)))
             for ell, _ in scheme.grids}
    splan = shard_plan(build_plan(scheme), 8)
    out = ct_transform_sharded(grids, scheme, mesh, "grid",
                               plan=splan, gather=False)
    assert out.shape[0] == 8 * splan.slab_rows
    assert isinstance(out.sharding, NamedSharding)
    assert out.sharding.spec[0] == "grid"
    want = np.asarray(ct_transform(grids, scheme))
    np.testing.assert_array_equal(np.asarray(out)[:want.shape[0]], want)
    assert np.all(np.asarray(out)[want.shape[0]:] == 0)


@pytest.mark.slow
def test_dp_training_step_matches_single_device(no_x64):
    """8-way DP: global loss equals the 1-device loss on the same batch."""
    from repro.configs import get_smoke_config
    from repro.launch.steps import init_train_state, make_train_step
    from repro.launch import sharding as rules
    from repro.models import model as M
    from repro.models.config import ShapeConfig
    from repro.optim.schedule import constant
    cfg = get_smoke_config("smollm_360m")
    key = jax.random.PRNGKey(0)
    params, opt = init_train_state(key, cfg)
    batch = M.make_batch(cfg, ShapeConfig("t", 32, 8, "train"), key)
    step = make_train_step(cfg, constant(1e-3))
    l1 = float(step(params, opt, batch)[2]["loss"])
    mesh = jax.make_mesh((8, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    named = lambda t: jax.tree.map(
        lambda s: NamedSharding(mesh, s), t,
        is_leaf=lambda x: isinstance(x, P))
    sds = jax.eval_shape(lambda: init_train_state(key, cfg))
    ps = rules.param_specs(sds[0], mesh)
    bs = {"tokens": P("data", None), "labels": P("data", None)}
    with mesh:
        jitted = jax.jit(step, in_shardings=(named(ps), None, named(bs)))
        l8 = float(jitted(params, opt, batch)[2]["loss"])
    np.testing.assert_allclose(l8, l1, rtol=2e-4)


@pytest.mark.slow
def test_elastic_remesh_restore(tmp_path, no_x64):
    """Elastic downscale: train 8 steps on an 8-device mesh, checkpoint,
    'lose' half the fleet, restore onto the plan_mesh-chosen 4-device mesh
    and keep training — losses stay finite and the restore is exact."""
    from repro.checkpoint.checkpoint import restore_checkpoint, \
        save_checkpoint
    from repro.configs import get_smoke_config
    from repro.launch import sharding as rules
    from repro.launch.steps import init_train_state, make_train_step
    from repro.models import model as M
    from repro.models.config import ShapeConfig
    from repro.optim.schedule import constant
    from repro.runtime.elastic import plan_mesh

    cfg = get_smoke_config("smollm_360m")
    key = jax.random.PRNGKey(0)
    shape = ShapeConfig("t", 32, 8, "train")
    step = make_train_step(cfg, constant(1e-3))
    ckdir = str(tmp_path)

    def run_on(n_devs, params, opt, steps, start):
        plan = plan_mesh(n_devs, chips_per_pod=8, preferred_model=2)
        mesh = jax.make_mesh(plan.shape(), plan.axes(),
                             axis_types=(AxisType.Auto,) * len(plan.axes()))
        named = lambda t: jax.tree.map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        sds = jax.eval_shape(lambda: init_train_state(key, cfg))
        psh = named(rules.param_specs(sds[0], mesh))
        osh = named(rules.opt_state_specs(sds[0], mesh))
        params = jax.device_put(params, psh)
        opt = jax.device_put(opt, osh)
        with mesh:
            fn = jax.jit(step, in_shardings=(psh, osh, None),
                         out_shardings=(psh, osh, None))
            losses = []
            for s in range(start, start + steps):
                batch = M.make_batch(cfg, shape,
                                     jax.random.fold_in(key, s))
                params, opt, m = fn(params, opt, batch)
                losses.append(float(m["loss"]))
        return params, opt, losses

    params, opt = init_train_state(key, cfg)
    params, opt, l1 = run_on(8, params, opt, steps=4, start=0)
    save_checkpoint(ckdir, 4, (params, opt))
    # fleet shrinks to 4 devices: restore + continue
    tmpl = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype),
                        (params, opt))
    (params2, opt2), _ = restore_checkpoint(ckdir, 4, tmpl)
    # the restored params are bit-identical to the saved ones
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    params2, opt2, l2 = run_on(4, params2, opt2, steps=4, start=4)
    assert all(np.isfinite(l) for l in l1 + l2), (l1, l2)


@pytest.mark.slow
def test_ep_moe_matches_ragged(no_x64):
    """Expert-parallel shard_map dispatch == exact ragged dispatch at high
    capacity, and gradients flow (the production MoE path, §Perf)."""
    from repro.models.moe import moe_ffn, moe_ffn_ep
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    e, d, f, b, s, k = 8, 16, 32, 4, 12, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    params = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * 0.1,
        "wi_gate": jax.random.normal(ks[1], (e, d, f)) * d ** -0.5,
        "wi_up": jax.random.normal(ks[2], (e, d, f)) * d ** -0.5,
        "wo": jax.random.normal(ks[3], (e, f, d)) * f ** -0.5,
    }
    x = jax.random.normal(ks[4], (b, s, d), jnp.float32)
    y_ref, _ = moe_ffn(x.reshape(b * s, d), params, num_experts=e,
                       k=k, impl="ragged")
    with set_mesh(mesh):
        y_ep, _ = jax.jit(lambda x, p: moe_ffn_ep(
            x, p, num_experts=e, k=k, capacity_factor=8.0))(x, params)
        g = jax.jit(jax.grad(lambda p: jnp.sum(moe_ffn_ep(
            x, p, num_experts=e, k=k, capacity_factor=8.0)[0] ** 2)))(
            params)
    np.testing.assert_allclose(np.asarray(y_ep),
                               np.asarray(y_ref).reshape(b, s, d),
                               rtol=2e-4, atol=2e-4)
    for leaf in jax.tree.leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()


def test_ep_moe_no_mesh_fallback():
    """Without a mesh context moe_ffn_ep returns None and the block falls
    back to ragged — the 1-device smoke path."""
    from repro.models.moe import moe_ffn_ep
    x = jnp.zeros((2, 4, 8))
    params = {"router": jnp.zeros((8, 4))}
    assert moe_ffn_ep(x, params, num_experts=4, k=2) is None


@pytest.mark.slow
def test_dryrun_single_cell_smallpod(no_x64):
    """The dry-run machinery itself (build_cell + analysis) on an 8-chip
    mesh — fast proxy for the 256/512-chip sweep recorded in EXPERIMENTS."""
    from repro.configs import get_config
    from repro.launch.dryrun import build_cell
    from repro.launch.analysis import collective_bytes
    from repro.models.config import ShapeConfig
    cfg = get_config("smollm_360m")
    shape = ShapeConfig("t", 256, 8, "train")
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    fn, args = build_cell(cfg, shape, mesh)
    with mesh:
        compiled = fn.lower(*args).compile()
    cost = compiled.cost_analysis()
    assert cost.get("flops", 0) > 0
    coll = collective_bytes(compiled.as_text())
    assert sum(coll.values()) > 0, coll
