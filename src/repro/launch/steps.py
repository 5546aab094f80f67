"""Step functions bound for jit: train_step / prefill_step / serve_step,
plus the combination-technique steps (``make_ct_step`` /
``make_ct_eval_step``) backed by the batched executor.

Kept separate from the driver so the dry-run, the trainer and the tests
lower exactly the same computations.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models.config import ModelConfig
from repro.models.transformer import init_params, loss_fn
from repro.optim.adamw import AdamWState, adamw_init, adamw_update, \
    clip_by_global_norm

__all__ = ["make_train_step", "make_prefill_step", "make_serve_step",
           "init_train_state", "make_ct_step", "make_ct_eval_step"]


def init_train_state(key, cfg: ModelConfig):
    params = init_params(key, cfg)
    return params, adamw_init(params)


def make_train_step(cfg: ModelConfig, lr_fn: Callable,
                    grad_clip: float = 1.0,
                    grad_accum: int = 1) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``grad_accum`` > 1 splits the batch into microbatches scanned
    sequentially (activation memory / overlap lever used in §Perf).
    """

    def step(params, opt_state: AdamWState, batch):
        if grad_accum == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, cfg, batch)
        else:
            def micro(carry, mb):
                acc, loss_acc = carry
                l, g = jax.value_and_grad(loss_fn)(params, cfg, mb)
                acc = jax.tree.map(lambda a, b: a + b, acc, g)
                return (acc, loss_acc + l), None

            micro_batches = jax.tree.map(
                lambda t: t.reshape((grad_accum, t.shape[0] // grad_accum)
                                    + t.shape[1:]), batch)
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros((), jnp.float32)), micro_batches)
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            loss = loss / grad_accum
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        lr = lr_fn(opt_state.step)
        new_params, new_opt = adamw_update(grads, opt_state, params, lr=lr)
        metrics = {"loss": loss.astype(jnp.float32), "grad_norm": gnorm,
                   "lr": lr}
        return new_params, new_opt, metrics

    return step


def make_prefill_step(cfg: ModelConfig) -> Callable:
    def step(params, batch):
        return M.prefill_step(params, cfg, batch)
    return step


def make_serve_step(cfg: ModelConfig) -> Callable:
    def step(params, cache, batch):
        return M.serve_step(params, cfg, cache, batch)
    return step


def make_ct_step(scheme, *, spec=None) -> Callable:
    """ONE jitted function for the whole CT communication phase:
    ``{ell: nodal}`` -> sparse-grid surplus on the common fine grid.

    The scheme — classical ``CombinationScheme`` or downward-closed
    ``GeneralScheme`` (both hashable) — is bound at closure time, so the
    executor's bucket plan and index maps are trace-time constants:
    re-calling with new grid VALUES never retraces (one jit cache entry
    per scheme shape signature).  ``spec`` (a ``repro.core.engine.
    ExecSpec``) carries the execution policy — ``spec.merge`` opts the
    bound plan into cost-model-driven bucket merging (fewer launches per
    step, bit-identical surpluses).  For steps DEDUPED across many
    schemes by shape signature, serve through ``repro.core.engine.
    CTEngine`` instead — this helper compiles per scheme.
    """
    return jax.jit(_bind_ct_transform(scheme, spec))


def make_ct_eval_step(scheme, *, spec=None) -> Callable:
    """Jitted CT surrogate evaluation: ``({ell: nodal}, points (Q, d))`` ->
    combined-interpolant values (Q,) — transform + hierarchical-basis
    evaluation fused into one computation (the serving hot path).
    ``spec`` as in ``make_ct_step``."""
    from repro.core.interpolation import interpolate_hierarchical
    transform = _bind_ct_transform(scheme, spec)

    @jax.jit
    def step(nodal_grids, points):
        return interpolate_hierarchical(transform(nodal_grids), points)

    return step


def _bind_ct_transform(scheme, spec) -> Callable:
    """The gather bound to (scheme, spec) with the plan as a trace-time
    constant — honoring the WHOLE spec: a meshed spec binds the
    slab-sharded multi-device gather (``repro.core.engine`` precedence
    rule 4), everything else the single-device plan gather."""
    from repro.core.executor import build_plan, ct_transform_with_plan
    plan = build_plan(scheme, spec=spec)     # ShardedPlan when spec shards
    return lambda nodal_grids: ct_transform_with_plan(nodal_grids, plan,
                                                      spec=spec)
