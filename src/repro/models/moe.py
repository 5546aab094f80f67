"""Mixture-of-Experts FFN: token-choice top-k routing.

Three execution strategies (``config.moe_impl``):

* ``ragged``  — sort ALL tokens by expert, run ``jax.lax.ragged_dot``
  grouped GEMMs.  Exact, no drops — but the global argsort/scatter does
  NOT partition under GSPMD: the dry-run measured 1.8-3.7 TB/device temps
  on the MoE train cells (EXPERIMENTS.md §Perf).  Single-host / oracle
  path only.
* ``grouped`` — fixed-capacity (E, C, D) buffers + dense batched GEMMs;
  static shapes, still global dispatch.
* ``ep``      — PRODUCTION path: expert-parallel dispatch under a
  full-manual ``shard_map`` (experts over the ``model`` mesh axis, batch
  rows over the remaining axes).  Each shard owns E/TP
  experts, selects its tokens with a LOCAL argsort (capacity-bounded),
  runs local ragged GEMMs and combines with one psum — the same
  activation all-reduce a dense TP layer pays.  Tokens beyond
  ``capacity_factor * T * k / TP`` per shard are dropped (standard
  token-choice capacity semantics).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["moe_ffn", "moe_ffn_ep", "router_topk"]


def _ambient_mesh():
    """The mesh of the enclosing ``jax.sharding.set_mesh`` context, else
    the one a classic ``with mesh:`` block installed (which does not set
    the abstract mesh), else None."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is not None and mesh.shape:
        return mesh
    from jax._src import mesh as mesh_lib
    phys = mesh_lib.thread_resources.env.physical_mesh
    return None if phys.empty else phys


def router_topk(x, w_router, num_experts: int, k: int):
    """Returns (weights (T,k) f32 normalized, expert_idx (T,k) i32, aux_loss)."""
    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch-style load-balance aux loss
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], num_experts), axis=0)
    aux = num_experts * jnp.sum(me * ce)
    return weights, idx, aux


def _sort_by_expert(idx_flat, num_experts: int):
    """Stable sort of token-expert assignments; returns (perm, group_sizes)."""
    sort_idx = jnp.argsort(idx_flat, stable=True)
    group_sizes = jnp.bincount(idx_flat, length=num_experts)
    return sort_idx, group_sizes


def _ffn_ragged(xs, wi_gate, wi_up, wo, group_sizes):
    h = jax.nn.silu(jax.lax.ragged_dot(xs, wi_gate, group_sizes)) * \
        jax.lax.ragged_dot(xs, wi_up, group_sizes)
    return jax.lax.ragged_dot(h, wo, group_sizes)


def moe_ffn_ep(x, params, *, num_experts: int, k: int,
               capacity_factor: float = 2.0, axis_name: str = "model"):
    """Expert-parallel dispatch (see module docstring).  x: (B, S, D).

    The batch dim stays the DATA-sharded axis end to end — every sort /
    scatter is per-row, so nothing gathers the global token set (the
    failure mode of the ``ragged`` path under GSPMD).  Experts shard over
    ``axis_name``, batch rows over the remaining mesh axes, inside one
    full-manual shard_map; the only cross-shard communication is one
    activation psum, exactly like a dense TP layer.

    Returns None when no usable mesh context exists (caller falls back).
    """
    mesh = _ambient_mesh()
    if mesh is None or not getattr(mesh, "shape", None) or \
            axis_name not in mesh.shape:
        return None
    tp = mesh.shape[axis_name]
    if tp <= 1 or num_experts % tp:
        return None
    b, s, d = x.shape
    # batch rows distribute over the non-expert mesh axes (full-manual
    # shard_map: partial-auto lowers axis_index to a PartitionId op the
    # 0.4.x SPMD partitioner rejects); bail out to ragged when they don't
    data_axes = tuple(a for a in mesh.axis_names if a != axis_name)
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    if b % n_data:
        return None
    e_local = num_experts // tp
    # per-expert capacity per row; >=8 keeps decode (S=1) drop-free
    c_e = max(8, -(-int(capacity_factor * s * k / num_experts) // 8) * 8)
    cap = min(e_local * c_e, s * k)      # selected slots per row per shard

    x2 = x.reshape(b * s, d)
    weights, idx, aux = router_topk(x2, params["router"], num_experts, k)
    idx_r = idx.reshape(b, s * k).astype(jnp.int32)
    tok_r = jnp.repeat(jnp.arange(s, dtype=jnp.int32), k)[None].repeat(b, 0)
    # f32 across the shard_map boundary: shard_map's transpose inserts
    # psums for replicated inputs' cotangents, and bf16 psum/scatter-add
    # crashes the XLA:CPU SPMD partitioner ("Invalid binary instruction
    # opcode copy").  f32 is also the right combine accumulator; on TPU
    # the boundary converts fuse away.
    out_dtype = x.dtype
    x = x.astype(jnp.float32)
    w_r = weights.reshape(b, s * k).astype(jnp.float32)

    def local(xl, wf, idxf, tokf, wi_gate, wi_up, wo):
        wi_gate = wi_gate.astype(jnp.float32)
        wi_up = wi_up.astype(jnp.float32)
        wo = wo.astype(jnp.float32)
        bl = xl.shape[0]              # local batch rows (b / n_data)
        m = jax.lax.axis_index(axis_name)
        lo = m * e_local
        mine = (idxf >= lo) & (idxf < lo + e_local)          # (B, S*k)
        key = jnp.where(mine, idxf, num_experts)             # foreign last
        order = jnp.argsort(key, axis=-1)[:, :cap]           # per-row sort
        sel_e = jnp.clip(jnp.take_along_axis(idxf, order, 1) - lo,
                         0, e_local - 1)                     # (B, cap)
        valid = jnp.take_along_axis(mine, order, 1)
        toks = jnp.take_along_axis(tokf, order, 1)           # (B, cap)
        gates = jnp.take_along_axis(wf, order, 1) * valid.astype(xl.dtype)
        # position of each slot within its expert group (slots are sorted
        # by expert, so groups are contiguous per row)
        eid = jnp.where(valid, sel_e, e_local)
        counts = jnp.sum(jax.nn.one_hot(eid, e_local + 1,
                                        dtype=jnp.int32), axis=1)
        starts = jnp.cumsum(counts, axis=-1) - counts        # exclusive
        pos = jnp.arange(cap, dtype=jnp.int32)[None] - \
            jnp.take_along_axis(starts, eid, 1)
        keep = valid & (pos < c_e)
        slot = jnp.where(keep, sel_e * c_e + pos, e_local * c_e)
        xs = jnp.take_along_axis(xl, toks[..., None], axis=1)  # (B, cap, D)
        xs = xs * keep[..., None].astype(xl.dtype)
        buf = jnp.zeros((bl, e_local * c_e + 1, d), xl.dtype)
        buf = buf.at[jnp.arange(bl)[:, None], slot].add(xs)
        xe = buf[:, :-1].reshape(bl, e_local, c_e, d)
        h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, wi_gate)) * \
            jnp.einsum("becd,edf->becf", xe, wi_up)
        ye = jnp.einsum("becf,efd->becd", h, wo)
        ys = ye.reshape(bl, e_local * c_e, d)[
            jnp.arange(bl)[:, None], jnp.minimum(slot, e_local * c_e - 1)]
        ys = ys * (gates * keep.astype(xl.dtype))[..., None]
        out = jnp.zeros_like(xl).at[jnp.arange(bl)[:, None], toks].add(ys)
        return jax.lax.psum(out, axis_name)

    bspec = data_axes if data_axes else None
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, None, None), P(bspec, None), P(bspec, None),
                  P(bspec, None),
                  P(axis_name, None, None), P(axis_name, None, None),
                  P(axis_name, None, None)),
        out_specs=P(bspec, None, None),
        check_vma=False)
    y = fn(x, w_r, idx_r, tok_r,
           params["wi_gate"], params["wi_up"], params["wo"])
    return y.astype(out_dtype), aux


def moe_ffn(x, params, *, num_experts: int, k: int, impl: str = "ragged",
            capacity_factor: float = 2.0):
    """x: (T, D) tokens; params: router (D,E), wi_gate/wi_up (E,D,F), wo (E,F,D).

    Returns (y (T, D), aux_loss).
    """
    t, d = x.shape
    if impl == "ep":     # (T,D) entry point: EP needs the (B,S,D) caller
        impl = "ragged"  # (moe_ffn_ep); exact fallback for smoke scale
    weights, idx, aux = router_topk(x, params["router"], num_experts, k)
    idx_flat = idx.reshape(-1)                       # (T*k,)
    tok_flat = jnp.repeat(jnp.arange(t), k)          # source token per slot
    w_flat = weights.reshape(-1).astype(x.dtype)

    if impl == "ragged":
        perm, group_sizes = _sort_by_expert(idx_flat, num_experts)
        xs = x[tok_flat[perm]]                        # (T*k, D) sorted by expert
        ys = _ffn_ragged(xs, params["wi_gate"], params["wi_up"], params["wo"],
                         group_sizes)
        ys = ys * w_flat[perm][:, None]
        y = jnp.zeros_like(x).at[tok_flat[perm]].add(ys)
        return y, aux

    if impl == "grouped":
        capacity = int(capacity_factor * t * k / num_experts)
        capacity = max(8, -(-capacity // 8) * 8)
        perm, group_sizes = _sort_by_expert(idx_flat, num_experts)
        idx_sorted = idx_flat[perm]
        # position of each sorted slot within its expert group
        starts = jnp.cumsum(group_sizes) - group_sizes
        pos = jnp.arange(t * k) - starts[idx_sorted]
        keep = pos < capacity
        slot = jnp.where(keep, idx_sorted * capacity + pos, num_experts * capacity)
        buf = jnp.zeros((num_experts * capacity + 1, d), x.dtype)
        buf = buf.at[slot].set(x[tok_flat[perm]] * keep[:, None])
        xe = buf[:-1].reshape(num_experts, capacity, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, params["wi_gate"])) * \
            jnp.einsum("ecd,edf->ecf", xe, params["wi_up"])
        ye = jnp.einsum("ecf,efd->ecd", h, params["wo"])
        ys = ye.reshape(num_experts * capacity, d)[jnp.minimum(
            slot, num_experts * capacity - 1)]
        ys = ys * (w_flat[perm] * keep)[:, None]
        y = jnp.zeros_like(x).at[tok_flat[perm]].add(ys)
        return y, aux

    raise ValueError(f"unknown moe impl {impl!r}")
