"""Serving driver: batched prefill + decode against a KV/state cache,
plus sparse-grid surrogate serving (``CTSurrogate``) on the batched
executor.

The production deployment lowers ``prefill_step``/``serve_step`` on the
pod mesh (proven by the dry-run's prefill_32k/decode_32k/long_500k cells);
this driver runs the same step functions at smoke scale on CPU, with
continuous batching semantics kept simple: one batch of requests, greedy
sampling, per-request stop lengths.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.models import model as M
from repro.models.config import ModelConfig

__all__ = ["ServeConfig", "generate", "CTSurrogate"]


@dataclass(frozen=True)
class ServeConfig:
    arch: str = "smollm_360m"
    smoke: bool = True
    max_new_tokens: int = 16
    temperature: float = 0.0          # 0 = greedy
    seed: int = 0


def generate(sc: ServeConfig, prompts: np.ndarray,
             params=None) -> Dict[str, np.ndarray]:
    """prompts: (B, T) int32 token prompts (right-aligned, no padding).

    Returns dict with "tokens" (B, T + max_new) and "logprobs"."""
    cfg = get_smoke_config(sc.arch) if sc.smoke else get_config(sc.arch)
    b, t = prompts.shape
    max_seq = t + sc.max_new_tokens
    key = jax.random.PRNGKey(sc.seed)
    if params is None:
        from repro.models.transformer import init_params
        params = init_params(key, cfg)

    cache = M.init_decode_cache(cfg, b, max_seq)
    if cfg.family == "encdec":
        audio = (jax.random.normal(key, (b, cfg.encoder_seq, cfg.d_model))
                 * 0.02).astype(jnp.dtype(cfg.dtype))
        cache["cross"] = M.encode_for_decode(params, cfg, audio)

    step = jax.jit(lambda p, c, bt: M.serve_step(p, cfg, c, bt))
    tokens = jnp.asarray(prompts, jnp.int32)
    logprobs: List[jnp.ndarray] = []
    # prefill via the decode path (smoke scale); production uses prefill_step
    last_logits = None
    for pos in range(t):
        last_logits, cache = step(params, cache,
                                  {"token": tokens[:, pos:pos + 1],
                                   "pos": jnp.asarray(pos, jnp.int32)})
    out = [tokens]
    cur = None
    for i in range(sc.max_new_tokens):
        logits = last_logits[:, 0, :cfg.vocab_size]
        if sc.temperature > 0:
            key, sub = jax.random.split(key)
            cur = jax.random.categorical(sub, logits / sc.temperature, -1)
        else:
            cur = jnp.argmax(logits, -1)
        lp = jax.nn.log_softmax(logits, -1)
        logprobs.append(jnp.take_along_axis(lp, cur[:, None], 1)[:, 0])
        cur = cur[:, None].astype(jnp.int32)
        out.append(cur)
        last_logits, cache = step(params, cache,
                                  {"token": cur,
                                   "pos": jnp.asarray(t + i, jnp.int32)})
    return {"tokens": np.asarray(jnp.concatenate(out, axis=1)),
            "logprobs": np.asarray(jnp.stack(logprobs, axis=1))}


class CTSurrogate:
    """Sparse-grid surrogate server: solve once, answer point queries fast.

    A THIN single-tenant view over ``repro.core.engine.CTEngine`` — the
    surrogate registers itself as one named tenant and delegates ingest,
    queries and lifecycle to the engine, so its jitted ingest executable
    is automatically DEDUPED (process-wide) with every other surrogate or
    engine tenant sharing its plan shape-signature.  Serving many schemes
    side by side, or overlapping ingest with query traffic through the
    continuous-batching queue, is the engine's job; this class keeps the
    one-scheme convenience API.

    The CT workload's serving shape: a solver produces nodal values on
    every component grid; queries arrive as batches of points in [0,1]^d.
    The transform runs ONCE at ingest (one jitted call, no per-grid
    dispatch); queries hit only the cached surplus buffer through the
    engine's batched evaluation, so steady-state latency is a single
    interpolation kernel.

    Accepts the classical ``CombinationScheme`` or a downward-closed
    ``GeneralScheme`` (adaptive index sets, ``repro.core.adaptive``) —
    the executor plan is scheme-shape-keyed either way.  ``refit`` swaps
    in a refined scheme through the incremental ``extend_plan`` path;
    ``drop_grid`` is the serving-side fault hook: coefficients are
    recomputed by inclusion-exclusion while every bucket and index map of
    the live plan is kept, so recovery costs one re-ingest, not a plan
    rebuild (and, because index maps and coefficients are executable
    ARGUMENTS, usually not even a recompile).

    Execution policy comes as one ``spec=repro.core.engine.ExecSpec``:
    ``ExecSpec(mesh=...)`` runs the ingest slab-sharded over the mesh
    axis (per-device embedded memory ``fine_size / n_devices``; the
    served surplus stays replicated, so the query path is unchanged),
    ``ExecSpec(merge=...)`` turns on cost-model-driven bucket merging,
    and both survive ``refit`` / ``drop_grid`` through the incremental
    rebuilds.

    The backing engine is thread-safe: ``submit_query`` /
    ``submit_update`` enqueue from any thread and return ``CTFuture``
    handles, riding the engine's deadline-aware batching (see the
    ``repro.core.engine`` docstring for the scheduler contract); the
    synchronous ``query`` / ``update`` remain the one-caller
    convenience path.

    ``cluster=`` swaps the single engine for a whole
    ``repro.runtime.cluster.CTCluster`` fleet: the surrogate registers
    its one tenant through the cluster front door and every call routes
    by consistent-hash placement, with health-checked failover
    underneath — the API here does not change at all.  (In that mode
    the spec must be mesh-free; meshes belong to the cluster's hosts.)

    ``store=`` (a ``repro.runtime.durability.DurableStore``) makes the
    surrogate's OWN engine durable: every admitted update is journaled
    to a write-ahead log at admission and the served surplus is
    snapshotted every ``snapshot_interval`` acked updates, so a crashed
    process rebuilds this tenant bit-identically with
    ``CTSurrogate.restore(store, ...)`` (snapshot adopt + WAL replay —
    see the ``repro.runtime.durability`` docstring).  Only meaningful
    when the surrogate constructs its own engine; with ``engine=`` /
    ``cluster=`` durability is the backing deployment's property
    (``CTEngine(store=...)`` / ``CTCluster(durability_dir=...)``), and
    passing ``store=`` too raises.
    """

    def __init__(self, scheme, nodal_grids, spec=None, *,
                 engine=None, cluster=None, name: str = "surrogate",
                 store=None, snapshot_interval: int = 16):
        from repro.core.engine import CTEngine
        from repro.core.executor import ensure_spec
        if engine is not None and cluster is not None:
            raise ValueError("pass engine= or cluster=, not both")
        if store is not None and (engine is not None or cluster is not None):
            raise ValueError(
                "store= applies to the surrogate's own engine; a shared "
                "engine= / cluster= carries its own durability "
                "(CTEngine(store=...) / CTCluster(durability_dir=...))")
        spec = ensure_spec("CTSurrogate", spec)
        if cluster is not None:
            self._engine = cluster      # duck-typed CTEngine surface
        else:
            self._engine = engine if engine is not None else CTEngine(
                store=store, snapshot_interval=snapshot_interval)
        self._name = name
        self._engine.register(name, scheme, nodal_grids, spec=spec)

    @classmethod
    def restore(cls, store, *, name: str = "surrogate", spec=None,
                snapshot_interval: int = 16) -> "CTSurrogate":
        """Rebuild a durable surrogate after a crash: adopt tenant
        ``name``'s newest intact surplus snapshot from ``store`` and
        replay the WAL entries newer than it through the normal ingest
        path, so the restored surrogate answers BIT-identically to one
        that never crashed.  Raises ``KeyError`` when the store holds no
        tenant ``name``."""
        from repro.core.engine import CTEngine
        from repro.core.executor import ensure_spec
        engine = CTEngine(store=store, snapshot_interval=snapshot_interval)
        specs = None if spec is None \
            else {name: ensure_spec("CTSurrogate", spec)}
        if engine.restore(store, names=[name], specs=specs).get(name) is None:
            raise KeyError(f"durable store holds no tenant {name!r}")
        self = cls.__new__(cls)
        self._engine = engine
        self._name = name
        return self

    @property
    def engine(self):
        """The backing (possibly shared) ``CTEngine``."""
        return self._engine

    @property
    def scheme(self):
        return self._engine.scheme(self._name)

    @property
    def _plan(self):
        return self._engine.plan(self._name)

    @property
    def _ingest(self):
        """The signature-shared jitted ingest executable (exposed for
        retrace accounting in tests)."""
        return self._engine._tenant(self._name).executable

    @property
    def surplus(self) -> jnp.ndarray:
        """Sparse-grid surplus on the common fine grid (the served state)."""
        return self._engine.surplus(self._name)

    def update(self, nodal_grids) -> None:
        """Re-ingest new solver output (same scheme: no retrace)."""
        self._engine.update(self._name, nodal_grids)

    def refit(self, scheme, nodal_grids) -> None:
        """Swap in a (refined) scheme through the engine's incremental
        ``extend_plan`` path.  A failing ingest (e.g. ``nodal_grids``
        missing a grid of the new scheme) raises before any state
        mutates."""
        self._engine.refit(self._name, scheme, nodal_grids)

    def drop_grid(self, failed, nodal_grids) -> None:
        """Serving-side fault recovery: recombine without grid(s)
        ``failed`` (see ``repro.runtime.fault_tolerance.
        recombine_after_fault``).  ``nodal_grids`` must hold FINITE data
        for dropped grids (zeros suffice) — their recomputed coefficient
        is 0, so the stale values cancel out of the gather.  When the
        reduction activates a previously coefficient-0 grid (the classic
        (2,2)-drop case), ``nodal_grids`` must also supply that grid's
        data; a missing grid raises ``ValueError`` and leaves the
        surrogate unchanged.  On success later ``update`` calls recombine
        with the reduced coefficients (and keep tolerating the dead
        grids' stale entries in the dict); on a mesh the plan re-shards
        incrementally (untouched slab index maps reused by identity)."""
        self._engine.drop_grid(self._name, failed, nodal_grids)

    def query(self, points: np.ndarray) -> np.ndarray:
        """points: (Q, d) in [0,1]^d -> combined-interpolant values (Q,).

        Point dimensionality and dtype are validated HERE with a named
        error (not deep inside the jitted eval); Q is padded up to a
        power of two before dispatch so varying batch sizes compile once
        per bucket, not once per Q."""
        return self._engine.query(self._name, points)

    def submit_query(self, points, **kw):
        """Asynchronous ``query``: enqueue on the engine (thread-safe)
        and return the ``CTFuture``.  Accepts the engine's scheduling
        keywords (``deadline_ms=``, ``priority=``, ``block=``)."""
        return self._engine.submit_query(self._name, points, **kw)

    def submit_update(self, nodal_grids, **kw):
        """Asynchronous ``update``: enqueue an ingest on the engine
        (thread-safe) and return the ``CTFuture``."""
        return self._engine.submit_ingest(self._name, nodal_grids, **kw)


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    args = ap.parse_args(argv)
    sc = ServeConfig(arch=args.arch, max_new_tokens=args.max_new_tokens)
    cfg = get_smoke_config(args.arch)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32)
    out = generate(sc, prompts)
    print("generated:", out["tokens"].shape, "mean logprob:",
          float(out["logprobs"].mean()))


if __name__ == "__main__":
    main()
