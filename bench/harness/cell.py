"""Drive the system under test through one cell: set-up, window, check.

The program (``src/repro``) is used only through ``CTEngine``'s user
entry points (``register``, ``submit_ingest``, ``submit_query``,
``start``, ``flush``, ``stats``), its plan (to know which buckets the
Pallas kernels run) and its kernel names.  Everything else — the inputs,
the traffic, the reference, the arithmetic — is the benchmark's own.
"""

from __future__ import annotations

import math
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import data, scheme as sch, traffic as trf
from . import trace as trc

#: how long after the window closes an answer may still come
LATE_S = 60.0
#: points per readback query of a refresh cell's check; a refresh cell
#: reads every tenant back, with CHECK_QUERIES // 8 queries in all at
#: the least (64 for one tenant)
CHECK_POINTS = 64
#: most query answers a query cell's check compares (a seeded sample)
CHECK_QUERIES = 512

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def tenant_name(i: int) -> str:
    return f"t{i:04d}"


def _tpads(max_batch: int) -> List[int]:
    """Every batch padding the engine can pick: powers of two from 4 up
    to the one that holds ``max_batch``."""
    top = max(4, 1 << max(0, max_batch - 1).bit_length())
    out, t = [], 4
    while t <= top:
        out.append(t)
        t *= 2
    return out


class _CompileCounter:
    """Counts lowerings and backend compiles while armed."""

    def __init__(self):
        self.armed = False
        self.counts = {name: 0 for name in COMPILE_EVENTS}
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.armed and name in self.counts:
            self.counts[name] += 1

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)

    @property
    def compiles(self) -> int:
        return self.counts[COMPILE_EVENTS[0]]


@dataclass
class Window:
    """What the measured window saw."""

    kind: str                             # "refresh" | "query"
    t_open: float = 0.0
    t_close: float = 0.0                  # last completion
    attempted: int = 0
    failed: int = 0                       # raised or refused
    errors: List[str] = field(default_factory=list)
    missing: int = 0                      # never came
    latencies_s: List[float] = field(default_factory=list)
    lateness_s: List[float] = field(default_factory=list)
    compiles: int = 0
    counters_open: Dict[str, int] = field(default_factory=dict)
    counters_close: Dict[str, int] = field(default_factory=dict)
    counters_trace: Tuple[Dict[str, int], Dict[str, int]] = ({}, {})
    trace: Optional[trc.Trace] = None

    def fail(self, exc: BaseException, lock) -> None:
        """A request that raised or was refused: counted, and the first
        few kept for the run's log."""
        with lock:
            self.failed += 1
            if len(self.errors) < 5:
                self.errors.append(repr(exc))

    @property
    def completed(self) -> int:
        return len(self.latencies_s)

    @property
    def span_s(self) -> float:
        return self.t_close - self.t_open


class Cell:
    """One workload: a configuration under a traffic mix, from a seed."""

    def __init__(self, layout, workload: str, seed: int, seconds: float):
        self.layout = layout
        self.name = workload
        self.entry = layout.workload(workload)
        self.cfg = layout.config(self.entry["config"])
        self.mix = layout.traffic(self.entry["traffic"])
        self.seed, self.seconds = seed, float(seconds)
        self.grids = sch.scheme_grids(self.cfg["scheme"])
        self.dim = len(self.grids[0][0])
        self.n_tenants = int(self.cfg["tenants"])
        self.kind = trf.block_of(self.mix)
        self.engine = None
        self.sets: List[List[dict]] = []
        self.initial: List[dict] = []         # each tenant's registered set
        self.last: List[int] = []
        self.answers: Dict[int, np.ndarray] = {}
        self.sample: np.ndarray = np.zeros(0, int)
        self.schedule: Optional[trf.QuerySchedule] = None

    # -- inputs --------------------------------------------------------------

    def make_inputs(self) -> None:
        """Host grids of every tenant's ring and the query schedule, all
        drawn from the seed.  A refresh cell registers each tenant with
        a grid set of its own, outside the ring, so a refresh that leaves
        the state unchanged never reads as current, whichever ring set
        came last."""
        rng = np.random.default_rng(self.seed)
        refresh = self.kind == "refresh"
        ring = int(self.mix["refresh"]["ring"]) if refresh else 1
        per = ring + refresh
        n = self.n_tenants * per
        params = data.field_params(rng, n, self.dim, self.cfg["field"])
        fields = data.split(data.make_grids([l for l, _ in self.grids],
                                            params), n)
        self.sets = [fields[t * per:t * per + ring]
                     for t in range(self.n_tenants)]
        self.initial = [fields[t * per + per - 1]
                        for t in range(self.n_tenants)]
        # ring index last ingested; -1: the registration set
        self.last = [-1 if refresh else 0] * self.n_tenants
        if self.kind == "query":
            self.new_schedule(rng)
        self.check_rng = np.random.default_rng(rng.integers(1 << 62))

    def new_schedule(self, rng: np.random.Generator) -> None:
        """Draw the window's queries (and the sample the check compares)."""
        self.schedule = trf.query_schedule(
            self.mix["query"], self.seconds, self.n_tenants, self.dim, rng)
        self.answers = {}
        n_q = len(self.schedule.offsets)
        self.sample = np.sort(rng.choice(
            n_q, size=min(n_q, CHECK_QUERIES), replace=False))

    # -- set-up --------------------------------------------------------------

    def setup(self) -> Dict[str, float]:
        """Build the engine, register every tenant (its first ingest) and
        warm every shape the window uses.  Returns phase timings."""
        import jax
        from repro.core.engine import CTEngine, ExecSpec
        from repro.core.levels import CombinationScheme

        t0 = time.perf_counter()
        self.make_inputs()
        t1 = time.perf_counter()
        sc = self.cfg["scheme"]
        scheme = CombinationScheme(int(sc["dim"]), int(sc["level"]))
        self.engine = CTEngine(ExecSpec(**self.cfg["exec"]),
                               **self.cfg["engine"])
        for t in range(self.n_tenants):
            self.engine.register(tenant_name(t), scheme, self.initial[t])
        jax.block_until_ready([self.engine.surplus(tenant_name(t))
                               for t in range(self.n_tenants)])
        t2 = time.perf_counter()
        if self.kind == "query":
            self._warm_queries()
        self.engine.start()
        if self.kind == "refresh":
            self._refresh_once(0)
        t3 = time.perf_counter()
        return {"inputs_s": t1 - t0, "register_s": t2 - t1,
                "warm_s": t3 - t2}

    def _refresh_once(self, t: int) -> None:
        """One refresh of tenant ``t`` with the next grid set of its ring,
        through the scheduler and the ingest pool as the window's are."""
        import jax
        r = (self.last[t] + 1) % len(self.sets[t])
        fut = self.engine.submit_ingest(tenant_name(t), self.sets[t][r])
        if not fut.wait(LATE_S):
            raise TimeoutError(f"refresh of {tenant_name(t)} never came")
        jax.block_until_ready(fut.result())
        self.last[t] = r

    def _warm_queries(self) -> None:
        """Run one batch at every padding the window can reach, full and
        one short (so the zero rows are warmed too), and read every row
        back as the window's clients do."""
        q = int(self.mix["query"]["points"])
        pts = np.full((q, self.dim), 0.5, np.float32)
        max_batch = int(self.cfg["engine"].get("max_batch", 32))
        for tpad in _tpads(max_batch):
            for n in sorted({max(1, tpad // 2 + 1), min(tpad, max_batch)}):
                futs = [self.engine.submit_query(tenant_name(i % self.n_tenants), pts)
                        for i in range(n)]
                self.engine.flush()
                for f in futs:
                    f.result()

    # -- the window ------------------------------------------------------------

    def run_window(self, trace: bool = False,
                   trace_parent: Optional[str] = None) -> Window:
        w = Window(kind=self.kind)
        counter = _CompileCounter()
        w.counters_open = self._counters()
        threads: List[threading.Thread] = []
        counter.armed = True
        w.t_open = time.perf_counter()
        stop_at = w.t_open + self.seconds
        lock = threading.Lock()
        if self.kind == "refresh":
            for block in trf.client_blocks(self.n_tenants,
                                           int(self.mix["refresh"]["clients"])):
                threads.append(threading.Thread(
                    target=self._client, args=(block, stop_at, w, lock),
                    name="bench-client", daemon=True))
        else:
            inbox: "queue.Queue" = queue.Queue()
            threads.append(threading.Thread(
                target=self._generator, args=(w.t_open, w, inbox, lock),
                name="bench-generator", daemon=True))
            threads.append(threading.Thread(
                target=self._collector, args=(stop_at, w, inbox, lock),
                name="bench-collector", daemon=True))
        for th in threads:
            th.start()
        captured = None
        if trace:
            captured = self._trace_slice(w, trace_parent)
        for th in threads:
            th.join(timeout=self.seconds + 2 * LATE_S)
            if th.is_alive():
                raise RuntimeError(f"{th.name} did not finish")
        counter.armed = False
        counter.close()
        w.compiles = counter.compiles
        w.counters_close = self._counters()
        if captured is not None:
            try:
                w.trace = trc.load(captured["path"])
            finally:
                trc.discard(captured)
        return w

    def _trace_slice(self, w: Window, parent: Optional[str]):
        import jax
        at = w.t_open + 0.3 * self.seconds
        length = min(2.0, 0.4 * self.seconds)
        time.sleep(max(0.0, at - time.perf_counter()))
        with trc.capture(parent) as captured:
            before = self._counters()
            with jax.profiler.TraceAnnotation(trc.WINDOW_SPAN):
                time.sleep(length)
            after = self._counters()
        w.counters_trace = (before, after)
        return captured

    def _counters(self) -> Dict[str, int]:
        s = self.engine.stats()
        return {"eval.queries": s["eval"]["queries"],
                "eval.batches": s["eval"]["batches"],
                "ingests": s["ingests"]}

    def _client(self, block, stop_at, w: Window, lock) -> None:
        """A closed-loop solver: refresh its tenants round-robin until the
        window closes."""
        import jax
        i = 0
        while time.perf_counter() < stop_at:
            t = block[i % len(block)]
            i += 1
            r = (self.last[t] + 1) % len(self.sets[t])
            t0 = time.perf_counter()
            with lock:
                w.attempted += 1
            try:
                fut = self.engine.submit_ingest(tenant_name(t), self.sets[t][r],
                                                block=False)
            except Exception as exc:
                w.fail(exc, lock)
                continue
            if not fut.wait(max(0.0, stop_at + LATE_S - time.perf_counter())):
                with lock:
                    w.missing += 1
                return
            try:
                jax.block_until_ready(fut.result())
            except Exception as exc:
                w.fail(exc, lock)
                continue
            t1 = time.perf_counter()
            self.last[t] = r
            with lock:
                w.latencies_s.append(t1 - t0)
                w.t_close = max(w.t_close, t1)

    def _generator(self, t_open, w: Window, inbox, lock) -> None:
        """Open loop: submit each query when it is due, whatever is
        pending."""
        s = self.schedule
        dl = s.deadline_ms if s.deadline_ms is not None else math.inf
        try:
            for i, off in enumerate(s.offsets):
                due = t_open + off
                delay = due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                late = time.perf_counter() - due
                with lock:
                    w.attempted += 1
                    w.lateness_s.append(max(0.0, late))
                try:
                    fut = self.engine.submit_query(
                        tenant_name(int(s.tenants[i])), s.points[i],
                        deadline_ms=dl, block=False)
                except Exception as exc:
                    w.fail(exc, lock)
                    continue
                inbox.put((i, due, fut))
        finally:
            inbox.put(None)

    def _collector(self, stop_at, w: Window, inbox, lock) -> None:
        """Read every answer back to the host, in arrival order; a query
        is timed from when it was due until its answer is on the host."""
        keep = set(self.sample.tolist())
        while True:
            item = inbox.get()
            if item is None:
                return
            i, due, fut = item
            if not fut.wait(max(0.0, stop_at + LATE_S - time.perf_counter())):
                with lock:
                    w.missing += 1
                continue
            try:
                ans = fut.result()
            except Exception as exc:
                w.fail(exc, lock)
                continue
            t1 = time.perf_counter()
            if i in keep:
                self.answers[i] = np.asarray(ans)
            with lock:
                w.latencies_s.append(t1 - due)
                w.t_close = max(w.t_close, t1)

    # -- after the window ------------------------------------------------------

    def readback(self) -> List[Tuple[int, np.ndarray, np.ndarray]]:
        """``(tenant, points, served answer)`` to compare: the sampled
        answers of the window (query cells), or, for refresh cells, every
        tenant's surplus read back through ``submit_query`` at seeded
        points."""
        if self.kind == "query":
            s = self.schedule
            return [(int(s.tenants[i]), s.points[i], self.answers.get(i))
                    for i in self.sample.tolist()]
        self.engine.stop(drain=True)
        per_tenant = max(1, CHECK_QUERIES // 8 // self.n_tenants)
        asks = [(t, self.check_rng.random((CHECK_POINTS, self.dim),
                                          dtype=np.float32))
                for t in range(self.n_tenants) for _ in range(per_tenant)]
        futs = [self.engine.submit_query(tenant_name(t), pts,
                                         deadline_ms=math.inf)
                for t, pts in asks]
        self.engine.flush()
        out = []
        for (t, pts), f in zip(asks, futs):
            try:
                ans = np.asarray(f.result(timeout=LATE_S))
            except Exception:
                ans = None
            out.append((t, pts, ans))
        return out

    def tenant_grids(self, t: int) -> List[Tuple[tuple, float, np.ndarray]]:
        """The grids tenant ``t`` last ingested, as the reference takes them."""
        g = (self.initial[t] if self.last[t] < 0
             else self.sets[t][self.last[t]])
        return [(ell, c, g[ell]) for ell, c in self.grids]

    def pallas_member_levels(self) -> List[tuple]:
        """Member level vectors of the buckets the program's plan sends to
        the Pallas kernels (kernel routing is the program's; the bytes
        are counted here from the level vectors)."""
        from repro.kernels.hierarchize import batched_method
        plan = self.engine.plan(tenant_name(0))
        base = getattr(plan, "plan", plan)
        return [lv for b in base.buckets
                if batched_method(b.shape) == "pallas" for lv in b.levels]

    def close(self) -> None:
        if self.engine is not None:
            self.engine.close()
            self.engine = None
