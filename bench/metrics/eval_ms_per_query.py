"""Device time of the batched eval program plus the surplus stack that
feeds it, per query, in milliseconds (profiler trace; queries counted by
the engine's counters over the traced slice).  The stack runs as one
program per row (``broadcast_in_dim``, which also makes the zero rows)
and one ``concatenate``."""

from harness import trace as trc

EVAL_PROGRAMS = r"^jit_(interpolate_hierarchical|concatenate|broadcast_in_dim)\b"


def read(ctx):
    if ctx.trace is None:
        return None
    queries = ctx.counters_trace.get("eval.queries", 0)
    runs = trc.executions(ctx.trace, EVAL_PROGRAMS)
    if queries <= 0 or not runs:
        return None
    return sum(r.dur_ns for r in runs) * 1e-6 / queries
