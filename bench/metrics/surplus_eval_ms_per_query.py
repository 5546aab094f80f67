"""Device time of the eval program, per query, in milliseconds: the runs
of ``jit_interpolate_hierarchical`` (one surplus contracted against the
hat basis of every point its rows ask for) in the profiler trace, over
the queries the engine counted in the traced slice."""

from harness import trace as trc

EVAL_PROGRAM = r"^jit_interpolate_hierarchical\b"


def read(ctx):
    if ctx.trace is None:
        return None
    queries = ctx.counters_trace.get("eval.queries", 0)
    runs = trc.executions(ctx.trace, EVAL_PROGRAM)
    if queries <= 0 or not runs:
        return None
    return sum(r.dur_ns for r in runs) * 1e-6 / queries
