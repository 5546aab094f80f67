"""Evals per eval chunk: the runs of ``jit_interpolate_hierarchical``
(one per distinct surplus a chunk reads, each under a ``ct.query.eval``
span) in the profiler trace, over the chunks the engine counted in the
traced slice (``stats()["eval"]["batches"]``).  It is the number of
distinct tenants a chunk holds, at most the tenant count."""

from harness import trace as trc

EVAL_PROGRAM = r"^jit_interpolate_hierarchical\b"


def read(ctx):
    if ctx.trace is None:
        return None
    batches = ctx.counters_trace.get("eval.batches", 0)
    runs = trc.executions(ctx.trace, EVAL_PROGRAM)
    if batches <= 0 or not runs:
        return None
    return len(runs) / batches
