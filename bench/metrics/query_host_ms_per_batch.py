"""Host time of an eval batch, in milliseconds: the mean duration of the
``ct.query.batch`` spans whose midpoint lies in the traced slice, less
the ``ct.query.wait`` (``block_until_ready``) inside them."""

from harness import spans
from harness import trace as trc


def read(ctx):
    if ctx.trace is None:
        return None
    batches = spans.in_slice(ctx.trace,
                             spans.events(ctx.trace, "SPAN_QUERY_BATCH"))
    if not batches:
        return None
    waits = trc.inside(spans.events(ctx.trace, "SPAN_QUERY_WAIT"), batches)
    return (sum(e.dur_ns for e in batches)
            - sum(e.dur_ns for e in waits)) * 1e-6 / len(batches)
