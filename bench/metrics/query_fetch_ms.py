"""Mean duration of the ``ct.query.fetch`` span, in milliseconds: one
answer's row sliced out of its batch and copied to the host, on the
thread that reads it, over the spans whose midpoint lies in the traced
slice."""

from harness import spans


def read(ctx):
    return spans.mean_ms(ctx.trace, "SPAN_QUERY_FETCH")
