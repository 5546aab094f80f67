"""Mean duration of the engine's ingest span (``ct.ingest``: one refresh
on its ingest-pool thread, from dispatch through the commit, the
watermark advance and the future set), in milliseconds, over the spans
whose midpoint lies in the traced slice.  Against the client's latency
it says how much of a refresh is queue and hand-off."""

from harness import spans


def read(ctx):
    return spans.mean_ms(ctx.trace, "SPAN_INGEST")
