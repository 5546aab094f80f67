"""Host-to-device time per ingest, in milliseconds: the summed
``ct.ingest.transfer`` spans (the ``jnp.asarray`` of every component
grid) inside ingest spans, over the number of ``ct.ingest`` spans, each
counted where its midpoint lies in the traced slice."""

from harness import spans
from harness import trace as trc


def read(ctx):
    if ctx.trace is None:
        return None
    ingests = spans.events(ctx.trace, "SPAN_INGEST")
    counted = spans.in_slice(ctx.trace, ingests)
    transfers = spans.in_slice(ctx.trace, trc.inside(
        spans.events(ctx.trace, "SPAN_INGEST_TRANSFER"), ingests))
    if not counted or not transfers:
        return None
    return sum(e.dur_ns for e in transfers) * 1e-6 / len(counted)
