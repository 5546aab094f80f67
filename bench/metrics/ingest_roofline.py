"""Share of the HBM roofline the ingest program reaches, in percent: the
least bytes of an ingest (every component grid read once, the fine-grid
surplus written once, from the scheme's level vectors) over the chip's
HBM bandwidth, divided by the device time of one run of the ingest
program (profiler trace)."""

from harness import roofline
from harness import trace as trc

INGEST_PROGRAM = r"^jit_ingest"


def read(ctx):
    if ctx.trace is None:
        return None
    runs = trc.executions(ctx.trace, INGEST_PROGRAM)
    if not runs:
        return None
    seconds = sum(r.dur_ns for r in runs) * 1e-9 / len(runs)
    return roofline.roofline_share(seconds, ctx.peaks,
                                   nbytes=ctx.sizes["least_ingest_bytes"])
