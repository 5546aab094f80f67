"""Share of the HBM roofline the Pallas hierarchization kernels reach,
in percent: each member grid of the buckets the program sends to the
kernels read once and written once (float32, unpadded, from the
buckets' level vectors), over the chip's HBM bandwidth, divided by the
device time of the kernel events inside the ingest program.  Buckets on
the jnp path cannot be told apart in the trace and are not counted."""

from harness import roofline
from harness import trace as trc

INGEST_PROGRAM = r"^jit_ingest"
#: a Pallas kernel is a custom call in the compiled program; the ingest
#: program has no other
KERNEL_OP = r"custom-call\("


def read(ctx):
    if ctx.trace is None or ctx.sizes.get("hier_kernel_bytes", 0) <= 0:
        return None
    runs = trc.executions(ctx.trace, INGEST_PROGRAM)
    ops = trc.select(trc.inside(ctx.trace.ops(), runs), KERNEL_OP)
    if not ops:
        return None
    seconds = sum(e.dur_ns for e in ops) * 1e-9
    return roofline.roofline_share(
        seconds, ctx.peaks, nbytes=ctx.sizes["hier_kernel_bytes"] * len(runs))
