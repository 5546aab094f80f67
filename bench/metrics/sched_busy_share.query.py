"""Share of the traced slice (the base is the slice) the scheduler
thread spent in passes that took work (``ct.sched.pass``), in
percent."""

from harness import spans


def read(ctx):
    return spans.slice_share(ctx.trace, "SPAN_SCHED_PASS",
                             present=(spans.ASLEEP,))
