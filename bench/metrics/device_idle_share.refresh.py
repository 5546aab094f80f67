"""Share of the traced window in which no operation ran on the device,
in percent: 1 - (union of the device-busy intervals) / window."""

from harness import trace as trc


def read(ctx):
    if ctx.trace is None:
        return None
    return trc.idle_share(ctx.trace)
