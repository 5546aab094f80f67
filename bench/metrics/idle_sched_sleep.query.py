"""Share of the device-idle time of the traced slice (the base is the
idle time, not the slice) during which the scheduler slept
(``ct.sched.sleep``) and no feed span and no engine span's self time was
open on any thread: the third part of the idle partition in
``harness/spans.py``.  Higher means the host is not what holds the
device back."""

from harness import spans


def read(ctx):
    return spans.idle_part_share(ctx.trace, "asleep", (spans.ASLEEP,))
