"""Share of the device-idle time of the traced slice (the base is the
idle time, not the slice) during which some host thread was inside a
feed span: ``ct.ingest.transfer``, ``ct.ingest.launch``,
``ct.query.stack``, ``ct.query.points`` or ``ct.query.launch``.  The
first part of the idle partition in ``harness/spans.py``."""

from harness import spans


def read(ctx):
    return spans.idle_part_share(ctx.trace, "feed", spans.FEED)
