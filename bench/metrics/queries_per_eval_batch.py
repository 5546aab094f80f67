"""Queries per batched eval dispatch over the window, from the engine's
own counters (``CTEngine.stats()["eval"]``): how much the continuous
batching coalesces."""


def read(ctx):
    batches = ctx.counters.get("eval.batches", 0)
    if batches <= 0:
        return None
    return ctx.counters["eval.queries"] / batches
