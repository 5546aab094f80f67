"""Device time of the scatter-adds inside the ingest program, per
ingest, in milliseconds (profiler trace).

XLA fuses each bucket's scatter-add into a fusion whose output is the
flat fine-grid buffer and which reads the bucket's s32 index map; the
sort XLA runs on those indices before a scatter is keyed by s32 too.
Both are counted; the buffer size comes from the scheme."""

import re

from harness import trace as trc

INGEST_PROGRAM = r"^jit_ingest"
WRITES_BUFFER = re.compile(r"%\S+ = f32\[(\d+)\]\S* (?:fusion|scatter)\((.*)")
INDEX_SORT = re.compile(r"%sort\S* = \(s32\[")


def is_scatter(name: str, fine_points: int) -> bool:
    m = WRITES_BUFFER.match(name)
    if m:
        return (int(m.group(1)) in (fine_points, fine_points + 1)
                and "s32[" in m.group(2))
    return INDEX_SORT.match(name) is not None


def read(ctx):
    if ctx.trace is None:
        return None
    runs = trc.executions(ctx.trace, INGEST_PROGRAM)
    if not runs:
        return None
    fine = ctx.sizes["fine_points"]
    ops = [e for e in trc.inside(ctx.trace.ops(), runs)
           if is_scatter(e.name, fine)]
    if not ops:
        return None
    return sum(e.dur_ns for e in ops) * 1e-6 / len(runs)
