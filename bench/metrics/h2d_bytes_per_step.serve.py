"""Bytes put on the device per grid step, in B: the mean ``h2d_bytes`` of
the scheduler's ``sched.dispatch`` spans inside the window. Nothing to
read where the spans carry no such count."""


def read(ctx):
    counts = [s.attr("h2d_bytes") for s in ctx.spans_named("sched.dispatch")]
    counts = [c for c in counts if c is not None]
    if not counts:
        return None
    return sum(counts) / len(counts)
