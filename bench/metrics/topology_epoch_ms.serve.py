"""Host time of a live DSST epoch, in ms: the scheduler's
``topology.epoch`` spans inside the window (the program's enqueue and the
install, which waits for the epoch's stats), per epoch. Nothing to read
where the window ran no epoch."""


def read(ctx):
    spans = ctx.spans_named("topology.epoch")
    if not spans:
        return None
    return sum(s.dur_s for s in spans) / len(spans) * 1e3
