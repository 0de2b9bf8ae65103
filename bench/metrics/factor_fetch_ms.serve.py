"""Host time of the DSST factors' fetch and fold per grid step, in ms:
the ``retire.factors`` children of the scheduler's ``sched.retire`` spans
inside the window, over those retire spans. Nothing to read where the
program has no such span."""


def read(ctx):
    retires = ctx.spans_named("sched.retire")
    fetches = [c for s in retires for c in ctx.children(s, "retire.factors")]
    if not fetches:
        return None
    return sum(c.dur_s for c in fetches) / len(retires) * 1e3
