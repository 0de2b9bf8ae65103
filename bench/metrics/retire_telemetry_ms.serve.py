"""Host time of retire's counter fold per grid step, in ms: the
``retire.telemetry`` children of the scheduler's ``sched.retire`` spans
inside the window, over those retire spans. Nothing to read where the
program has no such span."""


def read(ctx):
    retires = ctx.spans_named("sched.retire")
    folds = [c for s in retires for c in ctx.children(s, "retire.telemetry")]
    if not folds:
        return None
    return sum(c.dur_s for c in folds) / len(retires) * 1e3
