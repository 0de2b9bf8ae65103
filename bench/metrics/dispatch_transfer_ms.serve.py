"""Host time of dispatch's host-to-device put of the staged buffers per
grid step, in ms: the ``dispatch.transfer`` children of the scheduler's
``sched.dispatch`` spans inside the window, over those dispatch spans.
Nothing to read where the program has no such span."""


def read(ctx):
    dispatches = ctx.spans_named("sched.dispatch")
    puts = [c for s in dispatches
            for c in ctx.children(s, "dispatch.transfer")]
    if not puts:
        return None
    return sum(c.dur_s for c in puts) / len(dispatches) * 1e3
