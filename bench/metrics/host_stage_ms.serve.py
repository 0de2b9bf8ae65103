"""Host stage time per grid step, in ms: the self time of the scheduler's
``sched.stage`` spans, less their ``sched.admit`` and ``sched.poll_sources``
children, over the stage spans inside the window."""


def read(ctx):
    stages = ctx.spans_named("sched.stage")
    if not stages:
        return None
    own = sum(s.dur_s - sum(c.dur_s for name in ("sched.admit",
                                                 "sched.poll_sources")
                            for c in ctx.children(s, name))
              for s in stages)
    return own / len(stages) * 1e3
