"""Host time of admission's lane writes per session admitted inside the
window, in ms: the ``admit.write`` children of the scheduler's
``sched.admit`` spans over the sessions those spans admitted (their
``admitted`` attribute). Nothing to read where none was admitted or the
program has no such span."""


def read(ctx):
    admits = [s for s in ctx.spans_named("sched.admit")
              if s.attr("admitted", 0)]
    writes = [c for s in admits for c in ctx.children(s, "admit.write")]
    if not writes:
        return None
    n = sum(int(s.attr("admitted")) for s in admits)
    return sum(c.dur_s for c in writes) / n * 1e3
