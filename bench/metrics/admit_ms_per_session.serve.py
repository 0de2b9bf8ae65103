"""Admission time per session admitted inside the window, in ms: the
scheduler's ``sched.admit`` spans over the sessions they admitted (their
``admitted`` attribute). Nothing to read where none was admitted."""


def read(ctx):
    admits = ctx.spans_named("sched.admit")
    n = sum(int(s.attr("admitted", 0)) for s in admits)
    if not n:
        return None
    return sum(s.dur_s for s in admits) / n * 1e3
