"""Bytes output by admission's lane writes per session admitted inside the
window, in B: the ``bytes_written`` over the ``admitted`` of the
scheduler's ``sched.admit`` spans. Nothing to read where none was
admitted or the spans carry no such count."""


def read(ctx):
    admits = [s for s in ctx.spans_named("sched.admit")
              if s.attr("bytes_written") is not None]
    n = sum(int(s.attr("admitted", 0)) for s in admits)
    if not n:
        return None
    return sum(s.attr("bytes_written") for s in admits) / n
