"""Host retire time per grid step, in ms: the scheduler's ``sched.retire``
spans less their ``sched.device_wait`` child, over the retire spans inside
the window."""


def read(ctx):
    retires = ctx.spans_named("sched.retire")
    if not retires:
        return None
    own = sum(s.dur_s - sum(c.dur_s for c in
                            ctx.children(s, "sched.device_wait"))
              for s in retires)
    return own / len(retires) * 1e3
