"""Device time of the training step program per run, in ms, from the
trace: the ``jit_step`` module's executions inside the traced stretch."""


def read(ctx):
    if ctx.trace is None:
        return None
    hit = ctx.trace.program("jit_step")
    if hit is None or not hit[1]:
        return None
    return hit[0] / hit[1] * 1e3
