"""Device time of the live epoch program per call, in ms, from the trace:
the ``jit_topology_epoch`` module's executions inside the traced stretch,
per chip. Nothing to read where the stretch holds no such program."""


def read(ctx):
    if ctx.trace is None:
        return None
    hit = ctx.trace.program("jit_topology_epoch")
    if hit is None or not hit[1]:
        return None
    return hit[0] / hit[1] * 1e3
