"""Share of the traced stretch in which no operation ran on the device,
in %: one less the union of device-op intervals over the stretch."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * ctx.trace.idle_share
