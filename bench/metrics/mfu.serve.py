"""Model FLOP/s of the stream-timesteps retired in the window, as a share
of the chips' bf16 peak, in %. Model FLOPs per timestep come from the
configuration's shapes (``bench/flops.py``)."""


def read(ctx):
    c = ctx.counts
    if ctx.peak is None or not c.get("timesteps"):
        return None
    rate = c["timesteps"] * c["flops_per_timestep"] / c["window_s"]
    return 100.0 * rate / (ctx.chips * ctx.peak["bf16_flops"])
