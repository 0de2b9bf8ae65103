"""The live epoch program's share of one chip's HBM bandwidth, in %: the
bytes it moves on a chip — its delta shard read and written (the
``bytes_projected`` attribute of the ``topology.epoch`` spans) plus the
shared base's (``bench/epoch_bytes.py``) — over its device time per call
(the trace) times the chip's published bandwidth (``bench/peaks.py``).
Nothing to read without the attribute, the program in the trace, or the
chip's peaks."""


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    spans = [s for s in ctx.spans_named("topology.epoch")
             if s.attr("bytes_projected") is not None]
    hit = ctx.trace.program("jit_topology_epoch")
    if not spans or hit is None or not hit[1]:
        return None
    moved = (sum(s.attr("bytes_projected") for s in spans) / len(spans)
             + ctx.counts.get("epoch_base_bytes", 0))
    per_call_s = hit[0] / hit[1]
    return 100.0 * moved / (per_call_s * ctx.peak["hbm_bytes_per_s"])
