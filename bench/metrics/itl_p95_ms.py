"""95th percentile gap between consecutive output tokens of a stream, over
every gap of the window (``bench.harness.window.itl``)."""
from bench.harness import window


def read(ctx):
    gaps = window.itl(ctx.streams, ctx.t0, ctx.t1)
    return 1e3 * window.percentile(gaps, 95) if gaps else None
