"""Output tokens handed to clients in the window, over the window."""
from bench.harness import window


def read(ctx):
    return window.tokens(ctx.streams, ctx.t0, ctx.t1) / (ctx.t1 - ctx.t0)
