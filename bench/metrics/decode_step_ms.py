"""Mean decode round in the window: the engine's host-clock time around
each round (ending in ``block_until_ready``) over the rounds.  Layer:
model step."""


def read(ctx):
    s = ctx.stats
    return 1e3 * s["t_decode"] / s["decode_rounds"] if s["decode_rounds"] else None
