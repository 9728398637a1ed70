"""Streams per decode round in the window: the engine's slot_rounds over
decode_rounds.  Layer: scheduler."""


def read(ctx):
    s = ctx.stats
    return s["slot_rounds"] / s["decode_rounds"] if s["decode_rounds"] else None
