"""Prefill time per thousand prompt tokens in the window: the engine's
t_prefill over its prefill_tokens.  Layer: model step."""


def read(ctx):
    s = ctx.stats
    return 1e6 * s["t_prefill"] / s["prefill_tokens"] if s["prefill_tokens"] else None
