"""Host time per engine step in the window: the engine's t_step less t_wait
(its own block_until_ready calls), over steps.  Layer: engine host."""


def read(ctx):
    s = ctx.stats
    if not s.get("steps"):
        return None
    return 1e3 * (s["t_step"] - s["t_wait"]) / s["steps"]
