"""The whole decode round's share of the chip's roofline, in percent: the
least time the window's decode rounds could take (the work that the
configuration's family counts from the window's counters: weights at the
stored precision the configuration states, each round's cached KV at the
cell's KV type, operations at the chip's published peaks) over the engine's
t_decode.  Layer: model."""
from bench.harness import spec


def read(ctx):
    s = ctx.stats
    if ctx.peak is None or not s["decode_rounds"]:
        return None
    work = spec.family(ctx.config).decode(ctx.config, s, ctx.engine.get("kv_dtype", "fp"))
    return 100.0 * work.seconds(ctx.peak) / s["t_decode"]
