"""Device time per decode-program run under the ``attention`` scope in the
traced slice: scores, softmax and weighted sum over the cache
(``bench.harness.layers``).  Layer: model."""
from bench.harness import layers


def read(ctx):
    return layers.decode_scope_ms(ctx.trace, "attention")
