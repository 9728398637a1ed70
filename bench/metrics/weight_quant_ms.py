"""Device time per decode-program run under the ``weight_quant`` scope in
the traced slice: the on-the-fly ternary quantization of the latent weights
(``bench.harness.layers``); 0.0 once no operation carries the scope.
Layer: model."""
from bench.harness import layers


def read(ctx):
    return layers.decode_scope_ms(ctx.trace, "weight_quant", gone=0.0)
