"""Dense FFN: SwiGLU (llama family) or GELU MLP (whisper)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.layers.linear import linear_apply, linear_init
from repro.layers.sharding import PartitionCtx


def mlp_init(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.act == "silu":
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "w_gate": linear_init(k1, d, f, dtype=dtype),
            "w_up": linear_init(k2, d, f, dtype=dtype),
            "w_down": linear_init(k3, f, d, dtype=dtype, scale=1.0 / f**0.5),
        }
    k1, k2 = jax.random.split(key)
    return {
        "w_in": linear_init(k1, d, f, bias=True, dtype=dtype),
        "w_out": linear_init(k2, f, d, bias=True, dtype=dtype, scale=1.0 / f**0.5),
    }


@jax.named_scope("mlp")
def mlp_apply(params: dict, x: jax.Array, cfg: ModelConfig, pctx: PartitionCtx, *, training: bool = False) -> jax.Array:
    kw = dict(quant=cfg.quant, training=training)
    if "w_gate" in params:
        g = linear_apply(params["w_gate"], x, **kw)
        u = linear_apply(params["w_up"], x, **kw)
        g = pctx.shard(g, "batch", "seq", "ffn")
        h = (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)) * u
        return linear_apply(params["w_down"], h, **kw)
    h = linear_apply(params["w_in"], x, **kw)
    h = pctx.shard(h, "batch", "seq", "ffn")
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return linear_apply(params["w_out"], h, **kw)
