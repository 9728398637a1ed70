"""RMSNorm / LayerNorm (fp32 statistics, cast back to input dtype)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rmsnorm_init(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def layernorm_init(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def norm_init(kind: str, d: int, dtype=jnp.float32) -> dict:
    return rmsnorm_init(d, dtype) if kind == "rmsnorm" else layernorm_init(d, dtype)


@jax.named_scope("norm")
def apply_norm(params: dict, x: jax.Array, kind: str = "rmsnorm", eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * params["scale"].astype(jnp.float32)
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps)
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)
