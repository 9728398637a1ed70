"""Linear layers: dense bf16 and TLMM-backed ternary (the paper's static region).

Three execution regimes, all sharing one param layout:

* ``bf16``            — plain matmul on latent weights.
* ``ternary`` (train) — BitNet QAT: STE ternary weights + STE int8 acts.
* ``ternary`` (infer) — weights converted once to :class:`TernaryWeight`
                        (2-bit packed) and multiplied by the TLMM op; this is
                        the "static region" engine shared by both phases.
                        Latent weights given at inference are quantized in
                        every call instead (the same numbers, far more bytes).

The param dict is {"w": (K, N)} (+"b") for latent weights, or
{"w": TernaryWeight} after ``models.transformer.convert_for_inference``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import QuantConfig
from repro.kernels.tlmm.ops import tlmm_matmul
from repro.kernels.tlmm.ref import scale_epilogue
from repro.quant.act_quant import quantize_activations_int8
from repro.quant.ternary import TernaryWeight, ternary_quantize, ternary_quantize_ste


def linear_init(key, k: int, n: int, *, bias: bool = False, dtype=jnp.bfloat16, scale: Optional[float] = None) -> dict:
    if scale is None:
        scale = 1.0 / (k**0.5)
    p = {"w": (jax.random.normal(key, (k, n), jnp.float32) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((n,), dtype)
    return p


def _act_fake_quant_ste(x: jax.Array) -> jax.Array:
    x_q, scale = quantize_activations_int8(x)
    deq = (x_q.astype(jnp.float32) * scale).astype(x.dtype)
    return x + jax.lax.stop_gradient(deq - x)


@jax.named_scope("linear")
def linear_apply(
    params: dict,
    x: jax.Array,
    quant: QuantConfig,
    *,
    training: bool = False,
) -> jax.Array:
    w = params["w"]
    if isinstance(w, TernaryWeight):
        # inference TLMM path (packed 2-bit weights): the compiled kernel
        # wherever Mosaic compiles it, the reference on the CPU
        y = tlmm_matmul(x, w, out_dtype=x.dtype)
    elif quant.ternary:
        if training:
            # BitNet QAT: STE through both weight and activation quantizers
            w_ste, _ = ternary_quantize_ste(w.astype(jnp.float32))
            y = _act_fake_quant_ste(x).astype(jnp.float32) @ w_ste
            y = y.astype(x.dtype)
        else:
            # unconverted ternary inference: quantize on the fly (slow path)
            with jax.named_scope("act_quant"):
                x_q, s = quantize_activations_int8(x.reshape(-1, x.shape[-1]))
            with jax.named_scope("weight_quant"):
                w_q, beta = ternary_quantize(w.astype(jnp.float32))
            acc = jax.lax.dot_general(
                x_q, w_q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32,
            )
            y = scale_epilogue(acc, s * beta, x.dtype).reshape(*x.shape[:-1], w.shape[1])
    else:
        y = x @ w.astype(x.dtype)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y
