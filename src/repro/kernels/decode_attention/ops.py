"""Jitted wrapper for the decode attention kernel.

Accepts flat (B, H, D) queries, regroups to (B, Hkv, G, D), pads the cache
length to the KV block, and dispatches kernel vs oracle.

``_decode_attention_streaming`` is the compiled jnp path (kernel-shaped
dataflow): K/V stay in their storage dtype and the dots accumulate in f32
via ``preferred_element_type`` — the MXU semantics of the Pallas kernel.
The f32-upcast ``decode_attention_reference`` stays the max-precision
oracle for the kernel tests.  [§Perf iteration D1: the upcast version made
XLA hoist a full-cache f32 convert out of the layer scan — a whole-cache
HBM copy (2x KV bytes write + read) and a 2x peak-memory spike.]
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas,
    decode_attention_quant_pallas,
)
from repro.kernels.decode_attention.ref import decode_attention_reference
from repro.quant.kv_quant import dequantize_kv

# Aliasing contract, audited by the `program` analysis pass
# (repro.analysis.progcheck): these operands alias the persistent KV cache,
# and the op never writes or returns them — cache mutation belongs to the
# DONATED program-level buffers (layers/attention.py scatter writers), never
# to kernel entry points.
CACHE_OPERANDS = {
    "decode_attention": {"args": ("k", "v"), "writes": False},
}


def _decode_attention_streaming(
    q: jax.Array,  # (B, Hkv, G, D)
    k: jax.Array,  # (B, Hkv, S, D) — storage dtype (bf16/f32), never upcast
    v: jax.Array,
    lengths: jax.Array,
    starts: Optional[jax.Array],
    *,
    sm_scale: Optional[float] = None,
    return_stats: bool = False,
):
    b, hkv, g, d = q.shape
    s = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if starts is None:
        starts = jnp.zeros_like(lengths)
    scores = jnp.einsum(
        "bhgd,bhsd->bhgs", q.astype(k.dtype), k, preferred_element_type=jnp.float32
    ) * sm_scale
    pos = jnp.arange(s)[None, :]
    mask = (pos < lengths[:, None]) & (pos >= starts[:, None])  # (B, S)
    mask4 = mask[:, None, None, :]
    scores = jnp.where(mask4, scores, -1e30)
    m = jnp.max(scores, axis=-1, keepdims=True)  # (B,Hkv,G,1); -1e30 if empty
    p = jnp.where(mask4, jnp.exp(scores - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(
        "bhgs,bhsd->bhgd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    ) / jnp.maximum(l, 1e-30)
    if return_stats:
        return out, l, m
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,  # (B, H, D)
    k: jax.Array,  # (B, Hkv, S, D) — or packed payload (B, Hkv, S, Dp) when quantized
    v: jax.Array,
    lengths: jax.Array,  # (B,) int32
    starts: Optional[jax.Array] = None,  # (B,) int32 — sliding-window start
    *,
    bk: int = 512,
    use_kernel: bool = False,
    sm_scale: Optional[float] = None,
    return_stats: bool = False,
    k_scales: Optional[jax.Array] = None,  # (B, Hkv, S) f32 — quantized cache
    v_scales: Optional[jax.Array] = None,
    kv_dtype: str = "fp",
):
    """Attention of one query token per sequence over a masked KV cache.

    ``kv_dtype`` in {"int8", "int4"} (with ``k_scales``/``v_scales``) reads a
    *quantized* cache: the kernel path streams the packed payload and fuses
    dequant into the KV walk; the jnp path dequantizes then delegates (the
    oracle dataflow — it materializes the fp cache the kernel avoids).

    ``return_stats=True`` additionally returns the online-softmax stats
    (l, m) of shape (B, H, 1) — in f32, with the output UN-astype'd — so the
    caller can merge further blocks (e.g. the freshly-projected token)."""
    b, h, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    if kv_dtype != "fp":
        assert k_scales is not None and v_scales is not None, "quantized cache needs scales"
        if use_kernel:
            g = h // hkv
            out, l, m = decode_attention_quant_pallas(
                q.reshape(b, hkv, g, d), k, k_scales, v, v_scales,
                lengths.astype(jnp.int32),
                None if starts is None else starts.astype(jnp.int32),
                kv_dtype=kv_dtype, bk=bk, interpret=interpret_mode(), sm_scale=sm_scale,
            )
            if return_stats:
                return (out.reshape(b, h, d),
                        l[:, :, :, :1].reshape(b, h, 1), m[:, :, :, :1].reshape(b, h, 1))
            return out.reshape(b, h, d).astype(q.dtype)
        k = dequantize_kv(k, k_scales, kv_dtype)
        v = dequantize_kv(v, v_scales, kv_dtype)
    g = h // hkv
    qg = q.reshape(b, hkv, g, d)
    if not use_kernel:
        if return_stats:
            out, l, m = _decode_attention_streaming(
                qg, k, v, lengths, starts, sm_scale=sm_scale, return_stats=True
            )
            return out.reshape(b, h, d), l.reshape(b, h, 1), m.reshape(b, h, 1)
        out = _decode_attention_streaming(qg, k, v, lengths, starts, sm_scale=sm_scale)
        return out.reshape(b, h, d)
    # the kernel clamps bk to the cache and pads any partial final block
    out, l, m = decode_attention_pallas(
        qg, k, v, lengths.astype(jnp.int32), None if starts is None else starts.astype(jnp.int32),
        bk=bk, interpret=interpret_mode(), sm_scale=sm_scale
    )
    if return_stats:
        return (out.reshape(b, h, d),
                l[:, :, :, :1].reshape(b, h, 1), m[:, :, :, :1].reshape(b, h, 1))
    return out.reshape(b, h, d).astype(q.dtype)
