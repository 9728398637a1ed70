"""Jitted wrapper for the paged decode attention kernel.

Accepts flat (B, H, D) queries, regroups to (B, Hkv, G, D), and dispatches
kernel vs the compiled jnp path.  Mirrors ``decode_attention.ops``:

``_paged_attention_streaming`` is the kernel-shaped jnp path — K/V pages
stay in their storage dtype and the dots accumulate in f32 via
``preferred_element_type``.  It gathers each sequence's pages into a dense
view first, so its HBM traffic is O(B * P * bs) like the contiguous
engine's; the Pallas kernel is the one that walks the block table directly
(scalar prefetch) and skips unallocated pages.  Because page ``i`` covers
positions ``[i*bs, (i+1)*bs)``, the gathered view places every valid token
at the same index the contiguous cache would — the two layouts are
numerically *identical* under the same mask, which the serving tests
exploit (paged vs contiguous token-for-token parity).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.decode_attention.ops import _decode_attention_streaming
from repro.kernels.paged_attention.kernel import (
    paged_decode_attention_pallas,
    paged_decode_attention_quant_pallas,
)
from repro.kernels.paged_attention.ref import gather_pages
from repro.quant.kv_quant import dequantize_kv

# Aliasing contract, audited by the `program` analysis pass: the page pool
# (and its scale planes) alias the persistent paged KV storage; the ops
# gather/stream but never write or return the pool — page installs happen in
# the donated program-level pool buffers (page_write / chunk programs).
CACHE_OPERANDS = {
    "paged_decode_attention": {"args": ("k_pages", "v_pages"), "writes": False},
    "gather_scales": {"args": ("scales",), "writes": False},
}


def gather_scales(scales: jax.Array, block_tables: jax.Array) -> jax.Array:
    """(N, Hkv, bs) scale planes + (B, P) tables -> dense (B, Hkv, P*bs)."""
    b, p = block_tables.shape
    n, hkv, bs = scales.shape
    g = scales[block_tables]  # (B, P, Hkv, bs)
    return jnp.moveaxis(g, 2, 1).reshape(b, hkv, p * bs)


def _paged_attention_streaming(
    q: jax.Array,  # (B, Hkv, G, D)
    k_pages: jax.Array,  # (N, Hkv, bs, D) — storage dtype, never upcast
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, P) int32
    lengths: jax.Array,
    starts: Optional[jax.Array],
    *,
    sm_scale: Optional[float] = None,
    return_stats: bool = False,
):
    # Gather the pages dense, then delegate to the contiguous streaming path
    # — ONE implementation of the masked-softmax/stats math, so the engine's
    # paged-vs-contiguous token parity cannot drift.
    k = gather_pages(k_pages, block_tables)  # (B, Hkv, P*bs, D)
    v = gather_pages(v_pages, block_tables)
    return _decode_attention_streaming(
        q, k, v, lengths, starts, sm_scale=sm_scale, return_stats=return_stats
    )


def paged_decode_attention(
    q: jax.Array,  # (B, H, D)
    k_pages: jax.Array,  # (N, Hkv, bs, D) — packed (N, Hkv, bs, Dp) when quantized
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, P) int32
    lengths: jax.Array,  # (B,) int32
    starts: Optional[jax.Array] = None,  # (B,) int32 — sliding-window start
    *,
    use_kernel: bool = False,
    sm_scale: Optional[float] = None,
    return_stats: bool = False,
    k_scales: Optional[jax.Array] = None,  # (N, Hkv, bs) f32 — quantized pool
    v_scales: Optional[jax.Array] = None,
    kv_dtype: str = "fp",
):
    """Attention of one query token per sequence over its paged KV.

    ``kv_dtype`` in {"int8", "int4"} (with ``k_scales``/``v_scales``) walks a
    *quantized* page pool: the kernel path DMAs packed pages and fuses
    dequant into the walk; the jnp path gathers the packed pages (cheap —
    1/2 or 1/4 the bytes of an fp gather), dequantizes the dense view, and
    delegates to the shared streaming math.

    ``return_stats=True`` additionally returns the online-softmax stats
    (l, m) of shape (B, H, 1) — in f32, with the output UN-astype'd — so the
    caller can merge further blocks (the freshly-projected token)."""
    b, h, d = q.shape
    hkv = k_pages.shape[1]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d)
    if kv_dtype != "fp":
        assert k_scales is not None and v_scales is not None, "quantized pool needs scales"
        if use_kernel:
            out, l, m = paged_decode_attention_quant_pallas(
                qg, k_pages, k_scales, v_pages, v_scales,
                block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
                None if starts is None else starts.astype(jnp.int32),
                kv_dtype=kv_dtype, interpret=interpret_mode(), sm_scale=sm_scale,
            )
            if return_stats:
                return (out.reshape(b, h, d),
                        l[:, :, :, :1].reshape(b, h, 1), m[:, :, :, :1].reshape(b, h, 1))
            return out.reshape(b, h, d).astype(q.dtype)
        k = dequantize_kv(gather_pages(k_pages, block_tables),
                          gather_scales(k_scales, block_tables), kv_dtype)
        v = dequantize_kv(gather_pages(v_pages, block_tables),
                          gather_scales(v_scales, block_tables), kv_dtype)
        ret = _decode_attention_streaming(
            qg, k, v, lengths, starts, sm_scale=sm_scale, return_stats=return_stats
        )
        if return_stats:
            out, l, m = ret
            return out.reshape(b, h, d), l.reshape(b, h, 1), m.reshape(b, h, 1)
        return ret.reshape(b, h, d)
    if not use_kernel:
        if return_stats:
            out, l, m = _paged_attention_streaming(
                qg, k_pages, v_pages, block_tables, lengths, starts,
                sm_scale=sm_scale, return_stats=True,
            )
            return out.reshape(b, h, d), l.reshape(b, h, 1), m.reshape(b, h, 1)
        out = _paged_attention_streaming(
            qg, k_pages, v_pages, block_tables, lengths, starts, sm_scale=sm_scale
        )
        return out.reshape(b, h, d)
    out, l, m = paged_decode_attention_pallas(
        qg, k_pages, v_pages, block_tables.astype(jnp.int32),
        lengths.astype(jnp.int32),
        None if starts is None else starts.astype(jnp.int32),
        interpret=interpret_mode(), sm_scale=sm_scale,
    )
    if return_stats:
        return (out.reshape(b, h, d),
                l[:, :, :, :1].reshape(b, h, 1), m[:, :, :, :1].reshape(b, h, 1))
    return out.reshape(b, h, d).astype(q.dtype)
