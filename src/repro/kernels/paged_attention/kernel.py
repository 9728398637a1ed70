"""Pallas TPU kernel: paged decode attention (decode RM over a block pool).

Same dataflow as ``repro.kernels.decode_attention.kernel`` — pinned Q tile,
two independent K/V HBM->VMEM streams, single output writeback after the KV
walk — but the cache walked is a *page pool* ``(num_blocks, Hkv, bs, D)``
instead of a dense per-sequence buffer.  The per-sequence block table is a
scalar-prefetch operand, so the K/V BlockSpec index maps resolve
``pages[table[b, t]]`` *before* each grid step's DMA is issued: the kernel
streams exactly the pages a sequence owns, in table order, and never touches
the rest of the pool.

Pages past a sequence's length are skipped entirely (``pl.when`` guard —
their table entries are 0/garbage and their DMA result is never read), which
is what makes ragged continuous batching pay O(actual length), not
O(max_len), in both bandwidth and pool capacity — the paper's Eq. (5)
decode bound with ``context = actual`` rather than ``context = max``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention.kernel import (
    _quant_softmax_step,
    merge_out_planes,
    split_q_planes,
)

NEG_INF = -1e30


def _paged_decode_kernel(
    tables_ref,  # scalar-prefetch: (B, P) int32 — per-sequence page table
    start_ref,  # scalar-prefetch: (B,) int32 — window start (0 for full attn)
    len_ref,  # scalar-prefetch: (B,) int32
    q_ref,  # (1, 1, G, D)
    k_ref,  # (1, 1, bs, D) — page tables_ref[b, t] of this (layer-sliced) pool
    v_ref,  # (1, 1, bs, D)
    out_ref,  # (1, 1, G, D)
    out_l_ref,  # (1, 1, G, 128) — softmax denominator (stats output)
    out_m_ref,  # (1, 1, G, 128) — running max (stats output)
    m_ref,
    l_ref,
    acc_ref,
    *,
    bs: int,
    n_pages: int,
    sm_scale: float,
):
    b = pl.program_id(0)
    t = pl.program_id(2)
    length = len_ref[b]
    start = start_ref[b]

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Pages wholly outside [start, length) are unallocated (or dead window
    # prefix): their table entries are meaningless and their block is never
    # read — the walk skips them.
    @pl.when(jnp.logical_and(t * bs < length, (t + 1) * bs > start))
    def _step():
        q = q_ref[...].astype(jnp.float32)[0, 0]  # (G, D)
        k = k_ref[...].astype(jnp.float32)[0, 0]  # (bs, D)
        v = v_ref[...].astype(jnp.float32)[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * sm_scale  # (G, bs)
        pos = t * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        s = jnp.where(jnp.logical_and(pos >= start, pos < length), s, NEG_INF)

        m_prev = m_ref[...][:, :1]
        l_prev = l_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = jnp.broadcast_to(alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ()))
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(t == n_pages - 1)  # single writeback after the page walk
    def _finalize():
        l = l_ref[...][:, :1]
        out_ref[...] = (acc_ref[...] / jnp.maximum(l, 1e-30))[None, None].astype(out_ref.dtype)
        out_l_ref[...] = l_ref[...][None, None]
        out_m_ref[...] = m_ref[...][None, None]


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def paged_decode_attention_pallas(
    q: jax.Array,  # (B, Hkv, G, D) — query heads grouped by KV head
    k_pages: jax.Array,  # (N, Hkv, bs, D) — one layer's page pool
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, P) int32 — page ids per sequence
    lengths: jax.Array,  # (B,) int32 — per-sequence valid cache length
    starts: jax.Array | None = None,  # (B,) int32 — window start (default 0)
    *,
    sm_scale: float | None = None,
    interpret: bool = False,
):
    b, hkv, g, d = q.shape
    n, hkv_p, bs, d_p = k_pages.shape
    assert (hkv_p, d_p) == (hkv, d), (k_pages.shape, q.shape)
    n_pages = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    if starts is None:
        starts = jnp.zeros_like(lengths)
    kernel = functools.partial(_paged_decode_kernel, bs=bs, n_pages=n_pages, sm_scale=sm_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, n_pages),
        # K/V index maps dereference the prefetched block table: grid step
        # (bi, hi, ti) DMAs page tables[bi, ti] of head hi's pool slice.
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, ti, tbl, *_: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, bs, d), lambda bi, hi, ti, tbl, *_: (tbl[bi, ti], hi, 0, 0)),
            pl.BlockSpec((1, 1, bs, d), lambda bi, hi, ti, tbl, *_: (tbl[bi, ti], hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, ti, *_: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, g, 128), lambda bi, hi, ti, *_: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, g, 128), lambda bi, hi, ti, *_: (bi, hi, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out, out_l, out_m = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32),  # normalized out
            jax.ShapeDtypeStruct((b, hkv, g, 128), jnp.float32),  # l
            jax.ShapeDtypeStruct((b, hkv, g, 128), jnp.float32),  # m
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        jnp.clip(block_tables, 0, n - 1).astype(jnp.int32),
        starts.astype(jnp.int32),
        lengths.astype(jnp.int32),
        q,
        k_pages,
        v_pages,
    )
    return out, out_l, out_m


def _paged_decode_quant_kernel(
    tables_ref,  # scalar-prefetch: (B, P) int32
    start_ref,  # scalar-prefetch: (B,) int32
    len_ref,  # scalar-prefetch: (B,) int32
    q_ref,  # (1, 1, P, G, D/P) — split_q_planes
    kq_ref,  # (1, 1, bs, Dp) packed payload of page tables_ref[b, t]
    ks_ref,  # (1, 1, 1, bs) f32 scale row of the same page
    vq_ref,  # (1, 1, bs, Dp)
    vs_ref,  # (1, 1, 1, bs)
    out_ref,  # (1, 1, P, G, D/P)
    out_l_ref,
    out_m_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    bs: int,
    n_pages: int,
    sm_scale: float,
    kv_dtype: str,
):
    """Fused-dequant paged decode: the same block-table walk as
    ``_paged_decode_kernel``, but each grid step DMAs the page's *packed*
    payload (1/2 or 1/4 of the fp bytes) plus its fp32 scale plane, and the
    fp page exists only as the VMEM tile feeding the dot — decode reads
    packed pages directly, never materializing an fp cache in HBM."""
    b = pl.program_id(0)
    t = pl.program_id(2)
    length = len_ref[b]
    start = start_ref[b]

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(t * bs < length, (t + 1) * bs > start))
    def _step():
        pos = t * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        _quant_softmax_step(
            q_ref, kq_ref[...][0, 0], ks_ref[...][0, 0],
            vq_ref[...][0, 0], vs_ref[...][0, 0], pos, start, length,
            m_ref, l_ref, acc_ref, sm_scale=sm_scale, kv_dtype=kv_dtype,
        )

    @pl.when(t == n_pages - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        out_ref[...] = (acc_ref[...] / jnp.maximum(l, 1e-30))[None, None].astype(out_ref.dtype)
        out_l_ref[...] = l_ref[...][None, None]
        out_m_ref[...] = m_ref[...][None, None]


@functools.partial(jax.jit, static_argnames=("sm_scale", "kv_dtype", "interpret"))
def paged_decode_attention_quant_pallas(
    q: jax.Array,  # (B, Hkv, G, D)
    k_pages_q: jax.Array,  # (N, Hkv, bs, Dp) packed payload pool (one layer)
    k_scales: jax.Array,  # (N, Hkv, bs) f32 scale planes
    v_pages_q: jax.Array,
    v_scales: jax.Array,
    block_tables: jax.Array,  # (B, P) int32
    lengths: jax.Array,  # (B,) int32
    starts: jax.Array | None = None,
    *,
    kv_dtype: str,
    sm_scale: float | None = None,
    interpret: bool = False,
):
    """Fused-dequant variant of ``paged_decode_attention_pallas``: walks the
    block table over the *packed* page pool."""
    b, hkv, g, d = q.shape
    n, hkv_p, bs, dp = k_pages_q.shape
    assert hkv_p == hkv, (k_pages_q.shape, q.shape)
    n_pages = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    # (N, Hkv, 1, bs) scale planes: see decode_attention_quant_pallas.
    k_scales = k_scales[:, :, None, :]
    v_scales = v_scales[:, :, None, :]

    qp = split_q_planes(q, kv_dtype)
    n_planes, dq = qp.shape[2], qp.shape[4]

    if starts is None:
        starts = jnp.zeros_like(lengths)
    kernel = functools.partial(
        _paged_decode_quant_kernel, bs=bs, n_pages=n_pages, sm_scale=sm_scale, kv_dtype=kv_dtype
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, n_planes, g, dq), lambda bi, hi, ti, *_: (bi, hi, 0, 0, 0)),
            pl.BlockSpec((1, 1, bs, dp), lambda bi, hi, ti, tbl, *_: (tbl[bi, ti], hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, bs), lambda bi, hi, ti, tbl, *_: (tbl[bi, ti], hi, 0, 0)),
            pl.BlockSpec((1, 1, bs, dp), lambda bi, hi, ti, tbl, *_: (tbl[bi, ti], hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, bs), lambda bi, hi, ti, tbl, *_: (tbl[bi, ti], hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, n_planes, g, dq), lambda bi, hi, ti, *_: (bi, hi, 0, 0, 0)),
            pl.BlockSpec((1, 1, g, 128), lambda bi, hi, ti, *_: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, g, 128), lambda bi, hi, ti, *_: (bi, hi, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((n_planes, g, dq), jnp.float32),
        ],
    )
    out, out_l, out_m = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, n_planes, g, dq), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        jnp.clip(block_tables, 0, n - 1).astype(jnp.int32),
        starts.astype(jnp.int32),
        lengths.astype(jnp.int32),
        qp,
        k_pages_q,
        k_scales,
        v_pages_q,
        v_scales,
    )
    return merge_out_planes(out), out_l, out_m
