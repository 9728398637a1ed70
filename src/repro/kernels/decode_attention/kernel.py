"""Pallas TPU kernel: bandwidth-optimized decode attention (the decode RM).

Paper (C3 + §3.2.3): in decode, L=1 — no Q reuse exists; attention degenerates
to q_t · K^T -> softmax -> · V streaming the whole KV cache.  The FPGA design
re-maps the four DDR HP ports to 2xK + 2xV (instead of Q/K/V/O), streams the
one Q token into an on-chip buffer before the walk, and holds the output
token locally until the KV transfer finishes.

TPU mapping (DESIGN.md §2):
  * Q tile (G, D) for one KV head's query group is pinned in VMEM for the
    whole kernel (BlockSpec index constant in the KV-walk dim) — the "stream
    Q into the on-chip buffer first" step.
  * K and V have *separate* block specs walking the cache, so Mosaic
    double-buffers two independent HBM->VMEM DMA streams — the 2+2 port
    remap analogue; the HBM roofline term is ~ bytes(KV)/bw.
  * The output (G, D) is accumulated in VMEM scratch and written exactly
    once, after the last KV block ("write back after KV transfers complete").
  * GQA: the grid iterates KV heads; all G = H/Hkv query heads of a group
    ride the same KV stream (KV bytes read once per group, not per head).

Variable sequence lengths (continuous batching) come in via scalar prefetch:
``lengths[b]`` masks tail positions and skips fully-inactive KV blocks.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kv_planes(tile, kv_dtype):
    """In-VMEM unpack of one (bk, Dp) payload tile -> the f32 integer planes
    of its head_dim: ``[q]`` for int8; ``[even, odd]`` nibbles for int4,
    each (bk, D/2), sign-extended in int32 (no lane interleave, which Mosaic
    cannot lower).  This is the *fused* step: packed bytes are what the DMA
    moved; fp values exist only in VMEM, never in HBM."""
    if kv_dtype == "int4":
        w = tile.astype(jnp.int32)
        return [((w << 28) >> 28).astype(jnp.float32), ((w << 24) >> 28).astype(jnp.float32)]
    return [tile.astype(jnp.float32)]


def split_q_planes(q: jax.Array, kv_dtype: str) -> jax.Array:
    """(B, Hkv, G, D) -> (B, Hkv, P, G, D/P): the query's head_dim split the
    way ``_kv_planes`` splits the payload (P = 2 even/odd planes for int4)."""
    if kv_dtype != "int4":
        return q[:, :, None]
    b, hkv, g, d = q.shape
    return jnp.moveaxis(q.reshape(b, hkv, g, d // 2, 2), 4, 2)


def merge_out_planes(out: jax.Array) -> jax.Array:
    """Inverse of :func:`split_q_planes` on the (B, Hkv, P, G, D/P) output."""
    b, hkv, n, g, dp = out.shape
    return jnp.moveaxis(out, 2, 4).reshape(b, hkv, g, n * dp)


def _quant_softmax_step(q_ref, kq_tile, ks_row, vq_tile, vs_row, pos, start, length,
                        m_ref, l_ref, acc_ref, *, sm_scale, kv_dtype):
    """One online-softmax block over a quantized K/V tile.

    ``q . (kq * ks)^T == (q . kq^T) * ks`` and ``p . (vq * vs) == (p * vs) . vq``
    with ``ks``/``vs`` the (1, bk) scale rows of the tile's tokens: the rows
    broadcast along lanes of the (G, bk) scores, with no relayout.  q_ref is
    (1, 1, P, G, D/P) and acc_ref (P, G, D/P), one plane per payload plane."""
    ks = _kv_planes(kq_tile, kv_dtype)
    vs = _kv_planes(vq_tile, kv_dtype)
    s = sum(
        jax.lax.dot_general(q_ref[0, 0, i].astype(jnp.float32), k, (((1,), (1,)), ((), ())))
        for i, k in enumerate(ks)
    ) * (ks_row * sm_scale)
    s = jnp.where(jnp.logical_and(pos >= start, pos < length), s, NEG_INF)

    m_prev = m_ref[...][:, :1]
    l_prev = l_ref[...][:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = jnp.broadcast_to(alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)
    pv = p * vs_row
    for i, v in enumerate(vs):
        acc_ref[i] = acc_ref[i] * alpha + jax.lax.dot_general(pv, v, (((1,), (0,)), ((), ())))
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)


def _decode_kernel(
    start_ref,  # scalar-prefetch: (B,) int32 — window start (0 for full attn)
    len_ref,  # scalar-prefetch: (B,) int32
    q_ref,  # (1, 1, G, D)
    k_ref,  # (1, 1, bk, D)
    v_ref,  # (1, 1, bk, D)
    out_ref,  # (1, 1, G, D)
    out_l_ref,  # (1, 1, G, 128) — softmax denominator (stats output)
    out_m_ref,  # (1, 1, G, 128) — running max (stats output)
    m_ref,
    l_ref,
    acc_ref,
    *,
    bk: int,
    n_steps: int,
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

    # Skip KV blocks entirely outside [start, length) — sliding windows skip
    # the dead prefix, full attention (start=0) streams everything live.
    @pl.when(jnp.logical_and(t * bk < length, (t + 1) * bk > start))
    def _step():
        q = q_ref[...].astype(jnp.float32)[0, 0]  # (G, D)
        k = k_ref[...].astype(jnp.float32)[0, 0]  # (bk, D)
        v = v_ref[...].astype(jnp.float32)[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * sm_scale  # (G, bk)
        pos = t * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
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

    @pl.when(t == n_steps - 1)  # single writeback after the KV walk
    def _finalize():
        l = l_ref[...][:, :1]
        out_ref[...] = (acc_ref[...] / jnp.maximum(l, 1e-30))[None, None].astype(out_ref.dtype)
        out_l_ref[...] = l_ref[...][None, None]
        out_m_ref[...] = m_ref[...][None, None]


@functools.partial(jax.jit, static_argnames=("bk", "sm_scale", "interpret"))
def decode_attention_pallas(
    q: jax.Array,  # (B, Hkv, G, D) — query heads grouped by KV head
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,  # (B, Hkv, S, D)
    lengths: jax.Array,  # (B,) int32 — per-sequence valid cache length
    starts: jax.Array | None = None,  # (B,) int32 — window start (default 0)
    *,
    bk: int = 512,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, hkv, g, d = q.shape
    s = k.shape[2]
    # Partial final block: clamp the KV block to the cache and right-pad the
    # cache to a whole number of blocks — padded positions sit at pos >=
    # length, so the existing length mask already zeroes them.  Small
    # reduced-config caches need no caller-side padding.
    bk = min(bk, s)
    pad = (-s) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    n_steps = (s + pad) // bk

    if starts is None:
        starts = jnp.zeros_like(lengths)
    kernel = functools.partial(_decode_kernel, bk=bk, n_steps=n_steps, sm_scale=sm_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, n_steps),
        # NB: with scalar prefetch, index maps receive the scalar refs as
        # trailing arguments (absorbed by *_).
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, ti, *_: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ti, *_: (bi, hi, ti, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ti, *_: (bi, hi, ti, 0)),
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
    return pl.pallas_call(
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
    )(starts.astype(jnp.int32), lengths.astype(jnp.int32), q, k, v)


def _decode_quant_kernel(
    start_ref,  # scalar-prefetch: (B,) int32
    len_ref,  # scalar-prefetch: (B,) int32
    q_ref,  # (1, 1, P, G, D/P) — split_q_planes
    kq_ref,  # (1, 1, bk, Dp) int8 / uint8 packed payload
    ks_ref,  # (1, 1, 1, bk) f32 scale row
    vq_ref,  # (1, 1, bk, Dp)
    vs_ref,  # (1, 1, 1, bk)
    out_ref,  # (1, 1, P, G, D/P)
    out_l_ref,
    out_m_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    bk: int,
    n_steps: int,
    sm_scale: float,
    kv_dtype: str,
):
    """Fused-dequant decode RM: identical online-softmax walk to
    ``_decode_kernel`` but the K/V streams are the *packed* cache — the DMA
    moves 1/2 (int8) or 1/4 (int4) of the fp bytes plus a 4-byte scale per
    row, and dequant is fused into the block's dots."""
    b = pl.program_id(0)
    t = pl.program_id(2)
    length = len_ref[b]
    start = start_ref[b]

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(t * bk < length, (t + 1) * bk > start))
    def _step():
        pos = t * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        _quant_softmax_step(
            q_ref, kq_ref[...][0, 0], ks_ref[...][0, 0],
            vq_ref[...][0, 0], vs_ref[...][0, 0], pos, start, length,
            m_ref, l_ref, acc_ref, sm_scale=sm_scale, kv_dtype=kv_dtype,
        )

    @pl.when(t == n_steps - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        out_ref[...] = (acc_ref[...] / jnp.maximum(l, 1e-30))[None, None].astype(out_ref.dtype)
        out_l_ref[...] = l_ref[...][None, None]
        out_m_ref[...] = m_ref[...][None, None]


@functools.partial(jax.jit, static_argnames=("bk", "sm_scale", "kv_dtype", "interpret"))
def decode_attention_quant_pallas(
    q: jax.Array,  # (B, Hkv, G, D)
    k_q: jax.Array,  # (B, Hkv, S, Dp) packed payload (int8 / uint8)
    k_scale: jax.Array,  # (B, Hkv, S) f32
    v_q: jax.Array,
    v_scale: jax.Array,
    lengths: jax.Array,  # (B,) int32
    starts: jax.Array | None = None,
    *,
    kv_dtype: str,
    bk: int = 512,
    sm_scale: float | None = None,
    interpret: bool = False,
):
    """Fused-dequant variant of ``decode_attention_pallas`` over a quantized
    contiguous cache.  Same outputs (normalized out + l/m stats)."""
    b, hkv, g, d = q.shape
    s = k_q.shape[2]
    # The (1, bk) scale-row block must be lane-aligned unless it spans the
    # whole cache (TPU block tiling), so bk rounds up to a multiple of 128.
    bk = min(-(-bk // 128) * 128, s)
    pad = (-s) % bk
    if pad:
        pad4 = ((0, 0), (0, 0), (0, pad), (0, 0))
        k_q = jnp.pad(k_q, pad4)
        v_q = jnp.pad(v_q, pad4)
        pad3 = ((0, 0), (0, 0), (0, pad))
        k_scale = jnp.pad(k_scale, pad3)
        v_scale = jnp.pad(v_scale, pad3)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    n_steps = (s + pad) // bk
    dp = k_q.shape[3]
    # Scale rows go in as (B, Hkv, 1, S): a (1, bk) block of one head obeys
    # the TPU block-tiling rule, a (1, 1, bk) block of (B, Hkv, S) does not.
    k_scale = k_scale[:, :, None, :]
    v_scale = v_scale[:, :, None, :]

    qp = split_q_planes(q, kv_dtype)
    n_planes, dq = qp.shape[2], qp.shape[4]

    if starts is None:
        starts = jnp.zeros_like(lengths)
    kernel = functools.partial(
        _decode_quant_kernel, bk=bk, n_steps=n_steps, sm_scale=sm_scale, kv_dtype=kv_dtype
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, n_steps),
        in_specs=[
            pl.BlockSpec((1, 1, n_planes, g, dq), lambda bi, hi, ti, *_: (bi, hi, 0, 0, 0)),
            pl.BlockSpec((1, 1, bk, dp), lambda bi, hi, ti, *_: (bi, hi, ti, 0)),
            pl.BlockSpec((1, 1, 1, bk), lambda bi, hi, ti, *_: (bi, hi, 0, ti)),
            pl.BlockSpec((1, 1, bk, dp), lambda bi, hi, ti, *_: (bi, hi, ti, 0)),
            pl.BlockSpec((1, 1, 1, bk), lambda bi, hi, ti, *_: (bi, hi, 0, ti)),
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
    )(starts.astype(jnp.int32), lengths.astype(jnp.int32), qp, k_q, k_scale, v_q, v_scale)
    return merge_out_planes(out), out_l, out_m
