"""Attention layer: the *dynamic region* of PD-Swap.

One parameter set, two phase-specialized execution paths (the two RMs):

* ``attention_prefill``  — token-parallel blocked attention (compute-bound
  engine).  Dispatches to the Pallas reverse-scheduled flash kernel
  (``cfg.use_pallas``) or to a memory-bounded chunked-scan jnp path whose
  peak live set is O(S·chunk) instead of O(S²) — required for the 32k/500k
  dry-run cells.
* ``attention_decode``   — single-token KV-cache-streaming attention
  (bandwidth-bound engine), Pallas flash-decode kernel or jnp oracle, with
  per-sequence lengths for continuous batching and ring-buffer caches for
  sliding-window layers.

Projections (Q/K/V/O) are TLMM/dense linears — the paper's *static region* —
and are shared verbatim by both phases.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.kernels.prefill_attention.ops import prefill_attention
from repro.layers.linear import linear_apply, linear_init
from repro.layers.rotary import apply_rope
from repro.layers.sharding import PartitionCtx
from repro.quant.kv_quant import QuantKV, infer_kv_dtype, quantize_kv


class KVCache(NamedTuple):
    k: jax.Array  # (B, Hkv, Smax, D) — or a QuantKV (payload + scale plane)
    v: jax.Array  # (B, Hkv, Smax, D)


def _kv_leaf_args(k_leaf, v_leaf):
    """Split a (possibly quantized) K/V cache leaf pair into the positional
    payload arrays + the keyword scale/dtype arguments the kernel ops take.
    The cache pytree itself carries the precision — no dtype plumbing."""
    if isinstance(k_leaf, QuantKV):
        return k_leaf.q, v_leaf.q, dict(
            k_scales=k_leaf.scale, v_scales=v_leaf.scale,
            kv_dtype=infer_kv_dtype(k_leaf.q),
        )
    return k_leaf, v_leaf, {}


def attention_init(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": linear_init(k1, d, h * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": linear_init(k2, d, hkv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": linear_init(k3, d, hkv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": linear_init(k4, h * hd, d, dtype=dtype, scale=1.0 / (h * hd) ** 0.5),
    }


def _project_qkv(params, x, cfg: ModelConfig, positions, *, training, rope=True):
    b, s, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kw = dict(quant=cfg.quant, training=training)
    q = linear_apply(params["wq"], x, **kw).reshape(b, s, h, hd)
    k = linear_apply(params["wk"], x, **kw).reshape(b, s, hkv, hd)
    v = linear_apply(params["wv"], x, **kw).reshape(b, s, hkv, hd)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _chunked_attention(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, Hkv, Skv, D)
    v: jax.Array,
    *,
    causal: bool,
    window: Optional[int],
    chunk: int = 512,
    q_offset: int = 0,
) -> jax.Array:
    """Exact attention with O(S·chunk) live memory: scan over query chunks.

    GQA is handled grouped — KV is never expanded to H heads (that expansion
    is the hidden memory bug of naive GQA at 32k).
    """
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = h // hkv
    sm = 1.0 / math.sqrt(d)
    chunk = min(chunk, sq)
    pad = (-sq) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nc = (sq + pad) // chunk
    qg = q.reshape(b, hkv, g, nc, chunk, d)
    qg = jnp.moveaxis(qg, 3, 0)  # (nc, B, Hkv, G, chunk, D)
    kpos = jnp.arange(skv)

    def body(_, args):
        ci, qc = args  # qc: (B, Hkv, G, chunk, D)
        qpos = q_offset + ci * chunk + jnp.arange(chunk)
        # bf16 operands + f32 accumulation (preferred_element_type) — the
        # MXU semantics; never materialize f32 copies of K/V [§Perf T1]
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qc.astype(k.dtype), k,
                       preferred_element_type=jnp.float32) * sm
        mask = jnp.ones((chunk, skv), bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(mask[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return None, o.astype(q.dtype)

    # checkpoint: without it, backward saves every chunk's (.., chunk, Skv)
    # score tensor — the full S^2 matrix in aggregate.
    body = jax.checkpoint(body)
    _, out = jax.lax.scan(body, None, (jnp.arange(nc), qg))
    out = jnp.moveaxis(out, 0, 3).reshape(b, hkv, g, sq + pad, d)
    out = out.reshape(b, h, sq + pad, d)
    return out[:, :, :sq]


@jax.named_scope("attention")
def attention_prefill(
    params: dict,
    x: jax.Array,  # (B, S, d)
    positions: jax.Array,  # (B, S)
    cfg: ModelConfig,
    pctx: PartitionCtx,
    *,
    window: Optional[int] = None,
    causal: bool = True,
    training: bool = False,
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """The prefill RM.  Returns (y, (k, v)) with k/v in (B, Hkv, S, D) cache layout."""
    b, s, _ = x.shape
    rope = cross_kv is None and cfg.rope_theta > 0
    q, k, v = _project_qkv(params, x, cfg, positions, training=training, rope=rope)
    q = pctx.shard(q, "batch", "seq", "heads", "head_dim")
    qt = q.transpose(0, 2, 1, 3)  # (B, H, S, D)
    if cross_kv is not None:
        kt, vt = cross_kv  # encoder KV, (B, Hkv, Senc, D)
        causal = False
    else:
        kt = pctx.shard(k, "batch", "seq", "kv_heads", "head_dim").transpose(0, 2, 1, 3)
        vt = pctx.shard(v, "batch", "seq", "kv_heads", "head_dim").transpose(0, 2, 1, 3)

    if cfg.attn_impl == "stub":
        # Kernel-substituted lowering (dry-run): the attention core is a
        # shape-correct identity; kernels/costs.py supplies the Pallas
        # kernel's exact analytic cost.  Projections/KV collection stay real.
        out = qt
    elif cfg.use_pallas and window is None and causal and qt.shape[2] == kt.shape[2]:
        out = prefill_attention(qt, kt, vt, use_kernel=True)
    elif s <= 1024 and kt.shape[2] <= 1024:
        from repro.kernels.prefill_attention.ref import prefill_attention_reference

        g = cfg.num_heads // kt.shape[1]
        kk = jnp.repeat(kt, g, axis=1) if g > 1 else kt
        vv = jnp.repeat(vt, g, axis=1) if g > 1 else vt
        sm = 1.0 / math.sqrt(cfg.head_dim)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qt.astype(jnp.float32), kk.astype(jnp.float32)) * sm
        qi, ki = jnp.arange(s)[:, None], jnp.arange(kt.shape[2])[None, :]
        mask = jnp.ones((s, kt.shape[2]), bool)
        if causal:
            mask &= qi >= ki
        if window is not None:
            mask &= qi - ki < window
        scores = jnp.where(mask[None, None], scores, -1e30)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), vv.astype(jnp.float32)).astype(x.dtype)
    else:
        out = _chunked_attention(qt, kt, vt, causal=causal, window=window)

    out = pctx.shard(out, "batch", "heads", "seq", "head_dim")
    y = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.num_heads * cfg.head_dim)
    y = linear_apply(params["wo"], y, quant=cfg.quant, training=training)
    return y, (kt, vt)


@jax.named_scope("attention")
def attention_prefill_chunk(
    params: dict,
    x: jax.Array,  # (B, C, d) — one chunk of the prompt
    k_prefix: jax.Array,  # (B, Hkv, Cap, D) fp — the installed cache prefix,
    v_prefix: jax.Array,  # valid in [0, prefix_len), garbage beyond
    prefix_len: jax.Array,  # traced scalar — tokens already prefilled
    cfg: ModelConfig,
    pctx: PartitionCtx,
    *,
    window: Optional[int] = None,
    positions: Optional[jax.Array] = None,  # (B, C), default prefix_len + arange(C)
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Chunked-prefill attention: the chunk's queries attend over the
    already-installed KV-cache prefix PLUS the chunk itself, with a
    position-offset causal mask.

    This is the third execution path of the dynamic region (prefill RM run
    one bounded quantum at a time): query position ``q`` sits at global
    position ``prefix_len + q`` and may attend key ``k`` iff ``k`` is a
    valid prefix position (``k < prefix_len``) or a chunk position at or
    before it.  ``k_prefix``/``v_prefix`` are the prefill-resident fp
    mirror of the already-installed prefix (see
    ``transformer._prefill_chunk_body`` for why the fp values, not the
    possibly-quantized cache bytes, are what keep chunked == monolithic).

    Returns (y, (k, v)) with the CHUNK's new K/V in (B, Hkv, C, D) cache
    layout; the caller installs them at ``[prefix_len, prefix_len + C)``
    (quantize-on-write under ``kv_dtype``).  A Pallas chunk kernel is a
    future optimization — this jnp path matches the reference prefill's
    f32 einsum numerics, so chunked == monolithic bitwise in the reference
    regime (monolithic prompts past the 1024-token reference cutoff, or
    under the Pallas kernel, accumulate in a different order and agree to
    float rounding instead).
    """
    b, c, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cap = k_prefix.shape[2]
    if positions is None:
        positions = jnp.broadcast_to(prefix_len + jnp.arange(c), (b, c))
    q, k, v = _project_qkv(params, x, cfg, positions, training=False,
                           rope=cfg.rope_theta > 0)
    qt = q.transpose(0, 2, 1, 3)  # (B, H, C, D)
    kt = k.transpose(0, 2, 1, 3)  # (B, Hkv, C, D)
    vt = v.transpose(0, 2, 1, 3)

    if cfg.attn_impl == "stub":
        out = qt  # kernel-substituted lowering; see kernels/costs.py
    else:
        kk = jnp.concatenate([k_prefix.astype(jnp.float32), kt.astype(jnp.float32)], axis=2)
        vv = jnp.concatenate([v_prefix.astype(jnp.float32), vt.astype(jnp.float32)], axis=2)
        g = h // hkv
        if g > 1:
            kk = jnp.repeat(kk, g, axis=1)
            vv = jnp.repeat(vv, g, axis=1)
        sm = 1.0 / math.sqrt(hd)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qt.astype(jnp.float32), kk) * sm
        # global key positions: prefix buffer slot i holds position i (valid
        # iff i < prefix_len); chunk key j sits at prefix_len + j
        qpos = prefix_len + jnp.arange(c)[:, None]  # (C, 1)
        kpos = jnp.concatenate([jnp.arange(cap), prefix_len + jnp.arange(c)])
        valid = jnp.concatenate(
            [jnp.arange(cap) < prefix_len, jnp.ones((c,), bool)])
        mask = valid[None, :] & (qpos >= kpos[None, :])
        if window is not None:
            mask &= qpos - kpos[None, :] < window
        scores = jnp.where(mask[None, None], scores, -1e30)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), vv).astype(x.dtype)

    out = pctx.shard(out, "batch", "heads", "seq", "head_dim")
    y = out.transpose(0, 2, 1, 3).reshape(b, c, h * hd)
    y = linear_apply(params["wo"], y, quant=cfg.quant, training=False)
    return y, (kt, vt)


@jax.named_scope("attention")
def attention_verify(
    params: dict,
    x: jax.Array,  # (B, W, d) — per slot: [last sampled token, draft_1..draft_k]
    k_cache: jax.Array,  # (B, Hkv, Cap, D) dense cache view (fp/bf16, already
    v_cache: jax.Array,  # dequantized/gathered by the caller), valid [0, len)
    lengths: jax.Array,  # (B,) tokens already installed in the cache
    cfg: ModelConfig,
    pctx: PartitionCtx,
    *,
    window: Optional[int] = None,
    positions: Optional[jax.Array] = None,  # (B, W), default lengths + arange(W)
    store_roundtrip=None,  # fn: fresh K/V -> the values a cache read-back yields
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Speculative-verify attention: score a W = k+1 token block per slot.

    The fourth execution path of the dynamic region — the decode RM run
    ``k + 1`` positions at a time.  Block position ``i`` of slot ``b`` sits
    at global position ``lengths[b] + i`` and attends the installed cache
    prefix (``j < lengths[b]``) plus block positions ``<= i`` — the k-token
    variant of ``attention_prefill_chunk``'s position-offset causal mask,
    but batched over slots with per-slot traced prefix lengths.  Rows past
    a slot's real token count compute garbage that later rows never see
    (causality runs forward only); the caller drops their logits and
    routes their KV writes out of bounds.

    Numerics REPLICATE the decode RM step for step, which is what lets
    greedy speculative streams match plain decode bit-for-bit: sequential
    decode at position ``lengths + i`` (1) streams the cache — where block
    rows ``< i`` would by then sit in STORAGE precision, having been
    written (bf16 cast, or quantize-on-write) and read back — with the
    storage-dtype dot / f32-accumulate / P-cast-to-V-dtype math of
    ``_decode_attention_streaming``, then (2) folds its OWN fresh
    full-precision K/V via ``_merge_new_token``.  So here the streamed
    part extends the cache view with ``store_roundtrip``-rounded block
    rows under a strict mask (``j < i``), and each row's own token enters
    through the same online-softmax merge, elementwise-identical to the
    decode epilogue.

    Returns (y (B, W, d_model), (k, v)) with the BLOCK's new K/V in
    (B, Hkv, W, D) cache layout; the caller installs rows ``< n_tokens``
    at ``[lengths, lengths + n_tokens)`` (quantize-on-write under
    ``kv_dtype``) and the engine rolls rejected rows back by truncating
    the slot length / releasing overshoot pages.
    """
    b, w, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cap = k_cache.shape[2]
    if positions is None:
        positions = lengths[:, None] + jnp.arange(w)[None, :]
    q, k, v = _project_qkv(params, x, cfg, positions, training=False,
                           rope=cfg.rope_theta > 0)
    qt = q.transpose(0, 2, 1, 3)  # (B, H, W, D)
    kt = k.transpose(0, 2, 1, 3)  # (B, Hkv, W, D)
    vt = v.transpose(0, 2, 1, 3)

    if cfg.attn_impl == "stub":
        out = qt  # kernel-substituted lowering; see kernels/costs.py
    else:
        g = h // hkv
        sm = 1.0 / math.sqrt(hd)
        # block rows as a LATER cache read would see them: storage-rounded
        kt_st = store_roundtrip(kt) if store_roundtrip is not None else kt
        vt_st = store_roundtrip(vt) if store_roundtrip is not None else vt
        ext_k = jnp.concatenate([k_cache, kt_st.astype(k_cache.dtype)], axis=2)
        ext_v = jnp.concatenate([v_cache, vt_st.astype(v_cache.dtype)], axis=2)
        kk = jnp.repeat(ext_k, g, axis=1) if g > 1 else ext_k
        vv = jnp.repeat(ext_v, g, axis=1) if g > 1 else ext_v
        # --- stage 1: the streaming pass (_decode_attention_streaming) ---
        scores = jnp.einsum("bhqd,bhkd->bhqk", qt.astype(kk.dtype), kk,
                            preferred_element_type=jnp.float32) * sm
        iq = jnp.arange(w)
        qpos = lengths[:, None] + iq[None, :]  # (B, W) global query positions
        kpos_c = jnp.arange(cap)[None, :]  # cache key j holds position j
        mask_c = jnp.broadcast_to((kpos_c < lengths[:, None])[:, None, :], (b, w, cap))
        mask_b = jnp.broadcast_to((iq[:, None] > iq[None, :])[None], (b, w, w))  # strict:
        # a row's own token enters via the merge, exactly as in decode
        if window is not None:
            starts = jnp.maximum(0, qpos + 1 - window)  # (B, W), decode's window start
            mask_c &= kpos_c[:, None, :] >= starts[:, :, None]
            mask_b &= (lengths[:, None, None] + iq[None, None, :]) >= starts[:, :, None]
        mask = jnp.concatenate([mask_c, mask_b], axis=-1)[:, None]  # (B,1,W,cap+W)
        scores = jnp.where(mask, scores, -1e30)
        m = jnp.max(scores, axis=-1, keepdims=True)  # (B, H, W, 1)
        p = jnp.where(mask, jnp.exp(scores - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        out_c = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vv.dtype), vv,
                           preferred_element_type=jnp.float32)
        out_c = out_c / jnp.maximum(l, 1e-30)
        # --- stage 2: fold each row's own fresh K/V (_merge_new_token) ---
        kn = jnp.repeat(kt, g, axis=1) if g > 1 else kt  # (B, H, W, D), full precision
        vn = jnp.repeat(vt, g, axis=1) if g > 1 else vt
        s_new = jnp.sum(qt.astype(jnp.float32) * kn.astype(jnp.float32),
                        axis=-1, keepdims=True) * sm
        m2 = jnp.maximum(m, s_new)
        alpha = jnp.exp(m - m2)
        p_new = jnp.exp(s_new - m2)
        l2 = alpha * l + p_new
        out = (out_c * (alpha * l) + p_new * vn.astype(jnp.float32)) / jnp.maximum(l2, 1e-30)
        out = out.astype(x.dtype)

    out = pctx.shard(out, "batch", "heads", "seq", "head_dim")
    y = out.transpose(0, 2, 1, 3).reshape(b, w, h * hd)
    y = linear_apply(params["wo"], y, quant=cfg.quant, training=False)
    return y, (kt, vt)


def update_cache(cache: KVCache, k_new: jax.Array, v_new: jax.Array, lengths: jax.Array) -> KVCache:
    """Insert one token's K/V per sequence at its current length."""
    smax = cache.k.shape[2]
    idx = jnp.minimum(lengths, smax - 1)

    def upd(c, new, i):  # c: (Hkv, Smax, D); new: (Hkv, 1, D)
        return jax.lax.dynamic_update_slice(c, new.astype(c.dtype), (0, i, 0))

    k = jax.vmap(upd)(cache.k, k_new, idx)
    v = jax.vmap(upd)(cache.v, v_new, idx)
    return KVCache(k, v)


def scatter_new_tokens(buf: jax.Array, new: jax.Array, lengths: jax.Array) -> jax.Array:
    """Write every layer's new token into the decode cache in ONE update.

    buf: (B, L, Hkv, Smax, D) — the decode cache is BATCH-LEADING; new:
    (L, B, Hkv, 1, D), the per-layer tokens collected as scan ys.

    [§Perf iteration D2] During the decode scan the cache is READ-ONLY (the
    online-softmax merge folds each layer's fresh token into its attention
    output); afterwards, per batch element, all L layers' tokens land at ONE
    sequence position — with batch leading that is a single contiguous
    (L, Hkv, 1, D)-window dynamic_update_slice under a single-level leading-
    axis vmap.  Write traffic O(L*B*Hkv*D); the donated buffer aliases in
    place.

    (Earlier formulations all made XLA materialize/transpose the full cache:
    cache-as-carry + vmap-over-batch-axis-1 DUS — vmap moved the batch axis
    to the front, full transpose copies EVERY layer, 3.5x WORSE than
    baseline; jnp advanced indexing with non-adjacent indices — whole-buffer
    transpose to index-leading order and back; nested vmap over (L, B) —
    transposed f32 full-buffer scatters; reshape-flattening (L, B) — merged
    an unsharded dim into the batch-sharded dim and REPLICATED the cache on
    every device.  Lesson: batch-leading layout + one leading vmap axis is
    the only shape XLA updates in place.)
    """
    b, l, hkv, smax, d = buf.shape
    idx = jnp.minimum(lengths, smax - 1)  # (B,)
    newb = jnp.moveaxis(new[:, :, :, 0, :], 1, 0).astype(buf.dtype)  # (B, L, Hkv, D)

    def upd_one(c, n, i):  # c: (L, Hkv, Smax, D); n: (L, Hkv, D); i scalar
        return jax.lax.dynamic_update_slice(c, n[:, :, None, :], (0, 0, i, 0))

    return jax.vmap(upd_one)(buf, newb, idx)


def scatter_new_tokens_paged(
    pages: jax.Array, new: jax.Array, block_tables: jax.Array, lengths: jax.Array
) -> jax.Array:
    """Paged analogue of ``scatter_new_tokens``: write every layer's new
    token into its sequence's *current page* in one scatter.

    pages: (N, L, Hkv, bs, D) — the layer-complete page pool; new:
    (L, B, Hkv, 1, D) per-layer tokens collected as scan ys; block_tables:
    (B, P) int32; lengths: (B,).

    Sequence ``b``'s token lands at page ``tables[b, len//bs]``, in-page
    offset ``len % bs``.  Inactive slots (length 0) are routed to an
    out-of-bounds page id and dropped by the scatter, so they never corrupt
    live pages (NB: -1 would WRAP to the last pool page — jnp scatter
    normalizes negative indices; only ids >= N are dropped).  Distinct
    active slots always own distinct pages, so the scatter indices never
    collide.  Write traffic is O(L*B*Hkv*D), matching the contiguous path.
    """
    n, l, hkv, bs, d = pages.shape
    bsz = lengths.shape[0]
    page_idx = jnp.minimum(lengths // bs, block_tables.shape[1] - 1)
    page = jnp.take_along_axis(block_tables, page_idx[:, None], axis=1)[:, 0]
    page = jnp.where(lengths > 0, page, n)  # inactive slots: OOB -> dropped
    off = lengths % bs
    newb = jnp.moveaxis(new[:, :, :, 0, :], 1, 0).astype(pages.dtype)  # (B, L, Hkv, D)
    return pages.at[page, :, :, off, :].set(newb, mode="drop")


def write_prefill_pages(
    pages: jax.Array, kv: jax.Array, page_ids: jax.Array, *, block_size: int
) -> jax.Array:
    """Scatter a prefilled request's KV into its allocated pages.

    pages: (N, L, Hkv, bs, D); kv: prefill layout (L, 1, Hkv, S, D) with S a
    multiple of ``block_size`` (the compile bucket; the tail past the real
    prompt length is garbage masked by the per-slot length); page_ids:
    (S/bs,) int32 destinations, out-of-bounds entries dropped — prefix-cache
    hits keep their (identical, possibly shared) cached contents instead of
    being rewritten.  (Skip ids must be >= N, never -1: jnp scatter wraps
    negative indices to the end of the pool.)
    """
    l, b, hkv, s, d = kv.shape
    bs = block_size
    kb = kv[:, 0].reshape(l, hkv, s // bs, bs, d)
    kb = jnp.moveaxis(kb, 2, 0)  # (P, L, Hkv, bs, D)
    return pages.at[page_ids].set(kb.astype(pages.dtype), mode="drop")


def scatter_new_scales(buf: jax.Array, new: jax.Array, lengths: jax.Array) -> jax.Array:
    """Scale-plane analogue of ``scatter_new_tokens``.

    buf: (B, L, Hkv, Smax) fp32 per-token scale plane of the quantized
    contiguous cache; new: (L, B, Hkv, 1) fresh-token scales.  Same batch-
    leading single-DUS shape as the payload write.
    """
    b, l, hkv, smax = buf.shape
    idx = jnp.minimum(lengths, smax - 1)
    newb = jnp.moveaxis(new[:, :, :, 0], 1, 0).astype(buf.dtype)  # (B, L, Hkv)

    def upd_one(c, n, i):  # c: (L, Hkv, Smax); n: (L, Hkv); i scalar
        return jax.lax.dynamic_update_slice(c, n[:, :, None], (0, 0, i))

    return jax.vmap(upd_one)(buf, newb, idx)


@jax.named_scope("kv_write")
def scatter_new_tokens_q(buf, new: jax.Array, lengths: jax.Array):
    """``scatter_new_tokens`` generalized to a possibly-quantized cache leaf:
    quantize-on-write of the fresh token rows (payload + scale plane), so
    the fp cache is never materialized.  ``new`` is always fp (L, B, Hkv, 1,
    D); requantizing the same values reproduces the same bytes, which keeps
    preemption replay bit-identical under quantization."""
    if not isinstance(buf, QuantKV):
        return scatter_new_tokens(buf, new, lengths)
    payload, scale = quantize_kv(new, infer_kv_dtype(buf.q))
    return QuantKV(
        scatter_new_tokens(buf.q, payload, lengths),
        scatter_new_scales(buf.scale, scale, lengths),
    )


def scatter_new_scales_paged(
    pages: jax.Array, new: jax.Array, block_tables: jax.Array, lengths: jax.Array
) -> jax.Array:
    """Scale-plane analogue of ``scatter_new_tokens_paged``.

    pages: (N, L, Hkv, bs) fp32 scale planes; new: (L, B, Hkv, 1).  Inactive
    slots route to an out-of-bounds page id and are dropped, exactly like
    the payload scatter.
    """
    n, l, hkv, bs = pages.shape
    page_idx = jnp.minimum(lengths // bs, block_tables.shape[1] - 1)
    page = jnp.take_along_axis(block_tables, page_idx[:, None], axis=1)[:, 0]
    page = jnp.where(lengths > 0, page, n)
    off = lengths % bs
    newb = jnp.moveaxis(new[:, :, :, 0], 1, 0).astype(pages.dtype)  # (B, L, Hkv)
    return pages.at[page, :, :, off].set(newb, mode="drop")


@jax.named_scope("kv_write")
def scatter_new_tokens_paged_q(pages, new: jax.Array, block_tables: jax.Array, lengths: jax.Array):
    """``scatter_new_tokens_paged`` generalized to a possibly-quantized page
    pool leaf — quantize-on-write into the current page (see
    ``scatter_new_tokens_q`` for the determinism contract)."""
    if not isinstance(pages, QuantKV):
        return scatter_new_tokens_paged(pages, new, block_tables, lengths)
    payload, scale = quantize_kv(new, infer_kv_dtype(pages.q))
    return QuantKV(
        scatter_new_tokens_paged(pages.q, payload, block_tables, lengths),
        scatter_new_scales_paged(pages.scale, scale, block_tables, lengths),
    )


def write_prefill_scales(
    pages: jax.Array, scales: jax.Array, page_ids: jax.Array, *, block_size: int
) -> jax.Array:
    """Scale-plane analogue of ``write_prefill_pages``: pages (N, L, Hkv,
    bs), scales (L, 1, Hkv, S) with S a multiple of ``block_size``; same
    out-of-bounds skip semantics for prefix-cache hits."""
    l, b, hkv, s = scales.shape
    bs = block_size
    sb = scales[:, 0].reshape(l, hkv, s // bs, bs)
    sb = jnp.moveaxis(sb, 2, 0)  # (P, L, Hkv, bs)
    return pages.at[page_ids].set(sb.astype(pages.dtype), mode="drop")


@jax.named_scope("kv_write")
def write_prefill_pages_q(pages, kv: jax.Array, page_ids: jax.Array, *, block_size: int):
    """``write_prefill_pages`` generalized to a possibly-quantized pool leaf:
    the paged swap becomes quantize-on-write (per-token-per-head scales),
    so prefilled KV lands in the pool already packed."""
    if not isinstance(pages, QuantKV):
        return write_prefill_pages(pages, kv, page_ids, block_size=block_size)
    payload, scale = quantize_kv(kv, infer_kv_dtype(pages.q))
    return QuantKV(
        write_prefill_pages(pages.q, payload, page_ids, block_size=block_size),
        write_prefill_scales(pages.scale, scale, page_ids, block_size=block_size),
    )


def write_chunk_kv(buf: jax.Array, new: jax.Array, slot, start) -> jax.Array:
    """Install one prefill chunk's KV into the contiguous decode cache.

    buf: (B_slots, L, Hkv, Smax, D) batch-leading decode cache; new:
    (L, 1, Hkv, C, D) — the chunk's per-layer K or V collected as scan ys;
    ``slot``/``start`` are traced scalars.  All L layers' C tokens land in
    one contiguous window, so the write is a single dynamic_update_slice
    (the donated buffer aliases in place — same shape discipline as
    ``scatter_new_tokens``).  ``start + C <= Smax`` is the caller's
    contract (the chunk tail bucket is clamped to the cache bound;
    dynamic_update_slice would silently shift a write that overflows).
    """
    newb = jnp.moveaxis(new, 1, 0).astype(buf.dtype)  # (1, L, Hkv, C, D)
    return jax.lax.dynamic_update_slice(buf, newb, (slot, 0, 0, start, 0))


def write_chunk_scales(buf: jax.Array, new: jax.Array, slot, start) -> jax.Array:
    """Scale-plane analogue of ``write_chunk_kv``: buf (B, L, Hkv, Smax)
    fp32, new (L, 1, Hkv, C)."""
    newb = jnp.moveaxis(new, 1, 0).astype(buf.dtype)  # (1, L, Hkv, C)
    return jax.lax.dynamic_update_slice(buf, newb, (slot, 0, 0, start))


@jax.named_scope("kv_write")
def write_chunk_kv_q(buf, new: jax.Array, slot, start):
    """``write_chunk_kv`` generalized to a possibly-quantized cache leaf:
    quantize-on-write of the chunk rows (payload + scale plane).  Per-token
    scales mean chunk-at-a-time quantization writes exactly the bytes
    whole-prompt quantization would — the chunked/monolithic cache-state
    equivalence and preemption-replay bit-identity rest on that."""
    if not isinstance(buf, QuantKV):
        return write_chunk_kv(buf, new, slot, start)
    payload, scale = quantize_kv(new, infer_kv_dtype(buf.q))
    return QuantKV(
        write_chunk_kv(buf.q, payload, slot, start),
        write_chunk_scales(buf.scale, scale, slot, start),
    )


def scatter_verify_tokens(
    buf: jax.Array, new: jax.Array, lengths: jax.Array, n_tokens: jax.Array
) -> jax.Array:
    """Write a speculative verify block's KV into the contiguous cache.

    buf: (B, L, Hkv, Smax, D) batch-leading decode cache; new:
    (L, B, Hkv, W, D) per-layer block K or V collected as scan ys; row
    ``i`` of slot ``b`` lands at position ``lengths[b] + i`` iff
    ``i < n_tokens[b]`` — rows past a slot's real token count (draft
    padding, parked mid-prefill slots, free slots) route out of bounds and
    are dropped by the scatter, so they can never corrupt live KV or the
    chunked-prefill parked-write row ``Smax - 1`` (the engine additionally
    clamps draft depth so LIVE rows stay ``<= Smax - 2``).  Distinct live
    (slot, position) pairs never collide.
    """
    b, l, hkv, smax, d = buf.shape
    w = new.shape[3]
    iq = jnp.arange(w)[None, :]
    pos = lengths[:, None] + iq  # (B, W)
    pos = jnp.where(iq < n_tokens[:, None], pos, smax)  # OOB -> dropped
    bidx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, w))
    newb = jnp.moveaxis(jnp.moveaxis(new, 1, 0), 3, 1).astype(buf.dtype)  # (B, W, L, Hkv, D)
    return buf.at[bidx, :, :, pos, :].set(newb, mode="drop")


def scatter_verify_scales(
    buf: jax.Array, new: jax.Array, lengths: jax.Array, n_tokens: jax.Array
) -> jax.Array:
    """Scale-plane analogue of ``scatter_verify_tokens``: buf (B, L, Hkv,
    Smax) fp32, new (L, B, Hkv, W); same out-of-bounds drop routing."""
    b, l, hkv, smax = buf.shape
    w = new.shape[3]
    iq = jnp.arange(w)[None, :]
    pos = lengths[:, None] + iq
    pos = jnp.where(iq < n_tokens[:, None], pos, smax)
    bidx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, w))
    newb = jnp.moveaxis(jnp.moveaxis(new, 1, 0), 3, 1).astype(buf.dtype)  # (B, W, L, Hkv)
    return buf.at[bidx, :, :, pos].set(newb, mode="drop")


@jax.named_scope("kv_write")
def scatter_verify_tokens_q(buf, new: jax.Array, lengths: jax.Array, n_tokens: jax.Array):
    """``scatter_verify_tokens`` generalized to a possibly-quantized cache
    leaf: quantize-on-write of the block rows (payload + per-(layer, head,
    token) scale), the same granularity every other write path uses — so a
    verify-round append lands exactly the bytes sequential decode appends
    would, which is what keeps speculative streams and preemption replay
    bit-identical under quantization."""
    if not isinstance(buf, QuantKV):
        return scatter_verify_tokens(buf, new, lengths, n_tokens)
    payload, scale = quantize_kv(new, infer_kv_dtype(buf.q))
    return QuantKV(
        scatter_verify_tokens(buf.q, payload, lengths, n_tokens),
        scatter_verify_scales(buf.scale, scale, lengths, n_tokens),
    )


def scatter_verify_tokens_paged(
    pages: jax.Array, new: jax.Array, block_tables: jax.Array,
    lengths: jax.Array, n_tokens: jax.Array
) -> jax.Array:
    """Paged analogue of ``scatter_verify_tokens``: row ``i`` of slot ``b``
    lands in page ``tables[b, (lengths[b]+i) // bs]`` at in-page offset
    ``(lengths[b]+i) % bs``.  Rows with ``i >= n_tokens[b]`` (and inactive
    slots, ``lengths == 0``) route to the out-of-bounds page id and are
    dropped — the engine only grows the table to cover a slot's REAL rows,
    so padding rows must never consult it.  Live slots own distinct pages,
    so the (page, offset) scatter indices never collide.
    """
    n, l, hkv, bs, d = pages.shape
    w = new.shape[3]
    iq = jnp.arange(w)[None, :]
    pos = lengths[:, None] + iq  # (B, W) global positions
    page_idx = jnp.minimum(pos // bs, block_tables.shape[1] - 1)
    page = jnp.take_along_axis(block_tables, page_idx, axis=1)  # (B, W)
    valid = (iq < n_tokens[:, None]) & (lengths[:, None] > 0)
    page = jnp.where(valid, page, n)  # OOB -> dropped
    off = pos % bs
    newb = jnp.moveaxis(jnp.moveaxis(new, 1, 0), 3, 1).astype(pages.dtype)  # (B, W, L, Hkv, D)
    return pages.at[page, :, :, off, :].set(newb, mode="drop")


def scatter_verify_scales_paged(
    pages: jax.Array, new: jax.Array, block_tables: jax.Array,
    lengths: jax.Array, n_tokens: jax.Array
) -> jax.Array:
    """Scale-plane analogue of ``scatter_verify_tokens_paged``: pages
    (N, L, Hkv, bs) fp32, new (L, B, Hkv, W)."""
    n, l, hkv, bs = pages.shape
    w = new.shape[3]
    iq = jnp.arange(w)[None, :]
    pos = lengths[:, None] + iq
    page_idx = jnp.minimum(pos // bs, block_tables.shape[1] - 1)
    page = jnp.take_along_axis(block_tables, page_idx, axis=1)
    valid = (iq < n_tokens[:, None]) & (lengths[:, None] > 0)
    page = jnp.where(valid, page, n)
    off = pos % bs
    newb = jnp.moveaxis(jnp.moveaxis(new, 1, 0), 3, 1).astype(pages.dtype)  # (B, W, L, Hkv)
    return pages.at[page, :, :, off].set(newb, mode="drop")


@jax.named_scope("kv_write")
def scatter_verify_tokens_paged_q(
    pages, new: jax.Array, block_tables: jax.Array,
    lengths: jax.Array, n_tokens: jax.Array
):
    """``scatter_verify_tokens_paged`` generalized to a possibly-quantized
    page pool leaf — quantize-on-write of the verify block (see
    ``scatter_verify_tokens_q`` for the determinism contract)."""
    if not isinstance(pages, QuantKV):
        return scatter_verify_tokens_paged(pages, new, block_tables, lengths, n_tokens)
    payload, scale = quantize_kv(new, infer_kv_dtype(pages.q))
    return QuantKV(
        scatter_verify_tokens_paged(pages.q, payload, block_tables, lengths, n_tokens),
        scatter_verify_scales_paged(pages.scale, scale, block_tables, lengths, n_tokens),
    )


def _merge_new_token(
    out_cache: jax.Array,  # (B, H, D) — attention over cache, f32-normalized
    l_cache: jax.Array,  # (B, H, 1) — softmax denominator over cache
    m_cache: jax.Array,  # (B, H, 1) — running max over cache
    q: jax.Array,  # (B, H, D)
    k_new: jax.Array,  # (B, Hkv, 1, D)
    v_new: jax.Array,
    sm_scale: float,
) -> jax.Array:
    """Fold the freshly-projected token's K/V into cache attention output.

    [§Perf iteration D2] The classic online-softmax merge: the new token is
    one extra 'block', so the decode step never materializes an updated
    cache slice (update-then-attend would write+read O(cache) bytes; the
    merge is O(tokens)).
    """
    b, h, d = q.shape
    g = h // k_new.shape[1]
    kn = jnp.repeat(k_new[:, :, 0, :], g, axis=1) if g > 1 else k_new[:, :, 0, :]
    vn = jnp.repeat(v_new[:, :, 0, :], g, axis=1) if g > 1 else v_new[:, :, 0, :]
    s_new = jnp.sum(q.astype(jnp.float32) * kn.astype(jnp.float32), axis=-1, keepdims=True) * sm_scale
    m = jnp.maximum(m_cache, s_new)
    alpha = jnp.exp(m_cache - m)
    p_new = jnp.exp(s_new - m)
    l = alpha * l_cache + p_new
    out = (out_cache * (alpha * l_cache) + p_new * vn.astype(jnp.float32)) / jnp.maximum(l, 1e-30)
    return out


@jax.named_scope("attention")
def attention_decode(
    params: dict,
    x: jax.Array,  # (B, 1, d)
    cache: KVCache,
    lengths: jax.Array,  # (B,) tokens already in cache
    cfg: ModelConfig,
    pctx: PartitionCtx,
    *,
    window: Optional[int] = None,
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    cross_len: Optional[int] = None,
) -> Tuple[jax.Array, KVCache]:
    """The decode RM: one token against the streamed KV cache.

    Returns (y, (k_new, v_new)) — the NEW token's K/V only, shape
    (B, Hkv, 1, D); the caller scatters it into its carried cache buffer
    (``scatter_token``).  The attention output already includes the new
    token via the online-softmax merge, so the updated cache slice is never
    materialized.  Cross-attention (read-only KV) returns ``cache``
    unchanged.
    """
    b = x.shape[0]
    h, hd = cfg.num_heads, cfg.head_dim

    if cross_kv is not None:
        q, k, v = _project_qkv(params, x, cfg, lengths[:, None], training=False, rope=False)
        qd = q.reshape(b, h, hd)
        kt, vt = cross_kv
        if cfg.attn_impl == "stub":
            out = qd
        else:
            eff_len = jnp.full((b,), cross_len if cross_len is not None else kt.shape[2], jnp.int32)
            out = decode_attention(qd, kt, vt, eff_len, use_kernel=cfg.use_pallas)
        y = out.reshape(b, 1, h * hd)
        y = linear_apply(params["wo"], y, quant=cfg.quant, training=False)
        return y, cache

    def attend(qd, starts):
        k_arr, v_arr, qkw = _kv_leaf_args(cache.k, cache.v)
        return decode_attention(
            qd, k_arr, v_arr, lengths.astype(jnp.int32), starts,
            use_kernel=cfg.use_pallas, return_stats=True, **qkw,
        )

    return _decode_new_token(params, x, lengths, cfg, window, attend)


def _decode_new_token(params, x, lengths, cfg, window, attend_cache):
    """Shared decode-RM body for both cache layouts: project the one new
    token's Q/K/V, attend over the EXISTING cache ([start, len) valid) via
    ``attend_cache(qd, starts) -> (out, l, m)``, merge the fresh token
    analytically, and output-project.  Window start accounts for the
    appended token: valid range becomes [max(0, len+1-window), len+1).
    Returns (y, new-token K/V (B, Hkv, 1, D))."""
    b = x.shape[0]
    h, hd = cfg.num_heads, cfg.head_dim
    q, k, v = _project_qkv(params, x, cfg, lengths[:, None], training=False,
                           rope=cfg.rope_theta > 0)
    qd = q.reshape(b, h, hd)
    k_new = k.transpose(0, 2, 1, 3)  # (B, Hkv, 1, D)
    v_new = v.transpose(0, 2, 1, 3)
    if cfg.attn_impl == "stub":
        out = qd  # kernel-substituted lowering; see kernels/costs.py
    else:
        starts = None if window is None else jnp.maximum(0, lengths + 1 - window).astype(jnp.int32)
        sm_scale = 1.0 / math.sqrt(hd)
        out_c, l_c, m_c = attend_cache(qd, starts)
        out = _merge_new_token(out_c, l_c, m_c, qd, k_new, v_new, sm_scale).astype(x.dtype)

    y = out.reshape(b, 1, h * hd)
    y = linear_apply(params["wo"], y, quant=cfg.quant, training=False)
    return y, KVCache(k_new, v_new)


@jax.named_scope("attention")
def attention_decode_paged(
    params: dict,
    x: jax.Array,  # (B, 1, d)
    k_pages: jax.Array,  # (N, Hkv, bs, D) — this layer's slice of the pool
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, P) int32
    lengths: jax.Array,  # (B,) tokens already in cache
    cfg: ModelConfig,
    pctx: PartitionCtx,
    *,
    window: Optional[int] = None,
) -> Tuple[jax.Array, KVCache]:
    """The decode RM over the paged cache: one token against the block-table
    -walked KV.  Same contract as ``attention_decode``'s cache branch (both
    share ``_decode_new_token``, so the two layouts cannot drift) — the
    caller scatters the returned new-token K/V into the pool
    (``scatter_new_tokens_paged``); the attention output already folds it in
    via the online-softmax merge.
    """

    def attend(qd, starts):
        k_arr, v_arr, qkw = _kv_leaf_args(k_pages, v_pages)
        return paged_decode_attention(
            qd, k_arr, v_arr, block_tables, lengths.astype(jnp.int32), starts,
            use_kernel=cfg.use_pallas, return_stats=True, **qkw,
        )

    return _decode_new_token(params, x, lengths, cfg, window, attend)
