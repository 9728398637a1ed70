"""Decoder-only transformer (dense + MoE): granite, moonshot, chameleon,
deepseek, qwen, minicpm, smollm, bitnet.

Layer-stacked parameters (leading dim = num_layers) consumed by
``jax.lax.scan`` so the HLO stays one-layer-sized — essential for compiling
the 512-device dry-run of 48-layer models on a single CPU host.

Three entry points = the PD-Swap phase programs:
  * ``forward_train``  — full causal pass -> per-token loss (train_4k cells)
  * ``forward_prefill``— full causal pass -> logits + per-layer KV (prefill RM)
  * ``decode_step``    — one token against the cache (decode RM)
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.tlmm.ops import kernel_serves
from repro.layers.attention import (
    KVCache,
    attention_decode,
    attention_init,
    attention_prefill,
)
from repro.layers.mlp import mlp_apply, mlp_init
from repro.layers.moe import moe_apply, moe_init
from repro.layers.norm import apply_norm, norm_init
from repro.layers.sharding import NULL_CTX, PartitionCtx
from repro.quant.ternary import TernaryWeight, quantize_and_pack


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)  # "full": save nothing


def init(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> dict:
    vp = cfg.padded_vocab()
    k_emb, k_layers, k_head = jax.random.split(key, 3)

    def layer_init(k):
        ka, kf = jax.random.split(k)
        p = {
            "attn": attention_init(cfg, ka, dtype),
            "ln1": norm_init(cfg.norm, cfg.d_model),
            "ln2": norm_init(cfg.norm, cfg.d_model),
        }
        if cfg.moe:
            p["moe"] = moe_init(cfg, kf, dtype)
        else:
            p["mlp"] = mlp_init(cfg, kf, dtype)
        return p

    params = {
        "emb": (jax.random.normal(k_emb, (vp, cfg.d_model), jnp.float32) * 0.02).astype(dtype),
        "layers": jax.vmap(layer_init)(jax.random.split(k_layers, cfg.num_layers)),
        "ln_f": norm_init(cfg.norm, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (cfg.d_model, vp), jnp.float32) * 0.02
        ).astype(dtype)
    return params


# layer blocks whose linears ``linear_apply`` runs (the MoE experts do not)
_LINEAR_BLOCKS = ("attn", "mlp")


def _latent_linears(cfg: ModelConfig, params: dict) -> dict:
    """{block: {name: (L, K, N) latent w}} of every linear weight that
    ``linear_apply`` quantizes per call: empty unless ``cfg.quant.ternary``."""
    if not cfg.quant.ternary:
        return {}
    out = {}
    for blk in _LINEAR_BLOCKS:
        found = {name: p["w"] for name, p in params["layers"].get(blk, {}).items()
                 if not isinstance(p["w"], TernaryWeight)}
        if found:
            out[blk] = found
    return out


@jax.jit
def _pack_stacked(ws: dict) -> dict:
    # vmap over the layer axis: one absmean beta per layer, as the layer
    # scan of the latent path takes it from each (K, N) slice
    return jax.tree.map(jax.vmap(quantize_and_pack), ws)


def convert_for_inference(cfg: ModelConfig, params: dict) -> dict:
    """Ternarize and 2-bit pack every linear weight of a ternary config once
    (one jitted call), so the phase programs read packed ``TernaryWeight``s
    — packed (L, K/4, N), scale (L,) — instead of re-quantizing the latent
    float weights in every call.  Embedding, norms and MoE experts stay as
    they are.  Returns a new tree; the caller's is untouched.  For a
    non-ternary config it returns ``params`` itself."""
    latent = _latent_linears(cfg, params)
    if not latent:
        return params
    packed = _pack_stacked(latent)
    layers = dict(params["layers"])
    for blk, ws in packed.items():
        layers[blk] = dict(layers[blk])
        for name, w in ws.items():
            layers[blk][name] = {**layers[blk][name], "w": w}
    return {**params, "layers": layers}


def linear_residency(cfg: ModelConfig, params: dict) -> Tuple[int, int]:
    """(packed, latent): how many ternary linear matrices the tree serves
    from packed 2-bit weights, and how many it re-quantizes from latent
    weights in every call (the MoE experts among them).  (0, 0) for a
    non-ternary config."""
    if not cfg.quant.ternary:
        return 0, 0
    layers = params["layers"]
    packed = sum(p["w"].packed.shape[0] for blk in _LINEAR_BLOCKS
                 for p in layers.get(blk, {}).values() if isinstance(p["w"], TernaryWeight))
    latent = sum(w.shape[0] for ws in _latent_linears(cfg, params).values() for w in ws.values())
    latent += sum(layers["moe"][k].shape[0] * layers["moe"][k].shape[1]
                  for k in ("w_gate", "w_up", "w_down") if k in layers.get("moe", {}))
    return packed, latent


def kernel_linears(cfg: ModelConfig, params: dict) -> int:
    """How many of the tree's packed linear matrices ``tlmm_matmul`` serves
    through the compiled TLMM kernel here in a decode round (0 on the CPU,
    where the reference serves them)."""
    if not cfg.quant.ternary:
        return 0
    return sum(p["w"].packed.shape[0] for blk in _LINEAR_BLOCKS
               for p in params["layers"].get(blk, {}).values()
               if isinstance(p["w"], TernaryWeight) and kernel_serves(p["w"].k, p["w"].n))


@jax.named_scope("lm_head")
def _logits(params, x, cfg: ModelConfig, pctx: PartitionCtx) -> jax.Array:
    x = apply_norm(params["ln_f"], x, cfg.norm, cfg.norm_eps)
    head = params["emb"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x.astype(jnp.float32) @ head.astype(jnp.float32)
    return pctx.shard(logits, "batch", "seq", "vocab")


@jax.named_scope("embed")
def _embed(params, tokens, cfg, pctx):
    x = params["emb"][tokens]
    return pctx.shard(x, "batch", "seq", "embed")


def _block_prefill(x, lp, positions, cfg, pctx, *, training, collect_kv):
    h = apply_norm(lp["ln1"], x, cfg.norm, cfg.norm_eps)
    attn_out, kv = attention_prefill(
        lp["attn"], h, positions, cfg, pctx,
        window=cfg.sliding_window, training=training,
    )
    x = x + attn_out
    h = apply_norm(lp["ln2"], x, cfg.norm, cfg.norm_eps)
    if cfg.moe:
        ffn_out, aux = moe_apply(lp["moe"], h, cfg, pctx, training=training)
    else:
        ffn_out, aux = mlp_apply(lp["mlp"], h, cfg, pctx, training=training), jnp.float32(0)
    x = pctx.shard(x + ffn_out, "batch", "seq", "embed")
    return x, aux, (kv if collect_kv else None)


def forward_hidden(
    params: dict,
    tokens: jax.Array,  # (B, S)
    cfg: ModelConfig,
    pctx: PartitionCtx = NULL_CTX,
    *,
    training: bool = True,
):
    """Returns (final normed hidden (B,S,d), aux loss)."""
    b, s = tokens.shape
    x = _embed(params, tokens, cfg, pctx)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def body(carry, lp):
        x, aux = carry
        x, aux_l, _ = _block_prefill(x, lp, positions, cfg, pctx, training=training, collect_kv=False)
        return (x, aux + aux_l), None

    body = _remat(body, cfg)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0)), params["layers"])
    return apply_norm(params["ln_f"], x, cfg.norm, cfg.norm_eps), aux


def _head(params, cfg: ModelConfig):
    return params["emb"].T if cfg.tie_embeddings else params["lm_head"]


def forward_train(params, tokens, cfg: ModelConfig, pctx: PartitionCtx = NULL_CTX):
    """Full logits (B, S, Vp) — small-model/test path; training uses the
    chunked loss below to avoid materializing this tensor."""
    x, aux = forward_hidden(params, tokens, cfg, pctx, training=True)
    logits = x.astype(jnp.float32) @ _head(params, cfg).astype(jnp.float32)
    return pctx.shard(logits, "batch", "seq", "vocab"), aux


def loss_fn(params, batch: dict, cfg: ModelConfig, pctx: PartitionCtx = NULL_CTX,
            aux_weight: float = 0.01):
    """batch: tokens (B,S), targets (B,S), mask (B,S)."""
    from repro.train.losses import chunked_ce_loss

    x, aux = forward_hidden(params, batch["tokens"], cfg, pctx, training=True)
    loss = chunked_ce_loss(x, _head(params, cfg), batch["targets"], batch["mask"], pctx)
    return loss + aux_weight * aux / max(cfg.num_layers, 1), {"nll": loss, "aux": aux}


def forward_prefill(
    params: dict,
    tokens: jax.Array,  # (B, S)
    cfg: ModelConfig,
    pctx: PartitionCtx = NULL_CTX,
    *,
    split_tail: bool = False,
    last_pos: Optional[jax.Array] = None,
):
    """The prefill RM.  Returns (logits_last (B, Vp), kv_caches (L-pytree)).

    ``split_tail=True`` returns after the *last layer's attention* with a
    continuation closure — the hook the latency-overlapped swap (paper §3.4,
    Fig. 5) uses: KV is complete at that point, so the controller can launch
    the decode-engine relayout while the tail (last FFN + norm + logits)
    still runs.  See repro.core.swap.

    ``last_pos`` (traced scalar, default S-1) selects which position's
    logits are returned — variable-length prompts right-pad to a compile
    bucket and read the logits of their true last token; causality keeps
    positions <= last_pos independent of the padding tail.
    """
    b, s = tokens.shape
    x = _embed(params, tokens, cfg, pctx)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    n_scan = cfg.num_layers - 1 if split_tail else cfg.num_layers
    scan_layers = jax.tree.map(lambda a: a[:n_scan], params["layers"])

    def body(x, lp):
        x, _, kv = _block_prefill(x, lp, positions, cfg, pctx, training=False, collect_kv=True)
        return x, kv

    x, kvs = jax.lax.scan(body, x, scan_layers)

    if not split_tail:
        # logits only for the last (or requested) position — never (B, S, V)
        x_last = x[:, -1:, :] if last_pos is None else jax.lax.dynamic_slice_in_dim(
            x, last_pos, 1, axis=1)
        logits = _logits(params, x_last, cfg, pctx)
        return logits[:, -1, :], KVCache(kvs[0], kvs[1])

    # --- split point: run the last layer only through its attention ---
    last = jax.tree.map(lambda a: a[-1], params["layers"])
    h = apply_norm(last["ln1"], x, cfg.norm, cfg.norm_eps)
    attn_out, kv_last = attention_prefill(
        last["attn"], h, positions, cfg, pctx, window=cfg.sliding_window, training=False
    )
    x_mid = x + attn_out
    k_all = jnp.concatenate([kvs[0], kv_last[0][None]], axis=0)
    v_all = jnp.concatenate([kvs[1], kv_last[1][None]], axis=0)
    # The caller jits `prefill_tail` as its own program and dispatches the KV
    # relayout in between — that dispatch gap is the paper's overlap window.
    return x_mid, KVCache(k_all, v_all)


def prefill_tail(params, x_mid, cfg: ModelConfig, pctx: PartitionCtx = NULL_CTX,
                 last_pos: Optional[jax.Array] = None):
    """Standalone jittable tail (last FFN + logits) for the overlapped swap."""
    last = jax.tree.map(lambda a: a[-1], params["layers"])
    h2 = apply_norm(last["ln2"], x_mid, cfg.norm, cfg.norm_eps)
    if cfg.moe:
        ffn_out, _ = moe_apply(last["moe"], h2, cfg, pctx, training=False)
    else:
        ffn_out = mlp_apply(last["mlp"], h2, cfg, pctx, training=False)
    x_out = x_mid + ffn_out
    x_last = x_out[:, -1:, :] if last_pos is None else jax.lax.dynamic_slice_in_dim(
        x_out, last_pos, 1, axis=1)
    logits = _logits(params, x_last, cfg, pctx)
    return logits[:, -1, :]


def _prefill_chunk_body(params, tokens, prefix, prefix_len, cfg, pctx,
                        prefix_width=None):
    """Shared chunk forward for both cache layouts: run one prompt chunk
    through the layer stack, each layer attending over the prefill-resident
    fp KV ``prefix`` (valid in ``[0, prefix_len)``) plus the chunk itself.
    Returns (hidden (1, C, d), chunk KV ys (L, 1, Hkv, C, D), new prefix
    with the chunk inserted at ``[prefix_len, prefix_len + C)``).

    ``prefix_width`` (compile-time) truncates the prefix the attention
    SEES to its leading ``prefix_width`` positions — the caller picks a
    ladder bucket >= prefix_len, so a short prompt's chunks never pay
    attention over the buffer's full max_len capacity.  The running
    update still lands in the full-capacity buffer.

    Why an fp prefix mirror rather than re-reading the decode cache: the
    cache may be quantized (``kv_dtype``), and a chunk attending over a
    dequantized prefix would compute hidden states — and therefore KV —
    that drift from the monolithic prefill (which attends its own fp KV).
    The mirror keeps chunked prefill numerically equal to monolithic for
    EVERY kv_dtype; per-token quantize-on-write of the same fp values then
    lands the exact bytes whole-prompt quantization would, so the decode
    trajectory is invariant to chunking.  The mirror is one (L, 1, Hkv,
    Cap, D) fp32 buffer — the same transient footprint the monolithic
    prefill's KV held, bounded by max_len, and shared across requests
    because only one request prefills at a time.
    """
    from repro.layers.attention import attention_prefill_chunk

    b, c = tokens.shape
    x = _embed(params, tokens, cfg, pctx)
    positions = jnp.broadcast_to(prefix_len + jnp.arange(c), (b, c))
    pk, pv = prefix.k, prefix.v
    if prefix_width is not None and prefix_width < pk.shape[3]:
        pk = pk[:, :, :, :prefix_width, :]  # static slice: attention-visible
        pv = pv[:, :, :, :prefix_width, :]  # window of the running prefix

    def body(x, scanned):
        lp, li = scanned
        kp = jax.lax.dynamic_index_in_dim(pk, li, axis=0, keepdims=False)
        vp = jax.lax.dynamic_index_in_dim(pv, li, axis=0, keepdims=False)
        h = apply_norm(lp["ln1"], x, cfg.norm, cfg.norm_eps)
        attn_out, (k_new, v_new) = attention_prefill_chunk(
            lp["attn"], h, kp, vp, prefix_len, cfg, pctx,
            window=cfg.sliding_window, positions=positions,
        )
        x = x + attn_out
        h = apply_norm(lp["ln2"], x, cfg.norm, cfg.norm_eps)
        if cfg.moe:
            ffn_out, _ = moe_apply(lp["moe"], h, cfg, pctx, training=False)
        else:
            ffn_out = mlp_apply(lp["mlp"], h, cfg, pctx, training=False)
        return x + ffn_out, (k_new, v_new)

    x, (tok_k, tok_v) = jax.lax.scan(body, x, (params["layers"], jnp.arange(cfg.num_layers)))
    start = (0, 0, 0, prefix_len, 0)
    with jax.named_scope("kv_write"):
        new_prefix = KVCache(
            jax.lax.dynamic_update_slice(prefix.k, tok_k.astype(prefix.k.dtype), start),
            jax.lax.dynamic_update_slice(prefix.v, tok_v.astype(prefix.v.dtype), start),
        )
    return x, tok_k, tok_v, new_prefix


def prefill_chunk(
    params: dict,
    tokens: jax.Array,  # (1, C) int32 — one right-padded chunk of the prompt
    cache: KVCache,  # (B_slots, L, Hkv, Smax, D) decode cache (donated)
    prefix: KVCache,  # (L, 1, Hkv, Cap, D) fp32 running prefix (donated)
    slot: jax.Array,  # traced scalar — destination slot
    prefix_len: jax.Array,  # traced scalar — tokens already installed
    last_pos: jax.Array,  # traced scalar — chunk-local position of the last real token
    cfg: ModelConfig,
    pctx: PartitionCtx = NULL_CTX,
    prefix_width=None,  # compile-time attention-visible prefix width
):
    """One chunk of prefill installed into the CONTIGUOUS decode cache.

    The chunk's queries attend over the already-prefilled prefix plus the
    chunk itself with a position-offset causal mask (see
    ``_prefill_chunk_body`` for why the prefix is an fp mirror); the
    chunk's KV is installed at ``[prefix_len, prefix_len + C)`` of slot
    ``slot`` by one post-scan ``write_chunk_kv_q`` (quantize-on-write
    under ``kv_dtype``).  Returns (logits (1, Vp) of ``last_pos``,
    new_cache, new_prefix) — intermediate chunks simply ignore the logits
    (the head is one tiny matmul at these chunk sizes).

    Chunk boundaries are a pure function of (prompt length, chunk size), so
    a preemption-restart re-prefills through the exact same programs and
    replay stays bit-identical.
    """
    from repro.layers.attention import write_chunk_kv_q

    x, tok_k, tok_v, new_prefix = _prefill_chunk_body(
        params, tokens, prefix, prefix_len, cfg, pctx, prefix_width=prefix_width)
    new_k = write_chunk_kv_q(cache.k, tok_k, slot, prefix_len)
    new_v = write_chunk_kv_q(cache.v, tok_v, slot, prefix_len)
    x_last = jax.lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
    logits = _logits(params, x_last, cfg, pctx)
    return logits[:, -1, :], KVCache(new_k, new_v), new_prefix


def prefill_chunk_paged(
    params: dict,
    tokens: jax.Array,  # (1, C) int32 — one right-padded chunk, C % bs == 0
    pages: KVCache,  # (N, L, Hkv, bs, D) page pool (donated)
    prefix: KVCache,  # (L, 1, Hkv, Cap, D) fp32 running prefix (donated)
    page_ids: jax.Array,  # (C // bs,) int32 — destinations; OOB entries dropped
    prefix_len: jax.Array,  # traced scalar
    last_pos: jax.Array,  # traced scalar, chunk-local
    cfg: ModelConfig,
    pctx: PartitionCtx = NULL_CTX,
    prefix_width=None,  # compile-time attention-visible prefix width
):
    """One chunk of prefill installed into the PAGED pool —
    ``prefill_chunk`` with the chunk's KV scattered into its own pages by
    ``write_prefill_pages_q`` (quantize-on-write; prefix-cache-hit pages
    arrive as out-of-bounds ids and keep their shared contents).  The
    chunk start is page-aligned (``prefill_chunk % block_size == 0``), so
    every chunk writes whole pages.
    """
    from repro.layers.attention import write_prefill_pages_q

    bs = pages.k.q.shape[3] if hasattr(pages.k, "q") else pages.k.shape[3]
    x, tok_k, tok_v, new_prefix = _prefill_chunk_body(
        params, tokens, prefix, prefix_len, cfg, pctx, prefix_width=prefix_width)
    new_k = write_prefill_pages_q(pages.k, tok_k, page_ids, block_size=bs)
    new_v = write_prefill_pages_q(pages.v, tok_v, page_ids, block_size=bs)
    x_last = jax.lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
    logits = _logits(params, x_last, cfg, pctx)
    return logits[:, -1, :], KVCache(new_k, new_v), new_prefix


def prefill_chunk_kv(
    params: dict,
    tokens: jax.Array,  # (1, C) int32 — one right-padded chunk of the prompt
    prefix: KVCache,  # (L, 1, Hkv, Cap, D) fp32 running prefix (donated)
    prefix_len: jax.Array,  # traced scalar — tokens already prefilled
    last_pos: jax.Array,  # traced scalar, chunk-local
    cfg: ModelConfig,
    pctx: PartitionCtx = NULL_CTX,
    prefix_width=None,  # compile-time attention-visible prefix width
):
    """One chunk of prefill computed WITHOUT an install — the disaggregated
    prefill pool's chunk program.  Identical math to ``prefill_chunk`` /
    ``prefill_chunk_paged`` (same ``_prefill_chunk_body``, same logits
    epilogue); the chunk's fp KV is RETURNED instead of written, so the
    caller can ship it across the pool boundary and install it decode-side
    with the very same quantize-on-write scatter the colocated engine fuses
    in here — which is what keeps the two-pool engine bit-identical.
    Returns (logits (1, Vp) of ``last_pos``, chunk KV (L, 1, Hkv, C, D) fp,
    new_prefix)."""
    x, tok_k, tok_v, new_prefix = _prefill_chunk_body(
        params, tokens, prefix, prefix_len, cfg, pctx, prefix_width=prefix_width)
    x_last = jax.lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
    logits = _logits(params, x_last, cfg, pctx)
    return logits[:, -1, :], KVCache(tok_k, tok_v), new_prefix


def _kv_buffer(shape, dtype, kv_dtype: str):
    """One K or V cache buffer: a plain fp array, or a QuantKV holding the
    packed payload (int8, or uint8 nibble pairs for int4) plus the fp32
    per-(layer, head, token) scale plane."""
    from repro.quant.kv_quant import QuantKV, assert_kv_dtype

    assert_kv_dtype(kv_dtype)
    if kv_dtype == "fp":
        return jnp.zeros(shape, dtype)
    d = shape[-1]
    if kv_dtype == "int4":
        assert d % 2 == 0, f"head_dim must be even for int4 nibble packing, got {d}"
        payload = jnp.zeros(shape[:-1] + (d // 2,), jnp.uint8)
    else:
        payload = jnp.zeros(shape, jnp.int8)
    return QuantKV(payload, jnp.ones(shape[:-1], jnp.float32))


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               kv_dtype: str = "fp") -> KVCache:
    # Decode cache is BATCH-LEADING (B, L, Hkv, S, D): all layers' new
    # tokens for one sequence land in one contiguous DUS window, and the
    # leading dim is the vmap/sharding axis (see attention.scatter_new_tokens).
    # kv_dtype != "fp" stores packed payload + scale planes instead.
    shape = (batch, cfg.num_layers, cfg.num_kv_heads, max_len, cfg.head_dim)
    return KVCache(_kv_buffer(shape, dtype, kv_dtype), _kv_buffer(shape, dtype, kv_dtype))


def init_paged_pool(cfg: ModelConfig, num_blocks: int, block_size: int,
                    dtype=jnp.bfloat16, kv_dtype: str = "fp") -> KVCache:
    # Paged decode cache: the slot axis of init_cache becomes the PAGE axis
    # — (N, L, Hkv, bs, D), each page layer-complete for block_size token
    # positions.  Ownership/refcounts live in serving.paging.PagedKVCache.
    # kv_dtype != "fp" makes each page a packed payload + fp32 scale plane.
    shape = (num_blocks, cfg.num_layers, cfg.num_kv_heads, block_size, cfg.head_dim)
    return KVCache(_kv_buffer(shape, dtype, kv_dtype), _kv_buffer(shape, dtype, kv_dtype))


def _slice_layer(leaf, li):
    """Slice layer ``li`` (axis 1) from a decode-cache leaf; quantized leaves
    are QuantKV pytrees (payload + scale plane) — slice both together."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, li, axis=1, keepdims=False), leaf
    )


def decode_step(
    params: dict,
    token: jax.Array,  # (B,) int32 — current input token
    cache: KVCache,  # (L, B, Hkv, Smax, D)
    lengths: jax.Array,  # (B,)
    cfg: ModelConfig,
    pctx: PartitionCtx = NULL_CTX,
):
    """The decode RM: one step.  Returns (logits (B, Vp), new_cache).

    [§Perf iteration D2] The (batch-leading) cache is closed over and
    READ-ONLY during the scan: each layer dynamic-slices its K/V, the
    online-softmax merge folds the fresh token into the attention output,
    and the scan emits only the tiny (L,B,Hkv,1,D) new-token ys.  One
    post-scan ``scatter_new_tokens`` writes all layers' tokens into the
    (donated, aliased-in-place) cache — per-step cache write traffic is
    O(L*B*Hkv*D), not O(cache).
    """
    from repro.layers.attention import scatter_new_tokens_q

    b = token.shape[0]
    x = _embed(params, token[:, None], cfg, pctx)

    def body(x, scanned):
        lp, li = scanned
        ck = _slice_layer(cache.k, li)
        cv = _slice_layer(cache.v, li)
        h = apply_norm(lp["ln1"], x, cfg.norm, cfg.norm_eps)
        attn_out, new_kv = attention_decode(
            lp["attn"], h, KVCache(ck, cv), lengths, cfg, pctx, window=cfg.sliding_window
        )
        x = x + attn_out
        h = apply_norm(lp["ln2"], x, cfg.norm, cfg.norm_eps)
        if cfg.moe:
            ffn_out, _ = moe_apply(lp["moe"], h, cfg, pctx, training=False)
        else:
            ffn_out = mlp_apply(lp["mlp"], h, cfg, pctx, training=False)
        return x + ffn_out, (new_kv.k, new_kv.v)

    x, (tok_k, tok_v) = jax.lax.scan(body, x, (params["layers"], jnp.arange(cfg.num_layers)))
    new_k = scatter_new_tokens_q(cache.k, tok_k, lengths)
    new_v = scatter_new_tokens_q(cache.v, tok_v, lengths)
    logits = _logits(params, x, cfg, pctx)
    return logits[:, 0, :], KVCache(new_k, new_v)


def _store_roundtrip(cache_leaf):
    """How a cache read-back rounds freshly-written K/V — the function
    ``attention_verify`` applies to block rows so a verify pass sees
    earlier block tokens EXACTLY as sequential decode would after writing
    then re-reading them: a quantize/dequantize round trip for quantized
    caches, None (the concat's storage-dtype cast) for fp."""
    from repro.quant.kv_quant import QuantKV, dequantize_kv, infer_kv_dtype, quantize_kv

    if not isinstance(cache_leaf, QuantKV):
        return None
    dt = infer_kv_dtype(cache_leaf.q)

    def roundtrip(x):
        payload, scale = quantize_kv(x, dt)
        return dequantize_kv(payload, scale, dt)

    return roundtrip


def verify(
    params: dict,
    tokens: jax.Array,  # (B, W) int32 — per slot [last token, draft_1..draft_k]
    cache: KVCache,  # (B, L, Hkv, Smax, D) decode cache (donated)
    lengths: jax.Array,  # (B,) tokens already installed per slot
    n_tokens: jax.Array,  # (B,) real rows per slot (draft_len + 1; 0 = sit out)
    cfg: ModelConfig,
    pctx: PartitionCtx = NULL_CTX,
):
    """The speculative VERIFY pass over the contiguous cache: score a
    W = k+1 token block per slot in one forward.  Returns (logits
    (B, W, Vp), new_cache).

    Structure mirrors ``decode_step``: the cache is READ-ONLY during the
    layer scan (each layer slices its K/V; ``attention_verify`` applies the
    position-offset causal mask over prefix + block), and ONE post-scan
    ``scatter_verify_tokens_q`` writes all layers' block rows in place
    (quantize-on-write) — per-round cache write traffic O(L*B*Hkv*W*D).
    Rows past ``n_tokens`` are dropped by the scatter and their logits are
    garbage the host ignores; the engine truncates slot length / releases
    overshoot pages to roll back rejected rows.  Quantized caches are
    dequantized with the same math the decode jnp path uses, so verify
    reads exactly the fp values plain decode reads.
    """
    from repro.layers.attention import attention_verify, scatter_verify_tokens_q
    from repro.quant.kv_quant import QuantKV, dequantize_kv, infer_kv_dtype

    x = _embed(params, tokens, cfg, pctx)
    positions = lengths[:, None] + jnp.arange(tokens.shape[1])[None, :]
    roundtrip = _store_roundtrip(cache.k)

    def dense(leaf):  # (B, Hkv, Smax, D) fp view of one layer's cache slice
        if isinstance(leaf, QuantKV):
            return dequantize_kv(leaf.q, leaf.scale, infer_kv_dtype(leaf.q))
        return leaf

    def body(x, scanned):
        lp, li = scanned
        ck = dense(_slice_layer(cache.k, li))
        cv = dense(_slice_layer(cache.v, li))
        h = apply_norm(lp["ln1"], x, cfg.norm, cfg.norm_eps)
        attn_out, (k_new, v_new) = attention_verify(
            lp["attn"], h, ck, cv, lengths, cfg, pctx,
            window=cfg.sliding_window, positions=positions,
            store_roundtrip=roundtrip,
        )
        x = x + attn_out
        h = apply_norm(lp["ln2"], x, cfg.norm, cfg.norm_eps)
        if cfg.moe:
            ffn_out, _ = moe_apply(lp["moe"], h, cfg, pctx, training=False)
        else:
            ffn_out = mlp_apply(lp["mlp"], h, cfg, pctx, training=False)
        return x + ffn_out, (k_new, v_new)

    x, (tok_k, tok_v) = jax.lax.scan(body, x, (params["layers"], jnp.arange(cfg.num_layers)))
    new_k = scatter_verify_tokens_q(cache.k, tok_k, lengths, n_tokens)
    new_v = scatter_verify_tokens_q(cache.v, tok_v, lengths, n_tokens)
    logits = _logits(params, x, cfg, pctx)  # ALL W positions — the verify targets
    return logits, KVCache(new_k, new_v)


def verify_paged(
    params: dict,
    tokens: jax.Array,  # (B, W) int32
    pages: KVCache,  # (N, L, Hkv, bs, D) page pool (donated)
    block_tables: jax.Array,  # (B, P) int32
    lengths: jax.Array,  # (B,)
    n_tokens: jax.Array,  # (B,) real rows per slot
    cfg: ModelConfig,
    pctx: PartitionCtx = NULL_CTX,
):
    """The speculative VERIFY pass over the paged pool — ``verify`` with
    each layer's K/V gathered dense through the block table first (the
    paged jnp decode path's move: page ``i`` covers positions ``[i*bs,
    (i+1)*bs)``, so the gathered view places every token at the index the
    contiguous cache would, and paged vs contiguous verify cannot drift).
    The block's KV is scattered into each slot's pages by
    ``scatter_verify_tokens_paged_q`` (quantize-on-write; rows past
    ``n_tokens`` route out of bounds).
    """
    from repro.kernels.paged_attention.ops import gather_scales
    from repro.kernels.paged_attention.ref import gather_pages
    from repro.layers.attention import attention_verify, scatter_verify_tokens_paged_q
    from repro.quant.kv_quant import QuantKV, dequantize_kv, infer_kv_dtype

    x = _embed(params, tokens, cfg, pctx)
    positions = lengths[:, None] + jnp.arange(tokens.shape[1])[None, :]
    roundtrip = _store_roundtrip(pages.k)

    def dense(leaf):  # (B, Hkv, P*bs, D) fp gather of one layer's pages
        if isinstance(leaf, QuantKV):
            return dequantize_kv(gather_pages(leaf.q, block_tables),
                                 gather_scales(leaf.scale, block_tables),
                                 infer_kv_dtype(leaf.q))
        return gather_pages(leaf, block_tables)

    def body(x, scanned):
        lp, li = scanned
        ck = dense(_slice_layer(pages.k, li))
        cv = dense(_slice_layer(pages.v, li))
        h = apply_norm(lp["ln1"], x, cfg.norm, cfg.norm_eps)
        attn_out, (k_new, v_new) = attention_verify(
            lp["attn"], h, ck, cv, lengths, cfg, pctx,
            window=cfg.sliding_window, positions=positions,
            store_roundtrip=roundtrip,
        )
        x = x + attn_out
        h = apply_norm(lp["ln2"], x, cfg.norm, cfg.norm_eps)
        if cfg.moe:
            ffn_out, _ = moe_apply(lp["moe"], h, cfg, pctx, training=False)
        else:
            ffn_out = mlp_apply(lp["mlp"], h, cfg, pctx, training=False)
        return x + ffn_out, (k_new, v_new)

    x, (tok_k, tok_v) = jax.lax.scan(body, x, (params["layers"], jnp.arange(cfg.num_layers)))
    new_k = scatter_verify_tokens_paged_q(pages.k, tok_k, block_tables, lengths, n_tokens)
    new_v = scatter_verify_tokens_paged_q(pages.v, tok_v, block_tables, lengths, n_tokens)
    logits = _logits(params, x, cfg, pctx)
    return logits, KVCache(new_k, new_v)


def decode_step_paged(
    params: dict,
    token: jax.Array,  # (B,) int32 — current input token
    pages: KVCache,  # (N, L, Hkv, bs, D) page pool
    block_tables: jax.Array,  # (B, P) int32
    lengths: jax.Array,  # (B,)
    cfg: ModelConfig,
    pctx: PartitionCtx = NULL_CTX,
):
    """The decode RM over the paged KV cache: one step.

    Structure mirrors ``decode_step``: the pool is closed over and READ-ONLY
    during the layer scan (each layer slices its (N, Hkv, bs, D) plane; the
    online-softmax merge folds the fresh token in), and one post-scan
    ``scatter_new_tokens_paged`` writes all layers' tokens into each
    sequence's current page — per-step write traffic O(L*B*Hkv*D).  Returns
    (logits (B, Vp), new_pages).
    """
    from repro.layers.attention import attention_decode_paged, scatter_new_tokens_paged_q

    x = _embed(params, token[:, None], cfg, pctx)

    def body(x, scanned):
        lp, li = scanned
        pk = _slice_layer(pages.k, li)
        pv = _slice_layer(pages.v, li)
        h = apply_norm(lp["ln1"], x, cfg.norm, cfg.norm_eps)
        attn_out, new_kv = attention_decode_paged(
            lp["attn"], h, pk, pv, block_tables, lengths, cfg, pctx,
            window=cfg.sliding_window,
        )
        x = x + attn_out
        h = apply_norm(lp["ln2"], x, cfg.norm, cfg.norm_eps)
        if cfg.moe:
            ffn_out, _ = moe_apply(lp["moe"], h, cfg, pctx, training=False)
        else:
            ffn_out = mlp_apply(lp["mlp"], h, cfg, pctx, training=False)
        return x + ffn_out, (new_kv.k, new_kv.v)

    x, (tok_k, tok_v) = jax.lax.scan(body, x, (params["layers"], jnp.arange(cfg.num_layers)))
    new_k = scatter_new_tokens_paged_q(pages.k, tok_k, block_tables, lengths)
    new_v = scatter_new_tokens_paged_q(pages.v, tok_v, block_tables, lengths)
    logits = _logits(params, x, cfg, pctx)
    return logits[:, 0, :], KVCache(new_k, new_v)
