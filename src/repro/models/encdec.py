"""Whisper-style encoder-decoder with a stubbed conv frontend.

Per the assignment, the modality frontend is a STUB: ``input_specs()`` feeds
precomputed frame embeddings (B, encoder_seq, d_model).  LayerNorm + GELU,
learned absolute positions (decoder) / sinusoidal (encoder), no RoPE.

PD-Swap mapping (DESIGN.md §4): the encoder is prefill-only; decoder
self-attention swaps prefill/decode RMs; cross-attention KV is computed once
after encoding and then consumed in pure decode-style streaming.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.layers.attention import (
    KVCache,
    attention_decode,
    attention_init,
    attention_prefill,
)
from repro.layers.linear import linear_apply
from repro.layers.mlp import mlp_apply, mlp_init
from repro.layers.norm import apply_norm, norm_init
from repro.layers.sharding import NULL_CTX, PartitionCtx


class EncDecCache(NamedTuple):
    self_kv: KVCache  # (L, B, Hkv, Smax, D)
    cross_kv: KVCache  # (L, B, Hkv, Senc, D)


def _sinusoids(length: int, channels: int) -> jnp.ndarray:
    log_timescale = jnp.log(10000.0) / (channels // 2 - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(channels // 2))
    ang = jnp.arange(length)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=1)


def init(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> dict:
    vp = cfg.padded_vocab()
    ke, kenc, kdec, kp = jax.random.split(key, 4)

    def enc_layer(k):
        ka, kf = jax.random.split(k)
        return {
            "attn": attention_init(cfg, ka, dtype),
            "ln1": norm_init("layernorm", cfg.d_model),
            "mlp": mlp_init(cfg, kf, dtype),
            "ln2": norm_init("layernorm", cfg.d_model),
        }

    def dec_layer(k):
        ka, kx, kf = jax.random.split(k, 3)
        return {
            "attn": attention_init(cfg, ka, dtype),
            "cross": attention_init(cfg, kx, dtype),
            "ln1": norm_init("layernorm", cfg.d_model),
            "lnx": norm_init("layernorm", cfg.d_model),
            "ln2": norm_init("layernorm", cfg.d_model),
            "mlp": mlp_init(cfg, kf, dtype),
        }

    return {
        "emb": (jax.random.normal(ke, (vp, cfg.d_model), jnp.float32) * 0.02).astype(dtype),
        "pos_dec": (jax.random.normal(kp, (cfg.max_position_embeddings, cfg.d_model), jnp.float32) * 0.02).astype(dtype),
        "enc_layers": jax.vmap(enc_layer)(jax.random.split(kenc, cfg.encoder_layers)),
        "dec_layers": jax.vmap(dec_layer)(jax.random.split(kdec, cfg.num_layers)),
        "ln_enc": norm_init("layernorm", cfg.d_model),
        "ln_f": norm_init("layernorm", cfg.d_model),
    }


def encode(params, frames: jax.Array, cfg: ModelConfig, pctx: PartitionCtx = NULL_CTX) -> jax.Array:
    """frames: (B, Senc, d) precomputed embeddings (conv frontend stub)."""
    b, s, d = frames.shape
    x = frames + _sinusoids(s, d).astype(frames.dtype)[None]
    x = pctx.shard(x, "batch", "seq", "embed")
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def body(x, lp):
        h = apply_norm(lp["ln1"], x, "layernorm", cfg.norm_eps)
        attn_out, _ = attention_prefill(lp["attn"], h, positions, cfg, pctx, causal=False, training=False)
        x = x + attn_out
        h = apply_norm(lp["ln2"], x, "layernorm", cfg.norm_eps)
        x = x + mlp_apply(lp["mlp"], h, cfg, pctx, training=False)
        return x, None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return apply_norm(params["ln_enc"], x, "layernorm", cfg.norm_eps)


def compute_cross_kv(params, enc_out: jax.Array, cfg: ModelConfig, pctx: PartitionCtx = NULL_CTX) -> KVCache:
    """Project encoder output into per-decoder-layer cross K/V (done once)."""
    b, s, _ = enc_out.shape
    hkv, hd = cfg.num_kv_heads, cfg.head_dim

    def body(_, lp):
        kw = dict(quant=cfg.quant, training=False)
        k = linear_apply(lp["cross"]["wk"], enc_out, **kw).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
        v = linear_apply(lp["cross"]["wv"], enc_out, **kw).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
        return None, (k, v)

    _, (ks, vs) = jax.lax.scan(body, None, params["dec_layers"])
    return KVCache(ks, vs)


def _dec_block_prefill(x, lp, positions, cross_k, cross_v, cfg, pctx, *, training):
    h = apply_norm(lp["ln1"], x, "layernorm", cfg.norm_eps)
    attn_out, kv = attention_prefill(lp["attn"], h, positions, cfg, pctx, training=training)
    x = x + attn_out
    h = apply_norm(lp["lnx"], x, "layernorm", cfg.norm_eps)
    cross_out, _ = attention_prefill(
        lp["cross"], h, positions, cfg, pctx, causal=False, training=training,
        cross_kv=(cross_k, cross_v),
    )
    x = x + cross_out
    h = apply_norm(lp["ln2"], x, "layernorm", cfg.norm_eps)
    x = x + mlp_apply(lp["mlp"], h, cfg, pctx, training=training)
    return pctx.shard(x, "batch", "seq", "embed"), kv


def _decoder_hidden(params, tokens, cross: KVCache, cfg, pctx, *, training, collect_kv, pos_offset=0):
    b, s = tokens.shape
    x = params["emb"][tokens] + params["pos_dec"][pos_offset : pos_offset + s][None]
    x = pctx.shard(x, "batch", "seq", "embed")
    positions = jnp.broadcast_to(jnp.arange(pos_offset, pos_offset + s), (b, s))

    def body(x, scanned):
        lp, ck, cv = scanned
        x, kv = _dec_block_prefill(x, lp, positions, ck, cv, cfg, pctx, training=training)
        return x, (kv if collect_kv else None)

    if cfg.remat != "none" and training:
        body = jax.checkpoint(body)
    x, kvs = jax.lax.scan(body, x, (params["dec_layers"], cross.k, cross.v))
    return apply_norm(params["ln_f"], x, "layernorm", cfg.norm_eps), kvs


def forward_train(params, batch_inputs, cfg: ModelConfig, pctx: PartitionCtx = NULL_CTX):
    """batch_inputs: dict with 'frames' (B,Senc,d) and 'tokens' (B,S)."""
    enc_out = encode(params, batch_inputs["frames"], cfg, pctx)
    cross = compute_cross_kv(params, enc_out, cfg, pctx)
    x, _ = _decoder_hidden(params, batch_inputs["tokens"], cross, cfg, pctx, training=True, collect_kv=False)
    logits = x.astype(jnp.float32) @ params["emb"].astype(jnp.float32).T
    return pctx.shard(logits, "batch", "seq", "vocab"), jnp.float32(0)


def loss_fn(params, batch, cfg, pctx: PartitionCtx = NULL_CTX, aux_weight: float = 0.0):
    from repro.train.losses import chunked_ce_loss

    enc_out = encode(params, batch["frames"], cfg, pctx)
    cross = compute_cross_kv(params, enc_out, cfg, pctx)
    x, _ = _decoder_hidden(params, batch["tokens"], cross, cfg, pctx, training=True, collect_kv=False)
    loss = chunked_ce_loss(x, params["emb"].T, batch["targets"], batch["mask"], pctx)
    return loss, {"nll": loss, "aux": jnp.float32(0)}


def _padded_enc_seq(cfg: ModelConfig) -> int:
    """Cross-KV cache seq padded to a 128 multiple (1500 -> 1536) so the
    decode cache shards evenly over a 16-way axis; the padded tail is masked
    via ``cross_len``."""
    return ((cfg.encoder_seq + 127) // 128) * 128


def forward_prefill(params, tokens, cfg: ModelConfig, pctx: PartitionCtx = NULL_CTX, *, frames=None):
    """Encode + decoder prefill.  Returns (last logits, EncDecCache)."""
    enc_out = encode(params, frames, cfg, pctx)
    cross = compute_cross_kv(params, enc_out, cfg, pctx)
    x, kvs = _decoder_hidden(params, tokens, cross, cfg, pctx, training=False, collect_kv=True)
    logits = x[:, -1:, :].astype(jnp.float32) @ params["emb"].astype(jnp.float32).T
    pad = _padded_enc_seq(cfg) - cfg.encoder_seq
    cross_padded = KVCache(
        jnp.pad(cross.k, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))),
        jnp.pad(cross.v, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))),
    )
    return logits[:, -1, :], EncDecCache(KVCache(kvs[0], kvs[1]), cross_padded)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> EncDecCache:
    l = cfg.num_layers
    # batch-leading (B, L, Hkv, S, D) — see attention.scatter_new_tokens
    mk = lambda s: jnp.zeros((batch, l, cfg.num_kv_heads, s, cfg.head_dim), dtype)
    se = _padded_enc_seq(cfg)
    return EncDecCache(KVCache(mk(max_len), mk(max_len)), KVCache(mk(se), mk(se)))


def decode_step(params, token, cache: EncDecCache, lengths, cfg: ModelConfig, pctx: PartitionCtx = NULL_CTX):
    """[§Perf iteration D2] Self-attention KV read-only through the scan
    (merge handles the fresh token); one post-scan scatter writes all
    layers' tokens.  Cross-KV never updates."""
    from repro.layers.attention import scatter_new_tokens

    b = token.shape[0]
    x = params["emb"][token[:, None]]
    pos = params["pos_dec"][lengths][:, None, :]  # (B,1,d) gather per-sequence position
    x = x + pos

    def body(x, scanned):
        lp, li = scanned
        ck = jax.lax.dynamic_index_in_dim(cache.self_kv.k, li, axis=1, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cache.self_kv.v, li, axis=1, keepdims=False)
        xk = jax.lax.dynamic_index_in_dim(cache.cross_kv.k, li, axis=1, keepdims=False)
        xv = jax.lax.dynamic_index_in_dim(cache.cross_kv.v, li, axis=1, keepdims=False)
        h = apply_norm(lp["ln1"], x, "layernorm", cfg.norm_eps)
        attn_out, new_kv = attention_decode(lp["attn"], h, KVCache(ck, cv), lengths, cfg, pctx)
        x = x + attn_out
        h = apply_norm(lp["lnx"], x, "layernorm", cfg.norm_eps)
        cross_out, _ = attention_decode(
            lp["cross"], h, KVCache(xk, xv), lengths, cfg, pctx, cross_kv=(xk, xv),
            cross_len=cfg.encoder_seq,  # mask the 1500->1536 sharding pad
        )
        x = x + cross_out
        h = apply_norm(lp["ln2"], x, "layernorm", cfg.norm_eps)
        x = x + mlp_apply(lp["mlp"], h, cfg, pctx, training=False)
        return x, (new_kv.k, new_kv.v)

    x, (tok_k, tok_v) = jax.lax.scan(
        body, x, (params["dec_layers"], jnp.arange(cfg.num_layers)),
    )
    ks = scatter_new_tokens(cache.self_kv.k, tok_k, lengths)
    vs = scatter_new_tokens(cache.self_kv.v, tok_v, lengths)
    x = apply_norm(params["ln_f"], x, "layernorm", cfg.norm_eps)
    logits = x.astype(jnp.float32) @ params["emb"].astype(jnp.float32).T
    return logits[:, 0, :], EncDecCache(KVCache(ks, vs), cache.cross_kv)
