"""Mixture-of-Experts FFN with sort-based capacity routing.

Two distribution strategies, selected automatically per arch:

* **EP** (expert parallel) — when ``num_experts % tp_size == 0`` (moonshot:
  64e on a 16-way model axis): experts are sharded over the model axis and
  tokens move via ``all_to_all`` inside ``shard_map`` (GShard/Switch
  pattern).
* **TP** (tensor parallel experts) — otherwise (granite: 40e): every shard
  routes its local tokens to *all* experts and computes the expert FFNs on
  its slice of the expert hidden dim, with one ``psum`` over the model axis
  at the end.

Routing is sort-based (argsort + per-expert rank), never materializing the
(T, E, C) one-hot dispatch tensor — at 1M tokens that tensor is the classic
OOM of naive MoE implementations.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.layers.sharding import PartitionCtx
from repro.quant.ternary import ternary_quantize_ste


def moe_init(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> dict:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in, s_out = 1.0 / d**0.5, 1.0 / f**0.5
    return {
        "router": (jax.random.normal(k1, (d, e), jnp.float32) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(k2, (e, d, f), jnp.float32) * s_in).astype(dtype),
        "w_up": (jax.random.normal(k3, (e, d, f), jnp.float32) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k4, (e, f, d), jnp.float32) * s_out).astype(dtype),
    }


def _maybe_ternary(w: jax.Array, cfg: ModelConfig, training: bool) -> jax.Array:
    if not cfg.quant.ternary:
        return w
    if training:
        w_ste, _ = ternary_quantize_ste(w.astype(jnp.float32))
        return w_ste
    from repro.quant.ternary import ternary_quantize

    w_q, beta = ternary_quantize(w.astype(jnp.float32))
    return (w_q.astype(jnp.float32) * beta).astype(w.dtype)


def _route(gate_logits: jax.Array, k: int, capacity: int, num_experts: int):
    """Sort-based top-k routing.  gate_logits: (T, E) f32.

    Returns (token_idx (T*k,), dest (T*k,) into E*C flat buffer or OOB when
    dropped, combine_w (T*k,) f32).
    """
    t = gate_logits.shape[0]
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(probs, k)  # (T, k)
    topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-9)
    flat_e = topi.reshape(-1)  # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=num_experts)
    offsets = jnp.cumsum(counts) - counts  # exclusive prefix sum
    ranks_sorted = jnp.arange(t * k) - offsets[sorted_e]
    ranks = jnp.zeros((t * k,), jnp.int32).at[order].set(ranks_sorted.astype(jnp.int32))
    keep = ranks < capacity
    dest = jnp.where(keep, flat_e * capacity + ranks, num_experts * capacity)  # OOB -> dropped
    token_idx = jnp.repeat(jnp.arange(t), k)
    return token_idx, dest, topv.reshape(-1), probs


def _expert_ffn(buf: jax.Array, w_gate, w_up, w_down, act: str) -> jax.Array:
    """buf: (E, C, d) -> (E, C, d) through per-expert SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(buf.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(buf.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(buf.dtype))


def _dispatch(x_flat, token_idx, dest, e, c):
    buf = jnp.zeros((e * c + 1, x_flat.shape[-1]), x_flat.dtype)
    buf = buf.at[dest].add(x_flat[token_idx], mode="drop")
    return buf[: e * c].reshape(e, c, -1)


def _combine(y_buf, token_idx, dest, weights, t):
    e_c = y_buf.shape[0] * y_buf.shape[1]
    y_flat = y_buf.reshape(e_c, -1)
    safe = jnp.minimum(dest, e_c - 1)
    contrib = y_flat[safe] * (weights * (dest < e_c))[:, None].astype(y_flat.dtype)
    out = jnp.zeros((t, y_flat.shape[-1]), y_flat.dtype)
    return out.at[token_idx].add(contrib)


# Token-chunk size for the dispatch buffer: bounds the (E, C, d) working set
# to ~hundreds of MB at train_4k scale (65k tokens/shard would need GBs).
MOE_TOKEN_CHUNK = 8192


def _moe_tokens_chunked(x_flat, gate_logits, params, cfg: ModelConfig, *, training,
                        tp_axis, ep, chunk: int = MOE_TOKEN_CHUNK):
    t, d = x_flat.shape
    if t <= chunk:
        return _moe_tokens(x_flat, gate_logits, params, cfg, training=training,
                           tp_axis=tp_axis, ep=ep)
    pad = (-t) % chunk
    if pad:
        x_flat = jnp.pad(x_flat, ((0, pad), (0, 0)))
        gate_logits = jnp.pad(gate_logits, ((0, pad), (0, 0)))
    nc = (t + pad) // chunk

    def body(_, inp):
        xc, gc = inp
        return None, _moe_tokens(xc, gc, params, cfg, training=training,
                                 tp_axis=tp_axis, ep=ep)

    _, ys = jax.lax.scan(
        body, None,
        (x_flat.reshape(nc, chunk, d), gate_logits.reshape(nc, chunk, -1)),
    )
    return ys.reshape(nc * chunk, d)[:t]


def _moe_tokens(x_flat, gate_logits, params, cfg: ModelConfig, *, training: bool,
                tp_axis: Optional[str], ep: bool):
    """Local-view MoE over T tokens.  Runs standalone or inside shard_map."""
    t, d = x_flat.shape
    e, k = cfg.num_experts, cfg.top_k
    cap = max(8, int(t * k / e * cfg.moe_capacity_factor))
    w_gate = _maybe_ternary(params["w_gate"], cfg, training)
    w_up = _maybe_ternary(params["w_up"], cfg, training)
    w_down = _maybe_ternary(params["w_down"], cfg, training)

    token_idx, dest, comb_w, _ = _route(gate_logits, k, cap, e)
    buf = _dispatch(x_flat, token_idx, dest, e, cap)  # (E, C, d)

    if ep and tp_axis is not None:
        # expert-major send buffers to their owner shards (GShard pattern):
        # (E, C, d) --a2a--> (E_loc, n_sh*C, d): local experts, candidate
        # tokens from every source shard (concatenated in shard order).
        recv = jax.lax.all_to_all(buf, tp_axis, split_axis=0, concat_axis=1, tiled=True)
        y_loc = _expert_ffn(recv, w_gate, w_up, w_down, cfg.act)
        # inverse exchange: back to (E, C, d) holding this shard's own tokens
        y_buf = jax.lax.all_to_all(y_loc, tp_axis, split_axis=1, concat_axis=0, tiled=True)
        return _combine(y_buf, token_idx, dest, comb_w, t)

    # TP path: full expert set, hidden dim already sliced by the caller
    y_buf = _expert_ffn(buf, w_gate, w_up, w_down, cfg.act)
    out = _combine(y_buf, token_idx, dest, comb_w, t)
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
    return out


def load_balance_loss(gate_logits: jax.Array, k: int, num_experts: int) -> jax.Array:
    """Switch-style aux loss: E * sum_e f_e * p_e (f: token fraction, p: prob mass)."""
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1).reshape(-1, num_experts)
    _, topi = jax.lax.top_k(probs, k)
    f = jnp.mean(
        jnp.sum(jax.nn.one_hot(topi, num_experts, dtype=jnp.float32), axis=-2), axis=0
    ) / k
    p = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f * p)


def moe_apply(
    params: dict,
    x: jax.Array,  # (B, S, d)
    cfg: ModelConfig,
    pctx: PartitionCtx,
    *,
    training: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,d), aux_loss scalar)."""
    b, s, d = x.shape
    gate_logits = (x.astype(jnp.float32) @ params["router"]).astype(jnp.float32)
    aux = load_balance_loss(gate_logits, cfg.top_k, cfg.num_experts)

    tp = pctx.axes.tp if (pctx.mesh is not None and isinstance(pctx.axes.tp, str)) else None
    if tp is None:
        y = _moe_tokens_chunked(
            x.reshape(b * s, d), gate_logits.reshape(b * s, -1), params, cfg,
            training=training, tp_axis=None, ep=False,
        ).reshape(b, s, d)
        return y.astype(x.dtype), aux

    ep = cfg.num_experts % pctx.tp_size == 0
    dp = pctx.rules.get("batch")
    dp = pctx.axes.dp if dp == "__dp__" else None
    # [§Perf iteration M1] Tokens are SHARDED over the model axis inside the
    # MoE block whenever the sequence divides it.  The earlier P(dp, None,
    # None) spec replicated every token to all tp shards — routing, dispatch
    # and the expert FFNs ran tp_size x redundantly (useful_frac 1/19 on
    # moonshot train) and the all_to_all carried tp_size x the volume.  With
    # seq-sharded tokens: EP archs keep experts sharded + a2a (GShard); the
    # non-divisible-experts archs (granite 40e/16) replicate the (small)
    # expert weights and need NO collective at all inside the block — the
    # output all-gather back to replicated activations is the only cost.
    seq_sharded = s % max(pctx.tp_size, 1) == 0 and pctx.tp_size > 1
    x_spec = P(dp, tp, None) if seq_sharded else P(dp, None, None)
    if ep:
        w_specs = {"router": P(), "w_gate": P(tp, None, None), "w_up": P(tp, None, None), "w_down": P(tp, None, None)}
        inner_tp, inner_ep = tp, True
    elif seq_sharded:
        w_specs = {"router": P(), "w_gate": P(None, None, None), "w_up": P(None, None, None), "w_down": P(None, None, None)}
        inner_tp, inner_ep = None, False  # local experts, no collective
    else:
        w_specs = {"router": P(), "w_gate": P(None, None, tp), "w_up": P(None, None, tp), "w_down": P(None, tp, None)}
        inner_tp, inner_ep = tp, False  # hidden-dim split + psum

    def shard_fn(p, xs, gl):
        bl, sl, _ = xs.shape
        y = _moe_tokens_chunked(
            xs.reshape(bl * sl, d), gl.reshape(bl * sl, -1), p, cfg,
            training=training, tp_axis=inner_tp, ep=inner_ep,
        )
        return y.reshape(bl, sl, d)

    y = jax.shard_map(
        shard_fn,
        mesh=pctx.mesh,
        in_specs=(w_specs, x_spec, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(params, x, gate_logits)
    return y.astype(x.dtype), aux
