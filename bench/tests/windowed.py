"""A family module that no benchmark configuration names, for the tests: the
dense block (``bench.reference.dense``) with sliding-window attention, each
query attending its ``sliding_window`` most recent positions, itself
included (``ModelConfig.sliding_window``).  It brings what a new block
brings: its own ``model_fields``, layout, float32 reference and work.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.costs import Work
from bench.reference import dense
from bench.reference.model import (CONTROLS, Q_BLOCK, _logits, _Math, _rope,  # noqa: F401
                                   _spec, gaps)


def model_fields(c: dict) -> dict:
    return dict(dense.model_fields(c), sliding_window=int(c["sliding_window"]))


def layout(c: dict):
    """The dense block's leaves: a window changes no weight."""
    return dense.layout(c)


@functools.partial(jax.jit, static_argnames=("spec", "window", "precision"))
def _layer(x, lw, spec, window, precision):
    d, h, kv, hd, theta, eps, ternary = spec
    m = _Math(precision, ternary)
    s = x.shape[0]
    pos = jnp.arange(s)
    att, mlp = lw["attn"], lw["mlp"]
    n1 = m.rms(x, lw["ln1"]["scale"], eps)
    q = m.linear(n1, att["wq"]["w"], att["wq"].get("b")).reshape(s, h, hd)
    k = m.linear(n1, att["wk"]["w"], att["wk"].get("b")).reshape(s, kv, hd)
    v = m.linear(n1, att["wv"]["w"], att["wv"].get("b")).reshape(s, kv, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = h // kv
    qb = q.reshape(s // Q_BLOCK, Q_BLOCK, kv, g, hd)

    def block(args):
        i, qi = args  # qi: (Q_BLOCK, kv, g, hd)
        sc = m.mm("qkgd,tkd->kgqt", qi, k).astype(jnp.float32) / math.sqrt(hd)
        back = (i * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None] - pos[None, :]
        sc = jnp.where((back >= 0) & (back < window), sc, -jnp.inf)
        return m.mm("kgqt,tkd->qkgd", jax.nn.softmax(sc, axis=-1), v)

    o = jax.lax.map(block, (jnp.arange(s // Q_BLOCK), qb)).reshape(s, h * hd)
    x = x + m.linear(o, att["wo"]["w"])
    n2 = m.rms(x, lw["ln2"]["scale"], eps)
    gate = m.linear(n2, mlp["w_gate"]["w"]).astype(jnp.float32)
    up = m.linear(n2, mlp["w_up"]["w"]).astype(jnp.float32)
    return x + m.linear(m.f(jax.nn.silu(gate) * up), mlp["w_down"]["w"])


def logits(weights: dict, c: dict, tokens, positions, precision: str = "reference"):
    """``bench.reference.model.logits`` with the window in every layer."""
    tokens = np.asarray(tokens, np.int32)
    positions = np.asarray(positions, np.int32)
    positions = np.pad(positions, (0, -len(positions) % Q_BLOCK), mode="edge")
    ids = jnp.asarray(np.pad(tokens, (0, -len(tokens) % Q_BLOCK)))
    m = _Math(precision, c["weights"] == "ternary")
    x = m.f(weights["emb"][ids])
    spec = _spec(c)
    for i in range(c["num_hidden_layers"]):
        x = _layer(x, jax.tree.map(lambda a: a[i], weights["layers"]), spec,
                   int(c["sliding_window"]), precision)
    tied = bool(c["tie_word_embeddings"])
    head = weights["emb"] if tied else weights["lm_head"]
    return _logits(x[jnp.asarray(positions)], weights["ln_f"]["scale"], head, spec, precision,
                   tied)


weight_bytes = dense.weight_bytes
kv_bytes_per_token = dense.kv_bytes_per_token


def _attention(c: dict, pairs: float) -> Work:
    _, L, h, _, hd, _, _ = dense._dims(c)
    return Work(bf16_flops=4.0 * L * h * hd * pairs)


def decode(c: dict, stats: dict, kv_dtype: str = "fp") -> Work:
    """The dense block's decode with each stream-step reading and attending
    at most ``sliding_window`` cached positions: exact where every stream's
    context lies on one side of the window, an upper bound where they
    straddle it (the counters hold only the contexts' sum)."""
    w, steps = int(c["sliding_window"]), stats["slot_rounds"]
    pairs = min(stats["decode_ctx_tokens"] + steps, steps * w)
    return (dense._matmul_work(c, steps) + _attention(c, pairs)
            + Work(bytes=stats["decode_rounds"] * weight_bytes(c)
                   + pairs * kv_bytes_per_token(c, kv_dtype)))


def prefill(c: dict, prompt_len: int) -> Work:
    """The dense prefill with position ``i`` attending ``min(i + 1, window)``
    positions."""
    n, w = prompt_len, int(c["sliding_window"])
    pairs = n * (n + 1) / 2 if n <= w else w * (w + 1) / 2 + (n - w) * w
    return dense.prefill(c, n) + _attention(c, pairs - n * (n + 1) / 2)
