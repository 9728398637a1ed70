"""Plain forward pass of the dense family's decoder-only block, in float32
(``bench.reference.dense`` is the family module that re-exports it).

It imports nothing of the program.  It follows the published equations:

* a LLaMA-style block (Touvron et al. 2023; Qwen2 has the same block):
  ``x += Wo(attn(RoPE(Wq n1(x)), RoPE(Wk n1(x)), Wv n1(x)))``, then
  ``x += Wdown(silu(Wgate n2(x)) * Wup n2(x))``, with RMSNorm
  ``x / sqrt(mean(x^2) + eps) * g``, rotate-half RoPE, causal softmax
  attention scaled by ``1/sqrt(head_dim)``, grouped KV heads, q/k/v biases
  where the configuration has them, and a final RMSNorm and head;
* for BitNet b1.58 (arXiv:2402.17764) every linear layer of the blocks
  quantizes its weight by absmean, ``round(W / (mean|W| + eps))`` clipped
  to [-1, 1] times ``mean|W|``, and its input per token by absmax to int8.
  The embedding and the head stay in float.

Departures, each within what the comparison allows: the KV is kept in
float32 (the engine stores it in bfloat16), and attention runs in blocks of
queries over the whole sequence.

``precision="reference"`` computes in float32 with matmuls at ``highest``.
The other settings are the controls that ``bench/calibrate.py`` and the
tests show to fail the comparison: each computes one step below what the
configuration states.

* ``bf16``: the ternary model with every float operation (the residual
  stream, norms, attention, the scaled linear outputs and the head) in
  bfloat16 at default precision; the int8 activations stay.
* ``int4_activations``: the ternary model with its activations quantized
  per token to int4 (absmax to +-7) instead of int8; the float math stays.
* ``int4_activations_bf16``: both of the above at once.
* ``int8_w8a8``: a bfloat16 model whose linear layers (head included) take
  int8 weights and activations, scaled by absmax per output channel and
  per token.
* ``fp8_e4m3``: the same with float8 e4m3 weights and activations, scaled
  to the format's range.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512  # queries per attention block; sequences pad to a multiple
EPS_QUANT = 1e-5
CONTROLS = ("bf16", "int4_activations", "int4_activations_bf16", "int8_w8a8", "fp8_e4m3")
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _spec(c: dict) -> tuple:
    """The hashable sizes the jitted functions are specialised on."""
    return (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], float(c["rope_theta"]), float(c["rms_norm_eps"]),
            c["weights"] == "ternary")


class _Math:
    """Arithmetic of one precision setting."""

    def __init__(self, precision: str, ternary: bool):
        if precision not in ("reference",) + CONTROLS:
            raise ValueError(f"unknown precision {precision!r}")
        self.low = precision in ("bf16", "int4_activations_bf16")
        self.levels = 7 if precision.startswith("int4_activations") else 127
        self.int8 = precision == "int8_w8a8"
        self.fp8 = precision == "fp8_e4m3"
        self.ternary = ternary
        self.dt = jnp.bfloat16 if self.low else jnp.float32
        self.prec = jax.lax.Precision.DEFAULT if self.low else jax.lax.Precision.HIGHEST

    def f(self, x):
        return x.astype(self.dt)

    def mm(self, eq, a, b):
        return jnp.einsum(eq, self.f(a), self.f(b), precision=self.prec,
                          preferred_element_type=jnp.float32).astype(self.dt)

    def act_quant(self, x, axis=-1, levels=127):
        """Absmax to +-levels along ``axis``: integer values and the scale."""
        x = x.astype(jnp.float32)
        s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / levels + EPS_QUANT
        return jnp.clip(jnp.round(x / s), -levels, levels), s

    def linear(self, x, w, b=None):
        """``x @ w (+ b)`` for one linear layer of a block."""
        if self.ternary:
            w = w.astype(jnp.float32)
            beta = jnp.mean(jnp.abs(w))
            wq = jnp.clip(jnp.round(w / (beta + EPS_QUANT)), -1, 1)
            xq, s = self.act_quant(x, levels=self.levels)
            # integer operands: exact in float32 (and in bfloat16) products
            y = jnp.einsum("sk,kn->sn", xq, wq, precision=jax.lax.Precision.HIGHEST)
            y = self.f(y * s * beta)
        else:
            y = self.head(x, w)
        if b is not None:
            y = y + self.f(b)
        return y

    def head(self, x, w):
        """A float linear map (the head, or any linear of a float model)."""
        if self.int8:
            (xq, sx), (wq, sw) = self.act_quant(x), self.act_quant(w, axis=0)
            y = jnp.einsum("sk,kn->sn", xq, wq, precision=jax.lax.Precision.HIGHEST)
            return y * sx * sw
        if self.fp8:
            def fp8(a, axis):
                sc = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX + EPS_QUANT
                return (a / sc).astype(jnp.float8_e4m3fn).astype(jnp.float32) * sc
            w8, x8 = fp8(w.astype(jnp.float32), 0), fp8(x.astype(jnp.float32), -1)
            return jnp.einsum("sk,kn->sn", x8, w8, precision=jax.lax.Precision.HIGHEST)
        return self.mm("sk,kn->sn", x, w)

    def rms(self, x, g, eps):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return self.f(y * g.astype(jnp.float32))


def _rope(x, pos, theta):
    """Rotate-half RoPE; x: (S, H, D), pos: (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., : d // 2].astype(jnp.float32), x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def _layer(x, lw, spec, precision):
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
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(qpos[:, None] >= pos[None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return m.mm("kgqt,tkd->qkgd", p, v)

    o = jax.lax.map(block, (jnp.arange(s // Q_BLOCK), qb)).reshape(s, h * hd)
    x = x + m.linear(o, att["wo"]["w"])
    n2 = m.rms(x, lw["ln2"]["scale"], eps)
    gate = m.linear(n2, mlp["w_gate"]["w"]).astype(jnp.float32)
    up = m.linear(n2, mlp["w_up"]["w"]).astype(jnp.float32)
    return x + m.linear(m.f(jax.nn.silu(gate) * up), mlp["w_down"]["w"])


@functools.partial(jax.jit, static_argnames=("spec", "precision", "tied"))
def _logits(x, ln_f, head, spec, precision, tied):
    m = _Math(precision, spec[-1])
    n = m.rms(x, ln_f, spec[5])
    w = head.T if tied else head
    if m.ternary:  # the ternary model's head is a float layer
        return m.mm("sk,kn->sn", n, w).astype(jnp.float32)
    return m.head(n, w).astype(jnp.float32)


def logits(weights: dict, c: dict, tokens, positions, precision: str = "reference"):
    """Logits (padded vocab wide), float32, at ``positions`` of the causal
    forward over ``tokens``, one layer at a time so that only one layer's
    float32 copy of its weights is live.  ``positions`` is padded, by
    repeating its last entry, to a multiple of ``Q_BLOCK`` rows (so that
    few shapes compile); the rows past ``len(positions)`` are that pad."""
    tokens = np.asarray(tokens, np.int32)
    positions = np.asarray(positions, np.int32)
    positions = np.pad(positions, (0, -len(positions) % Q_BLOCK), mode="edge")
    s = len(tokens)
    pad = -s % Q_BLOCK
    ids = jnp.asarray(np.pad(tokens, (0, pad)))
    m = _Math(precision, c["weights"] == "ternary")
    x = m.f(weights["emb"][ids])
    spec = _spec(c)
    for i in range(c["num_hidden_layers"]):
        lw = jax.tree.map(lambda a: a[i], weights["layers"])
        x = _layer(x, lw, spec, precision)
    x = x[jnp.asarray(positions)]
    tied = bool(c["tie_word_embeddings"])
    head = weights["emb"] if tied else weights["lm_head"]
    return _logits(x, weights["ln_f"]["scale"], head, spec, precision, tied)


@jax.jit
def gaps(ref_logits, tokens):
    """How far each token's reference logit lies below the reference's best."""
    picked = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return jnp.max(ref_logits, axis=-1) - picked
