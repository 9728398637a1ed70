"""The dense family: a LLaMA-style decoder block (bitnet-730m, qwen2.5-14b).

What the harness takes from a configuration's family module
(``bench.harness.spec``): the program's ``ModelConfig`` fields, the weight
layout, the plain float32 reference (``bench.reference.model``) and the
roofline work.  Like the reference, it imports nothing of the program.

The work is what the model's equations need, whatever way the program
computes it: weights at the storage the configuration states (2 bits per
ternary weight, 2 bytes per bfloat16 one) and the KV cache at the cell's KV
type.  A step that reads more than this, or computes more, shows as a lower
share of the roofline.  The ternary model's linear layers are int8
arithmetic (W1.58-A8), everything else bfloat16.
"""
from __future__ import annotations

import dataclasses

from bench.harness.costs import KV_BYTES, TERNARY_BITS, Work
from bench.reference.model import CONTROLS, Q_BLOCK, gaps, logits  # noqa: F401

# bench config key -> repro ModelConfig field
_MODEL_FIELDS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
}


def model_fields(c: dict) -> dict:
    """Every size the file states, on a SwiGLU block with RMSNorm, full
    attention and no experts."""
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{c['name']}: only SwiGLU blocks are served")
    fields = {f: c[k] for k, f in _MODEL_FIELDS.items() if k in c}
    return dict(fields, norm="rmsnorm", act="silu", moe=False, sliding_window=None)


def layout(c: dict):
    """(path, shape, init) for every leaf, in the layer-stacked layout the
    serving engine takes: ``{"emb", "layers": {"attn", "ln1", "ln2",
    "mlp"}, "ln_f", "lm_head"}``, every per-layer leaf with a leading layer
    axis."""
    d, L, h, kv, hd, f = (c["hidden_size"], c["num_hidden_layers"],
                          c["num_attention_heads"], c["num_key_value_heads"],
                          c["head_dim"], c["intermediate_size"])
    v = c["vocab_size"]
    leaves = [
        (("emb",), (v, d), ("embed", 0.02)),
        (("ln_f", "scale"), (d,), ("gain",)),
        (("layers", "ln1", "scale"), (L, d), ("gain",)),
        (("layers", "ln2", "scale"), (L, d), ("gain",)),
        (("layers", "attn", "wq", "w"), (L, d, h * hd), ("normal", d ** -0.5)),
        (("layers", "attn", "wk", "w"), (L, d, kv * hd), ("normal", d ** -0.5)),
        (("layers", "attn", "wv", "w"), (L, d, kv * hd), ("normal", d ** -0.5)),
        (("layers", "attn", "wo", "w"), (L, h * hd, d), ("normal", (h * hd) ** -0.5)),
        (("layers", "mlp", "w_gate", "w"), (L, d, f), ("normal", d ** -0.5)),
        (("layers", "mlp", "w_up", "w"), (L, d, f), ("normal", d ** -0.5)),
        (("layers", "mlp", "w_down", "w"), (L, f, d), ("normal", f ** -0.5)),
    ]
    if c["attention_bias"]:
        for name, width in (("wq", h * hd), ("wk", kv * hd), ("wv", kv * hd)):
            leaves.append((("layers", "attn", name, "b"), (L, width), ("bias",)))
    if not c["tie_word_embeddings"]:
        leaves.append((("lm_head",), (d, v), ("head", 0.02)))
    return leaves


def _dims(c: dict):
    return (c["hidden_size"], c["num_hidden_layers"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"])


def linear_params_per_layer(c: dict) -> int:
    d, _, h, kv, hd, f, _ = _dims(c)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def other_params(c: dict) -> int:
    """Parameters outside the linear layers: embedding, head, norms, biases."""
    d, L, h, kv, hd, _, V = _dims(c)
    emb = V * d * (1 if c["tie_word_embeddings"] else 2)
    bias = (h + 2 * kv) * hd if c["attention_bias"] else 0
    return emb + L * (2 * d + bias) + d


def param_count(c: dict) -> int:
    return c["num_hidden_layers"] * linear_params_per_layer(c) + other_params(c)


def weight_bytes(c: dict) -> float:
    """The weights at the storage the configuration states."""
    lin = c["num_hidden_layers"] * linear_params_per_layer(c)
    if c["weights"] == "ternary":
        return lin * TERNARY_BITS / 8 + other_params(c) * 2
    if c["weights"] == "bfloat16":
        return (lin + other_params(c)) * 2
    raise ValueError(f"unknown weight storage {c['weights']!r}")


def kv_bytes_per_token(c: dict, kv_dtype: str = "fp") -> float:
    _, L, _, kv, hd, _, _ = _dims(c)
    return 2 * L * kv * hd * KV_BYTES[kv_dtype]


def _matmul_work(c: dict, tokens: int) -> Work:
    """The linear layers and the head for ``tokens`` rows (head: one row
    per token given)."""
    d, L, _, _, _, _, V = _dims(c)
    lin = 2.0 * L * linear_params_per_layer(c) * tokens
    head = 2.0 * d * V * tokens
    if c["weights"] == "ternary":
        return Work(int8_ops=lin, bf16_flops=head)
    return Work(bf16_flops=lin + head)


def _attention_flops(c: dict, pairs: float) -> float:
    """QK and PV over ``pairs`` (query, key) pairs, in every layer."""
    _, L, h, _, hd, _, _ = _dims(c)
    return 4.0 * L * h * hd * pairs


def decode(c: dict, stats: dict, kv_dtype: str = "fp") -> Work:
    """The window's decode rounds (``stats``' ``decode_rounds``), which
    served ``slot_rounds`` stream-steps over contexts summing to
    ``decode_ctx_tokens`` (cached tokens before each round): each round
    reads every weight once; each stream-step reads its cached tokens once,
    attends them and itself, and writes its new token's KV."""
    rounds, slot_rounds = stats["decode_rounds"], stats["slot_rounds"]
    ctx_tokens = stats["decode_ctx_tokens"]
    kvb = kv_bytes_per_token(c, kv_dtype)
    return _matmul_work(c, slot_rounds) + Work(
        bf16_flops=_attention_flops(c, ctx_tokens + slot_rounds),
        bytes=rounds * weight_bytes(c) + (ctx_tokens + slot_rounds) * kvb)


def prefill(c: dict, prompt_len: int) -> Work:
    """One prompt's prefill: every token through the linear layers, causal
    attention at each token's position, logits of the last token, the
    weights read once and the prompt's KV written."""
    n = prompt_len
    d, L, _, _, _, _, V = _dims(c)
    lin = _matmul_work(c, n)
    head_extra = 2.0 * d * V * (n - 1)  # _matmul_work counted n head rows
    lin = dataclasses.replace(
        lin, bf16_flops=lin.bf16_flops - head_extra)
    return lin + Work(bf16_flops=_attention_flops(c, n * (n + 1) / 2),
                      bytes=weight_bytes(c) + n * kv_bytes_per_token(c))
