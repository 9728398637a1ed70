"""The work a step needs, from the configuration's shapes alone.

Operations and bytes here do not depend on how the program computes them:
they are what the model's equations need, with weights counted at the
storage the configuration states (2 bits per ternary weight, 2 bytes per
bfloat16 one) and the KV cache at the cell's KV type.  A step that reads
more than this, or computes more, shows as a lower share of the roofline.

Operations are split by the peak they run at: the ternary model's linear
layers are int8 arithmetic (W1.58-A8), everything else bfloat16.
"""
from __future__ import annotations

import dataclasses

KV_BYTES = {"fp": 2, "int8": 1, "int4": 0.5}  # per cached element
TERNARY_BITS = 2  # the packed storage of one ternary weight


@dataclasses.dataclass(frozen=True)
class Work:
    int8_ops: float = 0.0
    bf16_flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.int8_ops + o.int8_ops, self.bf16_flops + o.bf16_flops,
                    self.bytes + o.bytes)

    def seconds(self, peak) -> float:
        """The least time the chip could take: compute or memory, whichever
        bounds it."""
        compute = self.int8_ops / peak.int8_ops + self.bf16_flops / peak.bf16_flops
        return max(compute, self.bytes / peak.hbm_bw)


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


def decode(c: dict, rounds: int, slot_rounds: int, ctx_tokens: int,
           kv_dtype: str = "fp") -> Work:
    """``rounds`` decode rounds that served ``slot_rounds`` stream-steps over
    contexts summing to ``ctx_tokens`` (cached tokens before each round):
    each round reads every weight once; each stream-step reads its cached
    tokens once, attends them and itself, and writes its new token's KV."""
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
