"""The cost and peak tables: counts pinned to hand arithmetic (the dense
family's work, ``bench.reference.dense``, read through the loader)."""
import json

import pytest

from bench.harness import peaks, spec
from bench.tests.tiny import BENCH


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def costs(c):
    return spec.family(c)


@pytest.mark.parametrize("name,params,kv", [
    # 24 x 28,311,552 linear + 32002 x 1536 embedding + 24 x 2 x 1536 + 1536 norms
    ("bitnet-730m", 728_707_584, 147_456),
    # 12 x (275,251,200 linear + 2 x 5120 norms + 56 x 128 biases) + 2 x 152064 x 5120 + 5120
    ("qwen2.5-14b", 4_860_363_776, 49_152),
])
def test_params_and_kv_bytes(name, params, kv):
    c = cfg(name)
    assert costs(c).param_count(c) == params
    assert costs(c).kv_bytes_per_token(c) == kv


def test_weight_bytes_at_stated_storage():
    # ternary linears at 2 bits, the tied embedding and norms at 2 bytes
    b = cfg("bitnet-730m")
    assert costs(b).weight_bytes(b) == 24 * 28_311_552 / 4 + (32002 * 1536 + 24 * 3072 + 1536) * 2
    q = cfg("qwen2.5-14b")
    assert costs(q).weight_bytes(q) == 2 * 4_860_363_776  # 9.72 GB of bfloat16


def test_decode_work():
    b = cfg("bitnet-730m")
    w = costs(b).decode(b, {"decode_rounds": 10, "slot_rounds": 40,
                            "decode_ctx_tokens": 40 * 7000})
    assert w.int8_ops == 2.0 * 24 * 28_311_552 * 40
    assert w.bf16_flops == 2.0 * 1536 * 32002 * 40 + 4.0 * 24 * 24 * 64 * (40 * 7000 + 40)
    assert w.bytes == 10 * costs(b).weight_bytes(b) + (40 * 7000 + 40) * 147_456
    # memory bound: 4 streams at 7000 tokens read ~4.1 GB of KV a round
    peak = peaks.peak_for("TPU v5 lite")
    assert w.seconds(peak) == pytest.approx(w.bytes / 819e9)
    assert w.bytes / 10 / 819e9 == pytest.approx(5.37e-3, rel=0.01)


def test_prefill_work():
    q = cfg("qwen2.5-14b")
    n = 1024
    w = costs(q).prefill(q, n)
    lin = 2.0 * 12 * 275_251_200 * n
    head = 2.0 * 5120 * 152064
    attn = 4.0 * 12 * 40 * 128 * n * (n + 1) / 2
    assert w.int8_ops == 0 and w.bf16_flops == pytest.approx(lin + head + attn)
    # compute bound, about 50 ms of the v5e's bfloat16 peak
    assert w.seconds(peaks.peak_for("TPU v5 lite")) == pytest.approx(w.bf16_flops / 197e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peak_for("TPU v9 imaginary")
    assert peaks.peak_for("TPU v5 lite").hbm_bw == 819e9
