"""Prefill/decode attention kernels: shape/dtype/schedule sweeps vs oracles,
plus the quantized-KV subsystem (int4 pack/unpack properties and the
fused-dequant kernel parity)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; the parametrized sweeps still run
    def given(*_a, **_k):
        return lambda f: pytest.mark.skip(reason="property tests need hypothesis")(f)

    def settings(*_a, **_k):
        return lambda f: f

    class _NullStrategies:
        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _NullStrategies()

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.prefill_attention.ops import prefill_attention
from repro.kernels.prefill_attention.ref import prefill_attention_reference
from repro.quant.kv_quant import QMAX, dequantize_kv, pack_int4, quantize_kv, unpack_int4


def _qkv(b, h, hkv, s, d, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("schedule", ["reverse", "forward"])
@pytest.mark.parametrize(
    "b,h,hkv,s,d,blk",
    [
        (1, 2, 2, 128, 64, 64),
        (2, 4, 2, 256, 64, 64),
        (2, 8, 2, 128, 128, 128),  # single kv block
        (1, 3, 1, 192, 32, 64),  # odd head count, GQA g=3
    ],
)
def test_prefill_kernel_sweep(schedule, b, h, hkv, s, d, blk):
    q, k, v = _qkv(b, h, hkv, s, d, seed=s + h)
    ref = prefill_attention_reference(q, k, v)
    out = prefill_attention(q, k, v, blk=blk, schedule=schedule, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_prefill_bf16():
    q, k, v = _qkv(1, 2, 2, 128, 64, dtype=jnp.bfloat16)
    ref = prefill_attention_reference(q, k, v)
    out = prefill_attention(q, k, v, blk=64, use_kernel=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=3e-2, atol=3e-2
    )


def test_prefill_reverse_equals_forward():
    """The paper's reverse schedule is a pure reordering — identical output."""
    q, k, v = _qkv(2, 4, 4, 256, 64, seed=3)
    a = prefill_attention(q, k, v, blk=64, schedule="reverse", use_kernel=True)
    b = prefill_attention(q, k, v, blk=64, schedule="forward", use_kernel=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "b,h,hkv,s,d,bk",
    [
        (2, 4, 2, 256, 64, 64),
        (1, 8, 1, 512, 64, 128),  # MQA
        (3, 6, 2, 128, 32, 32),
        (2, 2, 2, 64, 128, 64),  # MHA single block
    ],
)
def test_decode_kernel_sweep(b, h, hkv, s, d, bk):
    rng = np.random.default_rng(b * s + d)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    lengths = jnp.asarray(rng.integers(1, s + 1, size=(b,)), jnp.int32)
    ref = decode_attention(q, k, v, lengths, use_kernel=False)
    out = decode_attention(q, k, v, lengths, bk=bk, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@given(
    st.integers(1, 3),  # batch
    st.integers(1, 4),  # kv heads
    st.integers(1, 4),  # group size
    st.sampled_from([64, 128, 192]),  # cache len
    st.integers(0, 2**31 - 1),
)
@settings(max_examples=20, deadline=None)
def test_decode_window_property(b, hkv, g, s, seed):
    """Sliding-window decode == full decode over the truncated cache."""
    rng = np.random.default_rng(seed)
    h = hkv * g
    d = 32
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    length = int(rng.integers(1, s + 1))
    window = int(rng.integers(1, length + 1))
    lengths = jnp.full((b,), length, jnp.int32)
    starts = jnp.full((b,), length - window, jnp.int32)
    out = decode_attention(q, k, v, lengths, starts, bk=32, use_kernel=True)
    # oracle: zero-out everything outside the window by slicing
    ref = decode_attention(
        q, k[:, :, length - window : length], v[:, :, length - window : length],
        jnp.full((b,), window, jnp.int32), use_kernel=False,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_decode_stats_merge_matches_appended_cache(use_kernel):
    """attend(cache) + online-softmax merge of a fresh token ==
    attend(cache with the token appended) — the [§Perf D2] decode identity
    (attend-then-merge replaces update-then-attend)."""
    import math

    from repro.layers.attention import _merge_new_token

    rng = np.random.default_rng(7)
    b, hkv, g, s, d = 2, 2, 3, 64, 32
    h = hkv * g
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    lengths = jnp.asarray([13, 40], jnp.int32)
    k_new = jnp.asarray(rng.normal(size=(b, hkv, 1, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(b, hkv, 1, d)), jnp.float32)
    sm = 1.0 / math.sqrt(d)

    out_c, l_c, m_c = decode_attention(
        q, k, v, lengths, use_kernel=use_kernel, bk=32, return_stats=True
    )
    merged = _merge_new_token(out_c, l_c, m_c, q, k_new, v_new, sm)

    # reference: physically append the token at position `length`
    def append(buf, new):
        return jnp.stack([
            jax.lax.dynamic_update_slice(buf[i], new[i], (0, int(lengths[i]), 0))
            for i in range(b)
        ])

    k2, v2 = append(k, k_new), append(v, v_new)
    ref = decode_attention(q, k2, v2, lengths + 1, use_kernel=False)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(ref), rtol=3e-5, atol=3e-5)


# ------------------------------------------------ KV quantization (kv_dtype) --


@given(
    st.integers(1, 4),  # leading rows
    st.sampled_from([2, 8, 32, 64]),  # head_dim (even — nibble pairs)
    st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_int4_pack_unpack_roundtrip(rows, d, seed):
    """Nibble packing is lossless over the full int4 range [-8, 7]."""
    rng = np.random.default_rng(seed)
    q = rng.integers(-8, 8, size=(rows, 3, d)).astype(np.int8)
    packed = pack_int4(jnp.asarray(q))
    assert packed.shape == (rows, 3, d // 2) and packed.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed)), q)


@given(
    st.sampled_from(["int8", "int4"]),
    st.integers(1, 3),  # rows
    st.sampled_from([4, 16, 64]),  # head_dim
    st.integers(0, 2**31 - 1),
    st.floats(1e-2, 1e2),  # magnitude sweep: scales must track dynamic range
)
@settings(max_examples=30, deadline=None)
def test_kv_quant_error_bound_and_idempotent_requantization(kv_dtype, rows, d, seed, mag):
    """Symmetric per-row absmax quantization: reconstruction error is within
    half a quantization step, and requantizing the dequantized values is a
    payload FIXED POINT — the property bit-identical preemption replay
    rests on (same values -> same page bytes, every time)."""
    rng = np.random.default_rng(seed)
    x = (rng.normal(size=(rows, 2, 5, d)) * mag).astype(np.float32)
    payload, scale = quantize_kv(jnp.asarray(x), kv_dtype)
    assert scale.shape == x.shape[:-1]
    xh = np.asarray(dequantize_kv(payload, scale, kv_dtype))
    step = np.abs(x).max(axis=-1, keepdims=True) / QMAX[kv_dtype]
    assert np.all(np.abs(xh - x) <= step / 2 + 1e-4 * mag)
    # fixed point: quantize(dequantize(quantize(x))) == quantize(x) bit-for-bit
    p2, s2 = quantize_kv(jnp.asarray(xh), kv_dtype)
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(payload))
    np.testing.assert_allclose(np.asarray(s2), np.asarray(scale), rtol=2e-6)


def test_kv_quant_zero_rows_are_safe():
    """All-zero rows must not divide by zero and must reconstruct as zero."""
    x = jnp.zeros((2, 3, 8), jnp.float32)
    for kv_dtype in ("int8", "int4"):
        payload, scale = quantize_kv(x, kv_dtype)
        assert np.all(np.asarray(scale) == 1.0)
        np.testing.assert_array_equal(np.asarray(dequantize_kv(payload, scale, kv_dtype)), 0.0)


@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
@pytest.mark.parametrize(
    "b,h,hkv,s,d,bk",
    [
        (2, 4, 2, 37, 32, 16),  # partial final block, GQA
        (1, 8, 1, 130, 64, 64),  # MQA, partial final block
        (3, 6, 2, 64, 32, 32),  # exact blocks
    ],
)
def test_decode_quant_kernel_matches_dequant_reference(kv_dtype, b, h, hkv, s, d, bk):
    """Fused-dequant contiguous decode kernel == dequantize-then-attend
    oracle, through the op-level dispatch (randomized ragged lengths)."""
    rng = np.random.default_rng(b * s + d)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    kq, ks = quantize_kv(k, kv_dtype)
    vq, vs = quantize_kv(v, kv_dtype)
    lengths = jnp.asarray(rng.integers(1, s + 1, size=(b,)), jnp.int32)
    ref = decode_attention(q, kq, vq, lengths, k_scales=ks, v_scales=vs,
                           kv_dtype=kv_dtype, use_kernel=False)
    out = decode_attention(q, kq, vq, lengths, k_scales=ks, v_scales=vs,
                           kv_dtype=kv_dtype, bk=bk, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_decode_quant_tracks_fp_within_quant_error(kv_dtype):
    """The quantized decode output stays close to the fp output — the
    accuracy/bandwidth trade-off is bounded by the quantization step."""
    rng = np.random.default_rng(9)
    b, h, hkv, s, d = 2, 4, 2, 48, 32
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    lengths = jnp.asarray([s, 17], jnp.int32)
    fp = decode_attention(q, k, v, lengths, use_kernel=False)
    kq, ks = quantize_kv(k, kv_dtype)
    vq, vs = quantize_kv(v, kv_dtype)
    qd = decode_attention(q, kq, vq, lengths, k_scales=ks, v_scales=vs,
                          kv_dtype=kv_dtype, use_kernel=False)
    tol = {"int8": 0.05, "int4": 0.6}[kv_dtype]  # ~attention of one quant step
    assert float(jnp.max(jnp.abs(qd - fp))) < tol


@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
@pytest.mark.parametrize(
    "b,hkv,g,d,bs,n_pages_seq",
    [
        (2, 2, 2, 32, 8, 3),
        (1, 1, 4, 64, 16, 2),  # MHA-as-GQA grouping
        (3, 2, 1, 32, 4, 4),  # g=1
    ],
)
def test_paged_quant_kernel_matches_reference_at_ragged_lengths(
    kv_dtype, b, hkv, g, d, bs, n_pages_seq
):
    """Fused-dequant paged decode kernel == dequantize-the-pool oracle on
    randomized shuffled block tables and ragged lengths (partial pages)."""
    from repro.kernels.paged_attention.kernel import paged_decode_attention_quant_pallas
    from repro.kernels.paged_attention.ref import paged_decode_attention_quant_reference

    rng = np.random.default_rng(d + bs)
    n_blocks = b * n_pages_seq + 2
    q = jnp.asarray(rng.normal(size=(b, hkv, g, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_blocks, hkv, bs, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_blocks, hkv, bs, d)), jnp.float32)
    kq, ks = quantize_kv(kp, kv_dtype)
    vq, vs = quantize_kv(vp, kv_dtype)
    perm = rng.permutation(n_blocks)[: b * n_pages_seq].reshape(b, n_pages_seq)
    tables = jnp.asarray(perm, jnp.int32)
    lengths = jnp.asarray(rng.integers(1, n_pages_seq * bs + 1, size=b), jnp.int32)
    ref = paged_decode_attention_quant_reference(
        q, kq, ks, vq, vs, tables, lengths, kv_dtype=kv_dtype)
    out, _, _ = paged_decode_attention_quant_pallas(
        q, kq, ks, vq, vs, tables, lengths, kv_dtype=kv_dtype, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_decode_stats_empty_cache_merge_is_new_token_only():
    """lengths=0: merge must return attention over just the fresh token
    (softmax of one logit = that token's V)."""
    import math

    from repro.layers.attention import _merge_new_token

    rng = np.random.default_rng(8)
    b, hkv, g, s, d = 1, 1, 2, 32, 16
    h = hkv * g
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(b, hkv, 1, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(b, hkv, 1, d)), jnp.float32)
    lengths = jnp.zeros((b,), jnp.int32)
    out_c, l_c, m_c = decode_attention(q, k, v, lengths, return_stats=True)
    merged = _merge_new_token(out_c, l_c, m_c, q, k_new, v_new, 1.0 / math.sqrt(d))
    expect = jnp.broadcast_to(v_new[:, :, 0, :][:, :, None, :], (b, hkv, g, d)).reshape(b, h, d)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(expect), atol=1e-5)
