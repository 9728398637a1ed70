"""TLMM kernel: shape/dtype sweeps vs the jnp oracle + the paper's LUT
algorithm, and hypothesis property tests on the packing format."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.tlmm.kernel import tlmm_pallas
from repro.kernels.tlmm.ops import tlmm_matmul
from repro.kernels.tlmm.ref import tlmm_lut_reference, tlmm_reference
from repro.quant.act_quant import quantize_activations_int8
from repro.quant.ternary import (
    pack_ternary,
    quantize_and_pack,
    ternary_quantize,
    unpack_ternary,
)


def _mk(m, k, n, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    return x, quantize_and_pack(w)


@pytest.mark.parametrize(
    "m,k,n,bm,bn,bk",
    [
        (8, 64, 128, 8, 128, 64),
        (16, 256, 128, 8, 128, 64),
        (32, 512, 256, 16, 128, 128),
        (128, 1024, 512, 128, 256, 512),
        (8, 128, 384, 8, 128, 32),  # bn not dividing n exercises ops fallback
    ],
)
def test_kernel_matches_reference_shapes(m, k, n, bm, bn, bk):
    x, tw = _mk(m, k, n, seed=m + k + n)
    x_q, s = quantize_activations_int8(x)
    scale = s * tw.scale
    ref = tlmm_reference(x_q, tw.packed, scale, out_dtype=jnp.float32)
    if n % bn == 0 and k % bk == 0 and m % bm == 0:
        out = tlmm_pallas(x_q, tw.packed, scale, bm=bm, bn=bn, bk=bk, out_dtype=jnp.float32, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)
    out2 = tlmm_matmul(x, tw, use_kernel=True, out_dtype=jnp.float32,
                       block_m=bm, block_n=bn, block_k=bk)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_dtype_sweep(out_dtype):
    x, tw = _mk(16, 256, 128)
    ref = tlmm_matmul(x, tw, use_kernel=False, out_dtype=out_dtype)
    out = tlmm_matmul(x, tw, use_kernel=True, out_dtype=out_dtype)
    np.testing.assert_allclose(
        np.asarray(ref, np.float32), np.asarray(out, np.float32), rtol=2e-2, atol=2e-2
    )


def test_lut_algorithm_bit_exact():
    """The paper's index->lookup->accumulate == direct int matmul, exactly."""
    x, tw = _mk(4, 64, 32, seed=7)
    x_q, s = quantize_activations_int8(x)
    scale = s * tw.scale
    a = tlmm_reference(x_q, tw.packed, scale, out_dtype=jnp.float32)
    b = tlmm_lut_reference(x_q, tw.packed, scale, out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(st.integers(1, 64), st.integers(1, 16), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_pack_unpack_roundtrip(kq, n, seed):
    rng = np.random.default_rng(seed)
    w_q = jnp.asarray(rng.integers(-1, 2, size=(kq * 4, n)), jnp.int8)
    assert (unpack_ternary(pack_ternary(w_q)) == w_q).all()


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_absmean_quantizer_properties(seed):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(32, 16)) * rng.uniform(0.1, 10), jnp.float32)
    w_q, beta = ternary_quantize(w)
    assert set(np.unique(np.asarray(w_q))) <= {-1, 0, 1}
    assert float(beta) > 0
    # dequantized error is bounded by the quantization step
    err = np.abs(np.asarray(w) - np.asarray(w_q, np.float32) * float(beta))
    assert err.max() <= max(float(beta) * 1.5, float(np.abs(np.asarray(w)).max() - float(beta)))


def test_memory_footprint_is_quarter_byte():
    _, tw = _mk(8, 1024, 256)
    assert tw.packed.size == 1024 * 256 // 4
    assert tw.packed.dtype == jnp.uint8
