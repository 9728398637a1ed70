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
from repro.kernels.tlmm.ref import scale_epilogue, tlmm_lut_reference, tlmm_reference
from repro.quant.act_quant import quantize_activations_int8
from repro.quant.ternary import (
    TernaryWeight,
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
        (8, 128, 384, 8, 128, 32),  # N / 4 is no multiple of 128: the wrapper picks 128
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
    # the wrapper, with the blocks it picks from the shape
    out2 = tlmm_matmul(x, tw, use_kernel=True, out_dtype=jnp.float32)
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


# --- resident packed weights: the model-level conversion and the XLA path ---

def _bitnet(layers=3):
    from repro.configs import reduced_config
    from repro.models import get_model

    cfg = reduced_config("bitnet-730m", num_layers=layers)
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    # layers of different magnitude: one beta for the whole stack is wrong
    gain = jnp.asarray([0.5, 1.0, 3.0][:layers], jnp.float32)
    for blk in ("attn", "mlp"):
        for lin in params["layers"][blk].values():
            lin["w"] = lin["w"] * gain[:, None, None]
    return cfg, params


@pytest.mark.parametrize("block,name", [("attn", "wq"), ("attn", "wo"),
                                        ("mlp", "w_up"), ("mlp", "w_down")])
def test_conversion_takes_one_beta_per_layer(block, name):
    from repro.models.transformer import convert_for_inference

    cfg, params = _bitnet()
    w = params["layers"][block][name]["w"]
    tw = convert_for_inference(cfg, params)["layers"][block][name]["w"]
    assert tw.packed.shape == (w.shape[0], w.shape[1] // 4, w.shape[2])
    assert tw.packed.dtype == jnp.uint8 and tw.scale.shape == (w.shape[0],)
    betas = []
    for l in range(w.shape[0]):
        w_q, beta = ternary_quantize(w[l])
        np.testing.assert_array_equal(np.asarray(tw.scale[l]), np.asarray(beta))
        np.testing.assert_array_equal(np.asarray(tw.packed[l]), np.asarray(pack_ternary(w_q)))
        betas.append(float(beta))
    _, stack_beta = ternary_quantize(w)
    assert not np.allclose(betas, float(stack_beta), rtol=0.05)


@pytest.mark.parametrize("arch", ["smollm-135m", "qwen2.5-14b"])
def test_conversion_is_identity_without_ternary(arch):
    from repro.configs import reduced_config
    from repro.models import get_model
    from repro.models.transformer import convert_for_inference, linear_residency

    cfg = reduced_config(arch)
    assert not cfg.quant.ternary
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    assert convert_for_inference(cfg, params) is params
    assert linear_residency(cfg, params) == (0, 0)


def test_conversion_leaves_the_callers_tree():
    from repro.models.transformer import convert_for_inference, linear_residency

    cfg, params = _bitnet()
    before = jax.tree.map(np.asarray, params)
    ids = {k: id(v["w"]) for k, v in params["layers"]["attn"].items()}
    conv = convert_for_inference(cfg, params)
    assert all(id(params["layers"]["attn"][k]["w"]) == i for k, i in ids.items())
    jax.tree.map(np.testing.assert_array_equal, jax.tree.map(np.asarray, params), before)
    assert isinstance(conv["layers"]["mlp"]["w_gate"]["w"], TernaryWeight)
    assert conv["emb"] is params["emb"] and conv["layers"]["ln1"] is params["layers"]["ln1"]
    assert linear_residency(cfg, params) == (0, 7 * 3)
    assert linear_residency(cfg, conv) == (7 * 3, 0)
    assert convert_for_inference(cfg, conv)["layers"]["attn"]["wq"]["w"] is \
        conv["layers"]["attn"]["wq"]["w"]


@pytest.mark.parametrize("m,k,n", [(1, 64, 128), (4, 1536 // 4, 256), (13, 512, 96)])
def test_slot_major_reference_equals_unpack_then_dot(m, k, n):
    x, tw = _mk(m, k, n, seed=m * k)
    x_q, s = quantize_activations_int8(x)
    scale = s * tw.scale
    acc = jax.lax.dot_general(x_q, unpack_ternary(tw.packed), (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    for out_dtype in (jnp.float32, jnp.bfloat16):
        want = scale_epilogue(acc, scale, out_dtype)
        got = jax.jit(tlmm_reference, static_argnames="out_dtype")(
            x_q, tw.packed, scale, out_dtype=out_dtype)
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


@pytest.mark.parametrize("shape,dtype", [((4, 128), jnp.float32), ((2, 3, 256), jnp.float32),
                                         ((8, 512), jnp.bfloat16)])
def test_packed_and_latent_linear_agree_bit_for_bit(shape, dtype):
    from repro.configs.base import QuantConfig
    from repro.layers.linear import linear_apply

    rng = np.random.default_rng(shape[-1])
    x = jnp.asarray(rng.normal(size=shape), dtype)
    w = jnp.asarray(rng.normal(size=(shape[-1], 192)) * 0.05, jnp.float32)
    b = jnp.asarray(rng.normal(size=(192,)), jnp.float32)
    quant = QuantConfig(mode="ternary")
    apply = jax.jit(lambda p, x: linear_apply(p, x, quant))
    latent = apply({"w": w, "b": b}, x)
    packed = apply({"w": quantize_and_pack(w), "b": b}, x)
    assert packed.dtype == latent.dtype == dtype
    np.testing.assert_array_equal(np.asarray(packed, np.float32), np.asarray(latent, np.float32))


def test_moe_experts_stay_latent_and_are_counted():
    import dataclasses

    from repro.configs import reduced_config
    from repro.configs.base import QuantConfig
    from repro.models import get_model
    from repro.models.transformer import convert_for_inference, linear_residency

    cfg = dataclasses.replace(reduced_config("granite-moe-3b-a800m"),
                              quant=QuantConfig(mode="ternary"))
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    conv = convert_for_inference(cfg, params)
    assert conv["layers"]["moe"] is params["layers"]["moe"]
    experts = 3 * cfg.num_layers * cfg.num_experts
    assert linear_residency(cfg, params) == (0, 4 * cfg.num_layers + experts)
    assert linear_residency(cfg, conv) == (4 * cfg.num_layers, experts)


# --- the serving path: the kernel with the tiles chosen from the shape ---

def _ternary(k, n, seed, scale=0.37):
    rng = np.random.default_rng(seed)
    w_q = jnp.asarray(rng.integers(-1, 2, size=(k, n)), jnp.int8)
    return w_q, TernaryWeight(pack_ternary(w_q), jnp.float32(scale))


@pytest.mark.parametrize("k,n", [(1536, 1536), (1536, 4096), (4096, 1536)])
@pytest.mark.parametrize("m", [1, 4, 12])
def test_decode_tiles_are_bit_exact_at_bitnet_shapes(m, k, n):
    from repro.kernels.tlmm.ops import tlmm_tiles

    w_q, tw = _ternary(k, n, seed=m + k + n)
    bm, bn, bk = tlmm_tiles(m, k, n)
    # a decode call: all rows in one block, the whole of K, four weight tiles
    assert (bm, bk) == (m, k) and n // bn == 4
    x = jnp.asarray(np.random.default_rng(m).normal(size=(m, k)), jnp.float32)
    x_q, s = quantize_activations_int8(x)
    # int32 sums: a unit scale and a float32 output hold them exactly
    acc = jax.lax.dot_general(x_q, w_q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    sums = tlmm_pallas(x_q, tw.packed, jnp.ones((m, 1), jnp.float32), bm=bm, bn=bn, bk=bk,
                       out_dtype=jnp.float32, interpret=True)
    np.testing.assert_array_equal(np.asarray(sums), np.asarray(acc, np.float32))
    scale = s * tw.scale
    for out_dtype in (jnp.float32, jnp.bfloat16):
        want = tlmm_reference(x_q, tw.packed, scale, out_dtype=out_dtype)
        got = tlmm_matmul(x, tw, use_kernel=True, out_dtype=out_dtype)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_layer_stacked_weight_in_a_scan_matches_reference():
    layers, m, k, n = 3, 4, 256, 512
    packed, scales = [], []
    for l in range(layers):
        _, tw = _ternary(k, n, seed=l, scale=0.1 * (l + 1))
        packed.append(tw.packed)
        scales.append(tw.scale)
    stack = TernaryWeight(jnp.stack(packed), jnp.stack(scales))
    x = jnp.asarray(np.random.default_rng(5).normal(size=(m, k)), jnp.float32)

    def run(use_kernel):
        def body(h, w):
            y = tlmm_matmul(h, w, out_dtype=jnp.float32, use_kernel=use_kernel)
            return h, y
        return jax.jit(lambda s: jax.lax.scan(body, x, s)[1])(stack)

    got, want = run(True), run(False)
    assert got.shape == (layers, m, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # each step reads its own layer of the stack
    for l in range(layers):
        one = TernaryWeight(stack.packed[l], stack.scale[l])
        np.testing.assert_allclose(np.asarray(got[l]), np.asarray(
            tlmm_matmul(x, one, out_dtype=jnp.float32, use_kernel=False)), rtol=1e-6)


def _runs_kernel(x, tw) -> bool:
    jaxpr = jax.make_jaxpr(lambda x: tlmm_matmul(x, tw))(x)
    return "pallas_call" in str(jaxpr)


def test_path_follows_the_platform(monkeypatch):
    import repro.kernels as kernels

    _, tw = _ternary(256, 512, seed=1)
    x = jnp.ones((4, 256), jnp.float32)
    assert not kernels.compiled_kernels()
    assert not _runs_kernel(x, tw)  # the CPU: the reference
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)  # as on a TPU
    assert kernels.compiled_kernels() and _runs_kernel(x, tw)
    with kernels.partitioned():  # GSPMD cannot partition a Mosaic kernel
        assert not kernels.compiled_kernels() and not _runs_kernel(x, tw)
    assert _runs_kernel(x, tw)
    # a shape the kernel cannot tile takes the reference
    _, odd = _ternary(24, 128, seed=2)
    assert not _runs_kernel(jnp.ones((4, 24), jnp.float32), odd)
