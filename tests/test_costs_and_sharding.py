"""Analytic kernel-cost models and sharding-rule helpers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.configs.base import SHAPES, applicable_shapes
from repro.core.kernel_substitution import kernel_costs_for_cell
from repro.kernels.costs import (
    decode_attention_cost,
    mlstm_chunk_cost,
    prefill_attention_cost,
    tlmm_cost,
)


def test_decode_cost_is_kv_stream_bound():
    """Decode attention reads K and V exactly once per KV-head group."""
    b, h, hkv, s, d = 8, 32, 32, 2048, 128
    c = decode_attention_cost(b, h, hkv, s, d)
    kv_bytes = b * hkv * s * d * 2 * 2
    assert kv_bytes <= c.hbm_bytes <= 1.05 * kv_bytes + 1e6


def test_decode_cost_gqa_shares_kv_stream():
    full = decode_attention_cost(4, 32, 32, 4096, 128)
    gqa = decode_attention_cost(4, 32, 8, 4096, 128)  # 4 q heads per kv head
    assert gqa.hbm_bytes < full.hbm_bytes / 3.5  # ~4x less KV traffic
    assert abs(gqa.flops - full.flops) / full.flops < 0.01  # same math


def test_decode_cost_window_caps_traffic():
    full = decode_attention_cost(4, 8, 8, 32768, 128)
    win = decode_attention_cost(4, 8, 8, 32768, 128, window=1024)
    assert win.hbm_bytes < full.hbm_bytes / 16


def test_prefill_cost_causal_half_of_full():
    causal = prefill_attention_cost(2, 8, 8, 4096, 128, causal=True)
    full = prefill_attention_cost(2, 8, 8, 4096, 128, causal=False)
    assert 0.4 < causal.flops / full.flops < 0.6


def test_prefill_cost_quadratic_in_seq():
    a = prefill_attention_cost(1, 8, 8, 4096, 128)
    b = prefill_attention_cost(1, 8, 8, 8192, 128)
    assert 3.5 < b.flops / a.flops < 4.5


def test_vmem_budgets_fit_v5e():
    from repro.common.hardware import TPU_V5E

    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for cell in applicable_shapes(cfg):
            c = kernel_costs_for_cell(cfg, cell, dp=16, tp=16)
            assert c.vmem_bytes < TPU_V5E.vmem_bytes, (arch, cell.name, c.vmem_bytes)


def test_kernel_cost_scales_down_with_mesh():
    cfg = get_config("deepseek-7b")
    cell = SHAPES["decode_32k"]
    small = kernel_costs_for_cell(cfg, cell, dp=16, tp=16)
    big = kernel_costs_for_cell(cfg, cell, dp=32, tp=16)  # multi-pod
    assert big.hbm_bytes < small.hbm_bytes


def test_mlstm_cost_linear_in_seq():
    a = mlstm_chunk_cost(2, 4, 8192, 512)
    b = mlstm_chunk_cost(2, 4, 16384, 512)
    assert 1.9 < b.flops / a.flops < 2.1  # sub-quadratic: linear in S


def test_tlmm_cost_quarter_byte_weights():
    c = tlmm_cost(128, 4096, 4096)
    w_bytes_min = 4096 * 4096 / 4
    assert c.hbm_bytes >= w_bytes_min
    assert c.flops == 2 * 128 * 4096 * 4096


# ------------------------------------------------------------- sharding ----


def test_sanitize_spec_drops_indivisible_axes():
    from repro.layers.sharding import sanitize_spec
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()  # 1 device: every axis size 1 -> all divisible
    spec = sanitize_spec(P("data", "model"), (7, 13), mesh)
    assert spec == P("data", "model")  # size-1 axes always divide


def test_param_pspec_rules_cover_all_archs():
    """Every arch's full param tree gets a spec without error, and TP'd
    dims are actually divisible after sanitation (the xlstm w_if case)."""
    import os

    from repro.launch.sharding_rules import eval_shape_params, params_shardings

    if jax.device_count() != 1:
        pytest.skip("host test")
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        params = eval_shape_params(cfg, dtype=jnp.bfloat16)
        sh = params_shardings(params, cfg, mesh, train=True)
        assert len(jax.tree.leaves(sh)) == len(jax.tree.leaves(params))


@pytest.mark.parametrize("name,tp_dim", [("wq", 2), ("wv", 2), ("w_up", 2),
                                         ("wo", 1), ("w_down", 1)])
def test_packed_linear_shards_like_its_latent_weight(name, tp_dim):
    """A packed ternary linear keeps its latent weight's tensor-parallel dim
    (N for an output-parallel linear, the packed K/4 rows for an
    input-parallel one); its per-layer scale is replicated."""
    from repro.launch.sharding_rules import param_pspec

    blk = "mlp" if name.startswith("w_") else "attn"
    leaf = jax.ShapeDtypeStruct((24, 384, 4096), jnp.uint8)
    latent = param_pspec(f"layers/{blk}/{name}/w", jax.ShapeDtypeStruct((24, 1536, 4096),
                                                                      jnp.float32),
                         tp="model", fsdp=None, ep=False)
    packed = param_pspec(f"layers/{blk}/{name}/w/packed", leaf, tp="model", fsdp=None, ep=False)
    assert packed == latent and packed[tp_dim] == "model"
    scale = param_pspec(f"layers/{blk}/{name}/w/scale", jax.ShapeDtypeStruct((24,), jnp.float32),
                        tp="model", fsdp=None, ep=False)
    assert scale == P(None)
    # norms keep their own rule
    assert param_pspec("layers/ln1/scale", jax.ShapeDtypeStruct((24, 1536), jnp.float32),
                       tp="model", fsdp=None, ep=False) == P(None, None)
