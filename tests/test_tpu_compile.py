"""Compile rehearsals: every Pallas kernel family of the serving path,
compiled by Mosaic for a described (not attached) TPU v5e, at bitnet-730m
widths.  Nothing runs; a kernel the chip's compiler would refuse (a block
that breaks the tiling rule, an op the target lacks) fails here.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.  All such compiles live in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.kernel import decode_attention_pallas, decode_attention_quant_pallas
from repro.kernels.paged_attention.kernel import (
    paged_decode_attention_pallas,
    paged_decode_attention_quant_pallas,
)
from repro.kernels.prefill_attention.kernel import prefill_attention_pallas
from repro.kernels.tlmm.kernel import tlmm_pallas

CFG = get_config("bitnet-730m")
H, HKV, D = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
G = H // HKV
SLOTS, MAX_LEN, PROMPT, BLOCK = 4, 1024, 1024, 16
PAGES = MAX_LEN // BLOCK
POOL = SLOTS * PAGES
PAYLOAD = {"int8": (D, jnp.int8), "int4": (D // 2, jnp.uint8)}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_prefill_compiles(one_chip):
    _assert_kernel(_compile(
        one_chip, lambda q, k, v: prefill_attention_pallas(q, k, v, blk=256),
        ((1, H, PROMPT, D), jnp.bfloat16), ((1, HKV, PROMPT, D), jnp.bfloat16),
        ((1, HKV, PROMPT, D), jnp.bfloat16)))


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_decode_compiles(one_chip, layout):
    if layout == "contiguous":
        fn = lambda q, k, v, lengths: decode_attention_pallas(q, k, v, lengths)  # noqa: E731
        kv = ((SLOTS, HKV, MAX_LEN, D), jnp.bfloat16)
        extra = ()
    else:
        fn = lambda q, k, v, tables, lengths: paged_decode_attention_pallas(  # noqa: E731
            q, k, v, tables, lengths)
        kv = ((POOL, HKV, BLOCK, D), jnp.bfloat16)
        extra = (((SLOTS, PAGES), jnp.int32),)
    _assert_kernel(_compile(one_chip, fn, ((SLOTS, HKV, G, D), jnp.bfloat16), kv, kv,
                            *extra, ((SLOTS,), jnp.int32)))


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_quantized_decode_compiles(one_chip, layout, kv_dtype):
    dp, dt = PAYLOAD[kv_dtype]
    if layout == "contiguous":
        def fn(q, kq, ks, vq, vs, lengths):
            return decode_attention_quant_pallas(q, kq, ks, vq, vs, lengths, kv_dtype=kv_dtype)
        rows = (SLOTS, HKV, MAX_LEN)
        extra = ()
    else:
        def fn(q, kq, ks, vq, vs, tables, lengths):
            return paged_decode_attention_quant_pallas(
                q, kq, ks, vq, vs, tables, lengths, kv_dtype=kv_dtype)
        rows = (POOL, HKV, BLOCK)
        extra = (((SLOTS, PAGES), jnp.int32),)
    payload, scales = ((*rows, dp), dt), (rows, jnp.float32)
    _assert_kernel(_compile(one_chip, fn, ((SLOTS, HKV, G, D), jnp.bfloat16),
                            payload, scales, payload, scales, *extra, ((SLOTS,), jnp.int32)))


@pytest.mark.parametrize("m", [8, 128])
def test_tlmm_compiles(one_chip, m):
    k, n = CFG.d_model, CFG.d_ff
    _assert_kernel(_compile(
        one_chip, lambda x, w, s: tlmm_pallas(x, w, s, bm=min(m, 128), bn=128, bk=512),
        ((m, k), jnp.int8), ((k // 4, n), jnp.uint8), ((m, 1), jnp.float32)))


@pytest.mark.parametrize("k,n", [(CFG.d_model, CFG.d_model), (CFG.d_model, CFG.d_ff),
                                 (CFG.d_ff, CFG.d_model)])
@pytest.mark.parametrize("m", [4, 512])
def test_tlmm_serving_tiles_compile(one_chip, m, k, n):
    """The blocks ``tlmm_matmul`` picks for a decode round (4 rows) and a
    prefill chunk (512) fit the chip's VMEM at bitnet-730m's matrices."""
    from repro.kernels.tlmm.ops import tlmm_tiles

    bm, bn, bk = tlmm_tiles(m, k, n)
    _assert_kernel(_compile(
        one_chip, lambda x, w, s: tlmm_pallas(x, w, s, bm=bm, bn=bn, bk=bk),
        ((m, k), jnp.int8), ((k // 4, n), jnp.uint8), ((m, 1), jnp.float32)))


def test_tlmm_reads_the_layer_stack_in_place(one_chip):
    """In a layer scan the kernel's weight operand is the scan's slice of
    the (L, K/4, N) stack: the slice fuses into the kernel's operand, so no
    layer's packed matrix is copied out for the call."""
    from repro.kernels.tlmm.ops import tlmm_tiles

    layers, k, n = CFG.num_layers, CFG.d_model, CFG.d_ff
    bm, bn, bk = tlmm_tiles(4, k, n)

    def scan(x, stack, s):
        def body(acc, w):
            return acc + tlmm_pallas(x, w, s, bm=bm, bn=bn, bk=bk, out_dtype=jnp.float32), None
        return jax.lax.scan(body, jnp.zeros((4, n), jnp.float32), stack)[0]

    text = _compile(one_chip, scan, ((4, k), jnp.int8), ((layers, k // 4, n), jnp.uint8),
                    ((4, 1), jnp.float32)).as_text()
    comps = text.split("\n\n")
    calls = [c for c in comps if 'custom_call_target="tpu_custom_call"' in c]
    assert calls and all(f"u8[{layers},{k // 4},{n}]" in c for c in calls)
    assert "kind=kCustom" in text
