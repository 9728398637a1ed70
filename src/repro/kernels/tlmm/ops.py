"""Jitted user-facing wrapper for the TLMM kernel.

``tlmm_matmul`` is what :func:`repro.layers.linear.linear_apply` calls: it
quantizes activations per-token to int8 (A8), folds the BitNet weight scale
into the per-row activation scale, pads M to the sublane tile, and dispatches
to the Pallas kernel (interpreted on the CPU, see
:func:`repro.kernels.interpret_mode`) or the jnp reference (identical
numerics, faster to compile on the CPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.quant.act_quant import quantize_activations_int8
from repro.quant.ternary import TernaryWeight
from repro.kernels.tlmm.kernel import tlmm_pallas
from repro.kernels.tlmm.ref import tlmm_reference

# Aliasing contract, audited by the `program` analysis pass: the packed
# ternary weight is a persistent (resident) buffer the op streams but never
# writes or returns.
CACHE_OPERANDS = {
    "tlmm_matmul": {"args": ("w",), "writes": False},
}


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def tlmm_matmul(
    x: jax.Array,  # (..., K) float
    w: TernaryWeight,
    *,
    out_dtype=jnp.bfloat16,
    use_kernel: bool = False,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
) -> jax.Array:
    """y = (quantize_int8(x) @ unpack(w)) * act_scale * w_scale."""
    *lead, k = x.shape
    n = w.n
    x2 = x.reshape(-1, k)
    m = x2.shape[0]

    with jax.named_scope("act_quant"):
        x_q, act_scale = quantize_activations_int8(x2)
    scale = act_scale * w.scale  # (M, 1) f32 — weight absmean folded in

    if not use_kernel:
        y = tlmm_reference(x_q, w.packed, scale, out_dtype=out_dtype)
        return y.reshape(*lead, n)

    bm = min(block_m, _round_up(m, 8))
    bn = min(block_n, n)
    bk = min(block_k, k)
    while n % bn:
        bn //= 2
    while k % bk or bk % 4:
        bk //= 2
    mp = _round_up(m, bm)
    if mp != m:
        x_q = jnp.pad(x_q, ((0, mp - m), (0, 0)))
        scale = jnp.pad(scale, ((0, mp - m), (0, 0)))
    y = tlmm_pallas(
        x_q, w.packed, scale, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
        interpret=interpret_mode(),
    )[:m]
    return y.reshape(*lead, n)
