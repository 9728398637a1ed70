"""Jitted user-facing wrapper for the TLMM kernel.

``tlmm_matmul`` is what :func:`repro.layers.linear.linear_apply` calls: it
quantizes activations per-token to int8 (A8), folds the BitNet weight scale
into the per-row activation scale, and runs the Pallas kernel wherever
Mosaic compiles it (:func:`repro.kernels.compiled_kernels`: any backend but
the CPU, outside a program partitioned over several devices), with blocks
chosen from the shape (:func:`tlmm_tiles`).  Elsewhere, and for a shape the
kernel cannot tile, it runs the jnp reference: the same int32 sums.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import compiled_kernels, interpret_mode
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

LANE = 128
SUBLANE = 8
# Bounds on a step's tiles, so the kernel stays inside v5e's default scoped
# VMEM (16 MiB) with double buffering and the decode's temporaries (a few
# copies of the packed tile as 32-bit words and the four int8 slot tiles).
MAX_BLOCK_M = 256
MAX_W_TILE_BYTES = 1 << 20  # packed (bk/4, bn) uint8
MAX_ACC_BYTES = 1 << 20  # (bm, bn) int32
# Weight blocks per call that a small-M call aims for: enough steps that the
# next tile's DMA hides under this one's decode, few enough that per-step
# overhead stays small next to it.
N_STEPS = 4


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def tlmm_tiles(m: int, k: int, n: int) -> Optional[Tuple[int, int, int]]:
    """(bm, bn, bk) for an (M, K) x (K/4, N) packed call, or None where the
    kernel cannot tile the shape.

    The whole of K is one block, so each weight tile is read once per M
    block: (K/4, bn) with bn a multiple of 128 dividing N, about N / 4 wide
    (a decode call's grid is a few steps a matrix), within the VMEM bounds.
    bm is all of M up to ``MAX_BLOCK_M`` rows (a block as tall as the array
    needs no padding to the sublane tile); a longer prefill chunk is padded
    to MXU-sized blocks of that many rows, each decoding the weight tile
    once."""
    if k % 16:  # the decode reads whole 32-bit words of packed rows
        return None
    # a block of all M rows may be any height: no padding to the sublane tile
    bm = m if m <= MAX_BLOCK_M else MAX_BLOCK_M
    bk = k
    widths = [b for b in range(LANE, n + 1, LANE) if n % b == 0] or [n]
    fits = [b for b in widths if (bk // 4) * b <= MAX_W_TILE_BYTES and bm * b * 4 <= MAX_ACC_BYTES]
    if not fits:
        return None
    small = [b for b in fits if b * N_STEPS <= n]
    return bm, (max(small) if small else min(fits)), bk


def kernel_serves(k: int, n: int) -> bool:
    """Whether ``tlmm_matmul`` runs a decode call's (K, N) packed matrix
    through the compiled kernel here."""
    return compiled_kernels() and tlmm_tiles(SUBLANE, k, n) is not None


def tlmm_matmul(
    x: jax.Array,  # (..., K) float
    w: TernaryWeight,
    *,
    out_dtype=jnp.bfloat16,
    use_kernel: Optional[bool] = None,
) -> jax.Array:
    """y = (quantize_int8(x) @ unpack(w)) * act_scale * w_scale.

    ``use_kernel`` None takes the kernel where ``compiled_kernels()``; True
    or False forces the kernel (interpreted on the CPU) or the reference."""
    *lead, k = x.shape
    n = w.n
    x2 = x.reshape(-1, k)
    m = x2.shape[0]

    with jax.named_scope("act_quant"):
        x_q, act_scale = quantize_activations_int8(x2)
    scale = act_scale * w.scale  # (M, 1) f32 — weight absmean folded in

    if use_kernel is None:
        use_kernel = compiled_kernels()
    tiles = tlmm_tiles(m, k, n) if use_kernel else None
    if tiles is None:
        y = tlmm_reference(x_q, w.packed, scale, out_dtype=out_dtype)
        return y.reshape(*lead, n)

    bm, bn, bk = tiles
    mp = _round_up(m, bm)
    if mp != m:
        x_q = jnp.pad(x_q, ((0, mp - m), (0, 0)))
        scale = jnp.pad(scale, ((0, mp - m), (0, 0)))
    y = tlmm_pallas(
        x_q, w.packed, scale, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
        interpret=interpret_mode(),
    )[:m]
    return y.reshape(*lead, n)
