"""Pallas TPU kernel: ternary table-lookup matmul (TLMM), adapted to the MXU.

Paper (C2, §3.2.2): ternary weights live on-chip as base-3 group indices; a
per-activation-group lookup table of precomputed add/sub partial sums turns
matmul into index->lookup->accumulate, eliminating DDR weight streaming.

TPU adaptation (DESIGN.md §2): the *memory-system* property is what matters —
1.58-bit weights resident in fast memory so the linear layers stop being
weight-bandwidth-bound.  Here the packed 2-bit weights (uint8, 4 weights/byte,
see repro.quant.ternary) are streamed HBM->VMEM at 0.25 B/weight, once per
call, decoded to int8 *inside* the kernel, and multiplied on the MXU (int8 x
int8 -> int32), which is the roofline-correct compute engine on TPU — a
LUT-gather realization would run on the VPU at ~1/50th the throughput.  The
faithful LUT algorithm is kept as an oracle in ref.py (tlmm_lut_reference)
and the property tests assert all three agree exactly in integer arithmetic.

VMEM tiling: grid (M/bm, N/bn, K/bk); per step the kernel holds
  x tile   (bm, bk)   int8
  w tile   (bk/4, bn) uint8   <- 4x smaller than an int8 weight tile
  acc      (bm, bn)   int32 scratch (persistent across the K dimension)
K is the innermost, sequential ("arbitrary") grid dim; M/N are parallel.
``repro.kernels.tlmm.ops.tlmm_tiles`` picks the blocks from the shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tlmm.ref import scale_epilogue

_BYTE_LSB = 0x01010101  # bit 0 of each byte of a 32-bit word
_EVEN_BITS = 0x55555555  # the low bit of every 2-bit code


def _decode_ternary_slots(wp: jax.Array) -> list:
    """uint8 (bk/4, bn) -> four int8 (bk/4, bn): the weights of each code
    slot, ``repro.quant.ternary.decode_ternary_slot``'s map.

    Decoded four bytes at a time: the tile is viewed as 32-bit words and
    every operation keeps to its byte (masks, shifts of at most 7 bits whose
    spill the masks drop, a product of 0/1 bytes by 0xFE that cannot carry),
    so which byte of a word holds which row never matters and the int8
    result is the word viewed back.  Mosaic on v5e has no 8-bit vector
    arithmetic; this needs no widening of each byte to 32 bits either."""
    w = pltpu.bitcast(wp, jnp.int32)
    lo = w & _EVEN_BITS  # code 0b01: +1
    hi = jax.lax.shift_right_logical(w, 1) & _EVEN_BITS  # code 0b10: -1
    nonzero = lo ^ hi  # 0b11 is unused and decodes to 0
    negative = nonzero & hi
    slots = []
    for i in range(4):
        nz = jax.lax.shift_right_logical(nonzero, 2 * i) & _BYTE_LSB
        neg = jax.lax.shift_right_logical(negative, 2 * i) & _BYTE_LSB
        slots.append(pltpu.bitcast(nz | neg * 0xFE, jnp.int8))  # 1 -> 0x01, -1 -> 0xFF
    return slots


def _tlmm_kernel(x_ref, wp_ref, scale_ref, out_ref, acc_ref, *, n_k_steps: int, out_dtype):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # x arrives slot-major within each K block (see tlmm_pallas), so code
    # slot i multiplies the contiguous lane slice [i*q, (i+1)*q) of x: four
    # int8 MXU dots, and no sublane interleave of the decoded weights.
    q = wp_ref.shape[0]
    for i, w_i in enumerate(_decode_ternary_slots(wp_ref[...])):
        acc_ref[...] += jax.lax.dot_general(
            x_ref[:, i * q:(i + 1) * q],
            w_i,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    @pl.when(k_step == n_k_steps - 1)
    def _finalize():
        # scale_ref: (bm, 1) f32 = act_scale * weight_scale (folded in ops.py)
        out_ref[...] = scale_epilogue(acc_ref[...], scale_ref[...], out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "out_dtype", "interpret"),
)
def tlmm_pallas(
    x_q: jax.Array,  # (M, K) int8
    w_packed: jax.Array,  # (K//4, N) uint8
    scale: jax.Array,  # (M, 1) f32 — combined act*weight scale
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    m, k = x_q.shape
    kq, n = w_packed.shape
    assert kq * 4 == k, (k, kq)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    assert bk % 16 == 0, bk  # whole 32-bit words of packed rows
    n_k_steps = k // bk

    # Slot-major order within each K block: column 4j + i of a block moves
    # to i * bk/4 + j, next to the other weights packed in bit slot i.
    x_q = x_q.reshape(m, n_k_steps, bk // 4, 4).swapaxes(2, 3).reshape(m, k)
    grid = (m // bm, n // bn, n_k_steps)
    kernel = functools.partial(_tlmm_kernel, n_k_steps=n_k_steps, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk // 4, bn), lambda i, j, s: (s, j)),
            pl.BlockSpec((bm, 1), lambda i, j, s: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # a layer scan's slice of the (L, K/4, N) stack is read in
            # place, not copied out for the call
            allow_input_fusion=(False, True, False),
        ),
        interpret=interpret,
    )(x_q, w_packed, scale)
