"""BitNet b1.58 ternary weight quantization + 2-bit packing.

The paper's linear workload is W1.58-A8: weights in {-1, 0, +1} with one
per-tensor scale (absmean), activations per-token int8.  On the FPGA the
ternary weights live in URAM as base-3 group indices feeding a lookup table;
on TPU we keep the *memory* property (2 bits/weight resident in HBM, decoded
on the fly in VMEM inside the Pallas TLMM kernel) and use the MXU for the
arithmetic (DESIGN.md §2).

Packing format (shared by kernel, ops and ref):
  4 ternary values -> 1 uint8 along the *input* (K) dimension.
  2-bit codes: 0b00 -> 0, 0b01 -> +1, 0b10 -> -1  (0b11 unused).
  value k = 4*j + i  lives in bits [2i, 2i+2) of packed[j].
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TernaryWeight:
    """A packed ternary weight: the on-device format of a TLMM linear.

    A model's layer-stacked weight holds one per layer: packed (L, K // 4, N)
    and scale (L,), which the layer scan slices to one matrix."""

    packed: jax.Array  # uint8, (K // 4, N)
    scale: jax.Array  # f32 scalar — BitNet absmean beta

    @property
    def k(self) -> int:
        return self.packed.shape[-2] * 4

    @property
    def n(self) -> int:
        return self.packed.shape[-1]


def ternary_quantize(w: jax.Array, eps: float = 1e-5) -> Tuple[jax.Array, jax.Array]:
    """BitNet b1.58 absmean quantizer.

    W_q = RoundClip(W / (mean|W| + eps), -1, 1),  beta = mean|W|.
    Returns (w_q int8 in {-1,0,1}, beta f32 scalar).
    """
    beta = jnp.mean(jnp.abs(w.astype(jnp.float32)))
    w_q = jnp.clip(jnp.round(w.astype(jnp.float32) / (beta + eps)), -1, 1)
    return w_q.astype(jnp.int8), beta


def ternary_quantize_ste(w: jax.Array, eps: float = 1e-5) -> Tuple[jax.Array, jax.Array]:
    """Straight-through-estimator version for QAT training.

    Forward: dequantized ternary weights (w_q * beta).  Backward: identity
    w.r.t. the latent fp weights (BitNet training recipe).
    """
    w_q, beta = ternary_quantize(w, eps)
    deq = w_q.astype(w.dtype) * beta.astype(w.dtype)
    return w + jax.lax.stop_gradient(deq - w), beta


def pack_ternary(w_q: jax.Array) -> jax.Array:
    """Pack int8 ternary (K, N) -> uint8 (K//4, N); K must be a multiple of 4."""
    k, n = w_q.shape
    assert k % 4 == 0, f"K={k} not a multiple of 4"
    # {-1,0,1} -> codes {2,0,1}
    codes = jnp.where(w_q < 0, jnp.uint8(2), w_q.astype(jnp.uint8))
    codes = codes.reshape(k // 4, 4, n)
    packed = (
        codes[:, 0, :]
        | (codes[:, 1, :] << 2)
        | (codes[:, 2, :] << 4)
        | (codes[:, 3, :] << 6)
    )
    return packed.astype(jnp.uint8)


def decode_ternary_slot(packed: jax.Array, i: int) -> jax.Array:
    """uint8 (K//4, N) -> int8 (K//4, N): the weights of code slot ``i``,
    i.e. rows ``k = 4j + i`` of the unpacked weight.

    Elementwise on the byte, so XLA can fuse it into the operand of the
    matmul that consumes it.  The arithmetic stays in 8 bits: XLA on v5e
    fuses this decode into the dot, but writes out an int8 matrix for one
    that widens to int32.  (The Pallas kernel decodes four bytes per 32-bit
    word instead: Mosaic has no 8-bit vector arithmetic on v5e.)
    """
    c = (packed >> (2 * i)) & 0x3
    return (c & 1).astype(jnp.int8) - (c >> 1).astype(jnp.int8)


def unpack_ternary(packed: jax.Array) -> jax.Array:
    """uint8 (K//4, N) -> int8 ternary (K, N)."""
    kq, n = packed.shape
    parts = [decode_ternary_slot(packed, i) for i in range(4)]
    # (K//4, 4, N) -> (K, N)
    return jnp.stack(parts, axis=1).reshape(kq * 4, n)


def quantize_and_pack(w: jax.Array) -> TernaryWeight:
    w_q, beta = ternary_quantize(w)
    return TernaryWeight(packed=pack_ternary(w_q), scale=beta)


def packed_bytes(k: int, n: int) -> int:
    return (k // 4) * n
