"""Pure-jnp oracles for the TLMM kernel.

Two references:

* ``tlmm_reference`` — slot-major decode + int32 matmuls; numerically
  *exact* integer arithmetic, the ground truth the Pallas kernel must match
  bit-for-bit, and the XLA serving path of a packed linear.
* ``tlmm_lut_reference`` — the paper's actual FPGA algorithm (C2): group
  activations in groups of 4, precompute the 3^4 = 81 add/sub combinations
  of each group, re-encode each weight group as a base-3 index, and gather.
  Exactly equal to the direct matmul in integer arithmetic; kept as the
  algorithmic fidelity witness (property-tested in tests/test_tlmm.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.quant.ternary import decode_ternary_slot, unpack_ternary

TL_GROUP = 4
_POW3 = 3 ** np.arange(TL_GROUP)  # [1, 3, 9, 27]


def scale_epilogue(acc: jax.Array, scale: jax.Array, out_dtype) -> jax.Array:
    """int32 accumulator -> output: ``acc * scale`` in float32, where
    ``scale`` = per-row activation scale x weight absmean.  Shared by every
    ternary matmul path (this reference, the latent path of
    ``linear_apply``, and the Pallas kernel's finalize), so they agree bit
    for bit on the same ternary values."""
    return (acc.astype(jnp.float32) * scale).astype(out_dtype)


def tlmm_reference(x_q: jax.Array, w_packed: jax.Array, scale: jax.Array, out_dtype=jnp.bfloat16) -> jax.Array:
    """(M,K) int8 @ unpack(w_packed) -> (M,N), scaled per-row.

    Slot-major, as the kernel computes it: ``sum_i x[:, i::4] @ decode_i``,
    where ``decode_i`` maps the packed bytes elementwise to the weights of
    code slot ``i``.  The packed weight is never unpacked into one (K, N)
    operand, so XLA can fuse each decode into its dot and stream 2 bits per
    weight.  The int32 sums are exact: the result equals unpack-then-dot."""
    m, k = x_q.shape
    xs = x_q.reshape(m, k // TL_GROUP, TL_GROUP)
    acc = None
    for i in range(TL_GROUP):
        with jax.named_scope("weight_quant"):
            w_i = decode_ternary_slot(w_packed, i)  # (K//4, N) int8
        part = jax.lax.dot_general(
            xs[:, :, i], w_i, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
        )
        acc = part if acc is None else acc + part
    return scale_epilogue(acc, scale, out_dtype)


def _ternary_group_codes(w_q: np.ndarray) -> np.ndarray:
    """int8 ternary (K, N) -> base-3 group codes (K//4, N) in [0, 81)."""
    k, n = w_q.shape
    digits = (w_q.astype(np.int32) + 1).reshape(k // TL_GROUP, TL_GROUP, n)  # {-1,0,1}->{0,1,2}
    return np.einsum("gin,i->gn", digits, _POW3).astype(np.int32)


def _group_lut(x_group: np.ndarray) -> np.ndarray:
    """All 81 ternary combinations of a 4-activation group.

    x_group: (4,) int32 -> lut (81,) int32 with
    lut[code] = sum_i (digit_i(code) - 1) * x[i].
    This is the table the FPGA precomputes once per group per token and then
    indexes with URAM-resident weight codes.
    """
    codes = np.arange(3**TL_GROUP)
    digits = (codes[:, None] // _POW3[None, :]) % 3 - 1  # (81, 4) in {-1,0,1}
    return digits @ x_group.astype(np.int64)


def tlmm_lut_reference(x_q, w_packed, scale, out_dtype=jnp.bfloat16):
    """The paper's index->lookup->accumulate algorithm, bit-exact vs matmul."""
    x = np.asarray(x_q, dtype=np.int32)  # (M, K)
    w = np.asarray(unpack_ternary(w_packed), dtype=np.int8)  # (K, N)
    m, k = x.shape
    n = w.shape[1]
    codes = _ternary_group_codes(w)  # (K//4, N)
    out = np.zeros((m, n), dtype=np.int64)
    for row in range(m):
        xg = x[row].reshape(k // TL_GROUP, TL_GROUP)
        # one 81-entry table per activation group (precomputed add/sub sums)
        luts = np.stack([_group_lut(g) for g in xg])  # (K//4, 81)
        # index–lookup–accumulate: weights are indices into the tables
        out[row] = np.take_along_axis(luts, codes, axis=1).sum(axis=0)
    res = out.astype(np.float32) * np.asarray(scale, dtype=np.float32)
    return jnp.asarray(res).astype(out_dtype)
