"""Jitted wrapper for the prefill attention kernel (padding + dispatch)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.prefill_attention.kernel import prefill_attention_pallas
from repro.kernels.prefill_attention.ref import prefill_attention_reference

# Aliasing contract, audited by the `program` analysis pass: prefill K/V
# arrive as the prompt's freshly-projected (not yet cache-resident) tensors,
# but the same read-only rule applies — the op never writes or returns its
# K/V operands; installs happen in the donated program-level cache buffers.
CACHE_OPERANDS = {
    "prefill_attention": {"args": ("k", "v"), "writes": False},
}


def prefill_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    blk: int = 256,
    schedule: str = "reverse",
    use_kernel: bool = False,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Causal self-attention over a full prompt, (B,H,S,D) layout.

    use_kernel=False runs the jnp oracle (CPU-fast path used inside jitted
    model code); use_kernel=True runs the Pallas prefill RM (compiled on a
    TPU, interpreted on the CPU).  Sliding windows fall back to the oracle — the
    hymba SWA layers are never the prefill bottleneck.
    """
    if not use_kernel or window is not None:
        return prefill_attention_reference(q, k, v, window=window, sm_scale=sm_scale)
    b, h, s, d = q.shape
    blk = min(blk, s)
    pad = (-s) % blk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    out = prefill_attention_pallas(
        q, k, v, blk=blk, schedule=schedule, interpret=interpret_mode(), sm_scale=sm_scale
    )
    return out[:, :, :s] if pad else out
