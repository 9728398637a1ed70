"""Pallas TPU kernel: compute-optimized prefill attention (the prefill RM).

Paper (C3, §3.2.2, Fig. 3b): FlashAttention-style blocked online-softmax with
*reverse scheduling* — for query block i the K/V blocks are visited
j = i, i-1, ..., 0, so the first block processed is the (causally masked)
diagonal and every later block is mask-free.  On the FPGA this balances
pipeline trip counts; here it means exactly one masked block per Q row-block
and the running max m starts at the true row max for typical causal data
(the diagonal carries the largest logits), which stabilizes the exp rescale
chain.  ``schedule="forward"`` is kept for the ablation benchmark.

Tiling: grid (batch, q_heads, S/blk, S/blk) with the last (KV) dim
sequential.  Per step the kernel holds q (blk, d), k (blk, d), v (blk, d)
in VMEM plus f32 scratch m/l (blk, 128) and acc (blk, d) persisting across
the KV walk.  GQA: KV block specs index head h -> h // q_group, so a group
of q heads shares each streamed KV block.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _prefill_kernel(
    q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref, *, blk: int, sm_scale: float, reverse: bool
):
    i = pl.program_id(2)  # q block
    t = pl.program_id(3)  # walk step over kv blocks

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Causal: q block i needs kv blocks 0..i -> active for the first i+1 steps.
    @pl.when(t <= i)
    def _step():
        # reverse schedule: step t visits block j = i - t (diagonal first)
        j = i - t if reverse else t
        q = q_ref[...].astype(jnp.float32)[0, 0]  # (blk, d)
        k = k_ref[...].astype(jnp.float32)[0, 0]
        v = v_ref[...].astype(jnp.float32)[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * sm_scale  # (blk, blk)

        # Only the diagonal block needs the causal mask (bq == bk == blk).
        diag = jnp.equal(j, i)
        rows = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
        s = jnp.where(jnp.logical_or(jnp.logical_not(diag), rows >= cols), s, NEG_INF)

        m_prev = m_ref[...][:, :1]  # (blk, 1)
        l_prev = l_ref[...][:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # rmax(L^{(j)})
        m_new = jnp.maximum(m_prev, m_cur)  # Eq. (1) line 1
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # e^{L - m}
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)  # Eq. (1) line 2
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ()))
        )  # Eq. (1) line 3
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(t == i)  # last active step -> write the normalized output
    def _finalize():
        l = l_ref[...][:, :1]
        out = acc_ref[...] / jnp.maximum(l, 1e-30)
        out_ref[...] = out[None, None].astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("blk", "sm_scale", "schedule", "interpret")
)
def prefill_attention_pallas(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,  # (B, Hkv, S, D)
    *,
    blk: int = 256,
    sm_scale: float | None = None,
    schedule: str = "reverse",
    interpret: bool = False,
) -> jax.Array:
    b, h, s, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    assert s % blk == 0, (s, blk)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    nblk = s // blk
    reverse = schedule == "reverse"

    kernel = functools.partial(_prefill_kernel, blk=blk, sm_scale=sm_scale, reverse=reverse)

    def kv_index(bi, hi, ii, ti):
        ji = ii - ti if reverse else ti
        # clamp: masked-off steps (t > i) still produce an index; the body is
        # skipped by pl.when so the loaded block is unused.
        ji = jnp.clip(ji, 0, nblk - 1)
        return (bi, hi // g, ji, 0)

    return pl.pallas_call(
        kernel,
        grid=(b, h, nblk, nblk),
        in_specs=[
            pl.BlockSpec((1, 1, blk, d), lambda bi, hi, ii, ti: (bi, hi, ii, 0)),
            pl.BlockSpec((1, 1, blk, d), kv_index),
            pl.BlockSpec((1, 1, blk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, blk, d), lambda bi, hi, ii, ti: (bi, hi, ii, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk, 128), jnp.float32),
            pltpu.VMEM((blk, 128), jnp.float32),
            pltpu.VMEM((blk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
