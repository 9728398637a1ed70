"""Gradient compression for cross-pod (DCN) reduction, with error feedback.

At multi-pod scale the gradient all-reduce over the pod axis crosses DCN
(~25 GB/s vs 200 GB/s aggregate ICI), so it dominates the collective term of
the training roofline.  This module implements int8 gradient exchange with
error feedback (1-bit-Adam-style): each pod quantizes (grad + carried error)
per-tensor to int8, all-gathers the int8 payload + f32 scales over the pod
axis (wire bytes ~ 1/4 of f32), dequantizes and averages locally, and carries
the quantization residual into the next step.

Use inside shard_map over the pod axis (see trainer's compressed-DP mode);
``tests/test_distributed.py`` validates convergence + exactness bounds on a
4-device fake mesh.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp



def _quant_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    absmax = jnp.max(jnp.abs(x))
    scale = absmax / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_mean_over_axis(grads: Any, err: Any, axis: str) -> Tuple[Any, Any]:
    """Mean of grads over mesh axis ``axis`` using int8 wire format.

    Returns (mean_grads f32, new_error_feedback).  Must run inside shard_map
    with ``axis`` manual.
    """
    n = jax.lax.axis_size(axis)

    def one(g, e):
        g32 = g.astype(jnp.float32) + e
        q, scale = _quant_int8(g32)
        deq_local = q.astype(jnp.float32) * scale
        new_err = g32 - deq_local  # residual carried to next step
        # all_gather int8 payload (the wire savings) + tiny scale vector
        q_all = jax.lax.all_gather(q, axis)  # (n, ...)
        s_all = jax.lax.all_gather(scale, axis)  # (n,)
        mean = jnp.tensordot(s_all, q_all.astype(jnp.float32), axes=(0, 0)) / n
        return mean.astype(g.dtype), new_err

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    mean = jax.tree.unflatten(tdef, [o[0] for o in out])
    new_err = jax.tree.unflatten(tdef, [o[1] for o in out])
    return mean, new_err


def init_error_feedback(params: Any) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compressed_dp_grads(loss_fn, mesh, *, pod_axis: str = "pod", batch_spec=None):
    """DDP-style compressed data parallelism over the pod (DCN) axis.

    Returns ``grads_fn(params, err, batch) -> (loss_mean, grads_mean, err)``
    where each pod computes grads on its batch shard and the cross-pod mean
    uses the int8 + error-feedback wire format (1/4 the DCN bytes of f32).

    This is the integration point for the global-view trainer: in pjit the
    gradient reduction is implicit in the backward, so compression must own
    the reduction — hence the shard_map wrapper.
    """
    from jax.sharding import PartitionSpec as P

    batch_spec = batch_spec if batch_spec is not None else P(pod_axis)

    def local(params, err, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        mean, err = compressed_mean_over_axis(grads, err, pod_axis)
        loss = jax.lax.pmean(loss, pod_axis)
        return loss, mean, err

    rep = None  # replicated pytrees: spec inferred as fully-replicated
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(), batch_spec),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
