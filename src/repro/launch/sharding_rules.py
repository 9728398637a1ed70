"""Per-parameter PartitionSpecs inferred from pytree paths.

Training: FSDP (big dim over the data axis) x TP (heads/ffn/vocab over the
model axis).  Inference: TP only (fsdp=None) so decode never all-gathers
weights.  MoE experts shard over the model axis when EP applies.  A packed
ternary linear (``.../w/packed``, (L, K/4, N)) shards as its latent ``w``
does: a packed row holds four consecutive input rows.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.tree import tree_map_with_path_names
from repro.configs.base import ModelConfig

# linears whose *output* dim is tensor-parallel
_TP_OUT = ("wq/w", "wk/w", "wv/w", "w_in/w", "w_gate/w", "w_up/w",
           "w_qkv", "w_if", "w_og", "ssm/w_in", "slstm/w")
# linears whose *input* dim is tensor-parallel (psum after)
_TP_IN = ("wo/w", "w_out/w", "w_down/w", "mlstm/w_out", "slstm/w_out", "ssm/w_out")
# biases that follow a TP-output linear
_TP_BIAS = ("wq/b", "wk/b", "wv/b", "w_in/b", "slstm/b")
# ssm per-channel tensors: channel dim (second-to-last or last) is TP
_SSM_CHANNEL = ("ssm/conv", "a_log", "w_bc", "w_dt", "d_skip", "dt_bias")


def param_pspec(path: str, leaf: Any, *, tp: Optional[str], fsdp: Optional[str], ep: bool) -> P:
    nd = leaf.ndim
    p = path.lower()

    def spec(*tail):
        return P(*((None,) * (nd - len(tail)) + tail))

    if nd == 0:
        return P()
    if p.endswith("/w/scale"):  # a packed ternary linear's per-layer beta
        return P(*(None,) * nd)
    if p.endswith("emb"):
        return P(tp, fsdp)
    if p.endswith("lm_head"):
        return P(fsdp, tp)
    if "pos_dec" in p:
        return P(*(None,) * nd)
    # MoE expert stacks: (L, E, d, f) / (L, E, f, d)
    if "/moe/" in p or ("moe" in p and nd == 4):
        if "router" in p:
            return spec(fsdp, None)
        if "w_down" in p:
            return spec(tp, None, fsdp) if ep else spec(None, tp, fsdp)
        return spec(tp, fsdp, None) if ep else spec(None, fsdp, tp)
    if any(p.endswith(s) or f"/{s}/" in p + "/" for s in _TP_BIAS):
        return spec(tp)
    if any(s in p for s in _TP_IN):
        return spec(tp, fsdp)
    if any(s in p for s in _TP_OUT):
        return spec(fsdp, tp)
    if "slstm/r" in p:  # (G, H, hd, 4hd)
        return spec(None, tp)
    if any(s in p for s in _SSM_CHANNEL):
        if p.endswith(("d_skip", "dt_bias")):
            return spec(tp)
        if "conv" in p:
            return spec(tp)  # (L, w, d_in): channel is last
        return spec(tp, None)  # (L, d_in, N)-shaped
    if "router" in p:
        return spec(fsdp, None)
    return P(*(None,) * nd)  # norms, gates, stabilizers: replicated


def params_shardings(params: Any, cfg: ModelConfig, mesh: Mesh, *, train: bool,
                     tp_axis: str = "model", fsdp_axis: Optional[str] = "data") -> Any:
    """Pytree of NamedShardings matching ``params``."""
    tp = tp_axis if (tp_axis and tp_axis in mesh.axis_names) else None
    fsdp = fsdp_axis if (train and fsdp_axis and fsdp_axis in mesh.axis_names) else None
    ep = bool(cfg.moe and tp and cfg.num_experts % mesh.shape[tp] == 0)

    def rule(path, leaf):
        from repro.layers.sharding import sanitize_spec

        spec = param_pspec(path, leaf, tp=tp, fsdp=fsdp, ep=ep)
        return NamedSharding(mesh, sanitize_spec(spec, leaf.shape, mesh))

    return tree_map_with_path_names(rule, params)


def eval_shape_params(cfg: ModelConfig, dtype=None):
    """ShapeDtypeStruct pytree of the params without allocating (dry-run)."""
    import jax.numpy as jnp

    from repro.models import get_model

    api = get_model(cfg)
    kw = {} if dtype is None else {"dtype": dtype}
    return jax.eval_shape(lambda k: api.init(cfg, k, **kw), jax.random.PRNGKey(0))
