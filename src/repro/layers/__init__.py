from repro.layers.sharding import PartitionCtx, MeshAxes, NULL_CTX, TRAIN_RULES, PREFILL_RULES, DECODE_RULES, LONG_DECODE_RULES
from repro.layers.norm import norm_init, apply_norm
from repro.layers.rotary import apply_rope
from repro.layers.linear import linear_init, linear_apply
from repro.layers.attention import attention_init, attention_prefill, attention_decode, KVCache, update_cache
from repro.layers.mlp import mlp_init, mlp_apply
from repro.layers.moe import moe_init, moe_apply
