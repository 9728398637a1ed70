"""Random weights from the seed, made on the device in one jitted call, in
the type they are served in and in the layer-stacked layout the serving
engine takes (``{"emb", "layers": {"attn", "ln1", "ln2", "mlp"}, "ln_f",
"lm_head"}``, every per-layer leaf with a leading layer axis).

The vocabulary is padded to a multiple of 256, as the engine pads it, and
the padded rows are zero, as a padded checkpoint has them: their logits
are exactly 0 and never win an argmax over random logits of spread ~1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_MULTIPLE = 256
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def padded_vocab(c: dict) -> int:
    v = c["vocab_size"]
    return -(-v // VOCAB_MULTIPLE) * VOCAB_MULTIPLE


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (beyond 32 bits too)."""
    word = np.random.SeedSequence(int(seed) % 2**63).generate_state(1, np.uint32)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def _layout(c: dict):
    """(path, shape, init) for every leaf; init is ("normal", std),
    ("gain",) or ("bias",)."""
    d, L, h, kv, hd, f = (c["hidden_size"], c["num_hidden_layers"],
                          c["num_attention_heads"], c["num_key_value_heads"],
                          c["head_dim"], c["intermediate_size"])
    vp = padded_vocab(c)
    leaves = [
        (("emb",), (vp, d), ("embed", 0.02)),
        (("ln_f", "scale"), (d,), ("gain",)),
        (("layers", "ln1", "scale"), (L, d), ("gain",)),
        (("layers", "ln2", "scale"), (L, d), ("gain",)),
        (("layers", "attn", "wq", "w"), (L, d, h * hd), ("normal", d ** -0.5)),
        (("layers", "attn", "wk", "w"), (L, d, kv * hd), ("normal", d ** -0.5)),
        (("layers", "attn", "wv", "w"), (L, d, kv * hd), ("normal", d ** -0.5)),
        (("layers", "attn", "wo", "w"), (L, h * hd, d), ("normal", (h * hd) ** -0.5)),
        (("layers", "mlp", "w_gate", "w"), (L, d, f), ("normal", d ** -0.5)),
        (("layers", "mlp", "w_up", "w"), (L, d, f), ("normal", d ** -0.5)),
        (("layers", "mlp", "w_down", "w"), (L, f, d), ("normal", f ** -0.5)),
    ]
    if c["attention_bias"]:
        for name, width in (("wq", h * hd), ("wk", kv * hd), ("wv", kv * hd)):
            leaves.append((("layers", "attn", name, "b"), (L, width), ("bias",)))
    if not c["tie_word_embeddings"]:
        leaves.append((("lm_head",), (d, vp), ("head", 0.02)))
    return leaves


def make_weights(c: dict, seed: int, device=None) -> dict:
    """The whole parameter tree on ``device`` (default: the first)."""
    dtype = DTYPES[c["param_dtype"]]
    layout = _layout(c)
    v = c["vocab_size"]

    def init(key):
        tree: dict = {}
        for i, (path, shape, how) in enumerate(layout):
            k = jax.random.fold_in(key, i)
            if how[0] == "gain":  # norm gains stay float32, as the engine keeps them
                x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif how[0] == "bias":
                x = 0.1 * jax.random.normal(k, shape, dtype)
            elif how[0] == "embed":
                x = jax.random.normal(k, shape, dtype) * jnp.asarray(how[1], dtype)
                x = jnp.where(jnp.arange(shape[0])[:, None] < v, x, 0).astype(dtype)
            elif how[0] == "head":
                x = jax.random.normal(k, shape, dtype) * jnp.asarray(how[1], dtype)
                x = jnp.where(jnp.arange(shape[1])[None, :] < v, x, 0).astype(dtype)
            else:
                x = jax.random.normal(k, shape, dtype) * jnp.asarray(how[1], dtype)
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = x
        return tree

    device = device or jax.devices()[0]
    sharding = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(init, out_shardings=sharding)(key_for(seed))
