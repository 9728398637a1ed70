"""Random weights from the seed, made on the device in one jitted call, in
the type they are served in and in the layout the configuration's family
gives (``layout(c)``, ``bench.harness.spec``).

The vocabulary is padded to a multiple of 256, as the engine pads it, and
the padded rows are zero, as a padded checkpoint has them: their logits
are exactly 0 and never win an argmax over random logits of spread ~1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.spec import family

VOCAB_MULTIPLE = 256
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _padded(v: int) -> int:
    return -(-v // VOCAB_MULTIPLE) * VOCAB_MULTIPLE


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (beyond 32 bits too)."""
    word = np.random.SeedSequence(int(seed) % 2**63).generate_state(1, np.uint32)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def make_weights(c: dict, seed: int, device=None) -> dict:
    """The whole parameter tree on ``device`` (default: the first)."""
    dtype = DTYPES[c["param_dtype"]]
    layout = family(c).layout(c)

    def init(key):
        tree: dict = {}
        for i, (path, shape, how) in enumerate(layout):
            k = jax.random.fold_in(key, i)
            if how[0] == "gain":  # norm gains stay float32, as the engine keeps them
                x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif how[0] == "bias":
                x = 0.1 * jax.random.normal(k, shape, dtype)
            elif how[0] in ("embed", "head"):  # vocabulary rows / columns, padded
                axis = 0 if how[0] == "embed" else 1
                v = shape[axis]
                shape = shape[:axis] + (_padded(v),) + shape[axis + 1:]
                x = jax.random.normal(k, shape, dtype) * jnp.asarray(how[1], dtype)
                real = jnp.expand_dims(jnp.arange(shape[axis]) < v, 1 - axis)
                x = jnp.where(real, x, 0).astype(dtype)
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
