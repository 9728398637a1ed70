"""The comparison that decides ``correct``.

After the window, a sample of the requests the run finished, drawn from the
seed and always holding the one with the most served tokens, is run once
through the plain reference of the configuration's family
(``bench.harness.spec.family``) over its prompt and the tokens the engine
served.  At each served position the number compared is
how far the served token's reference logit lies below the reference's best
there; a run is correct when the widest such gap stays within the cell's
limit.  The tokens are greedy, so a sound engine serves the reference's
best token up to rounding near ties.

A control puts a lower-precision reference in the program's place: at the
same positions, the token it ranks first is read against the float32
reference in the same way (``verdict(..., control=...)``).  Beside the
widest gap, the verdict keeps the mean gap and the share of positions whose
token is not the reference's best; only the widest gap is compared.
"""
from __future__ import annotations

import numpy as np

from bench.harness.spec import family


def sample(streams: list, requests: dict, k: int, rng: np.random.Generator) -> list:
    """Up to ``k`` finished requests (their request ids), the longest in
    served tokens first; in-flight ones fill in when too few finished."""
    served = [s for s in streams if requests[s.rid].out_tokens and not s.failed]
    finished = [s for s in served if s.done is not None]
    pool = finished if len(finished) >= k else served
    if not pool:
        return []
    pool = sorted(pool, key=lambda s: (-len(requests[s.rid].out_tokens), s.rid))
    rest = [pool[i] for i in sorted(rng.permutation(len(pool) - 1)[: k - 1] + 1)]
    return [pool[0].rid] + [s.rid for s in rest]


def _positions(req, q_block: int):
    """The sequence the reference reads, the positions whose logits chose
    the served tokens, and those tokens padded as the reference pads rows
    (to a multiple of ``q_block``)."""
    p, out = np.asarray(req.prompt, np.int32), np.asarray(req.out_tokens, np.int32)
    seq = np.concatenate([p, out[:-1]])
    pos = np.arange(len(p) - 1, len(p) - 1 + len(out))
    return seq, pos, np.pad(out, (0, -len(out) % q_block), mode="edge")


def _stats(g: np.ndarray) -> dict:
    if not len(g):
        return {"logit_gap": None, "mean_gap": None, "not_best": None, "tokens": 0}
    return {"logit_gap": float(g.max()), "mean_gap": float(g.mean()),
            "not_best": float((g > 0).mean()), "tokens": len(g)}


def verdict(weights, config: dict, reqs: list, control: str = None) -> dict:
    """The reference's verdict on the served tokens of ``reqs``
    (``"served"``) and, with ``control``, on the tokens that precision
    ranks first at the same positions (``"control"``)."""
    import jax.numpy as jnp

    ref = family(config)
    served, low = [], []
    for req in reqs:
        seq, pos, out = _positions(req, ref.Q_BLOCK)
        want = ref.logits(weights, config, seq, pos)
        served.append(np.asarray(ref.gaps(want, out))[: len(pos)])
        if control is not None:
            top = jnp.argmax(ref.logits(weights, config, seq, pos, precision=control), axis=-1)
            low.append(np.asarray(ref.gaps(want, top.astype(jnp.int32)))[: len(pos)])
        del want
    out = {"served": _stats(np.concatenate(served) if served else np.zeros(0))}
    if control is not None:
        out["control"] = _stats(np.concatenate(low) if low else np.zeros(0))
    return out
