"""The plain reference against the serving engine at a tiny size on the
CPU: prefill logits as the cell runs it (chunked or monolithic) and decode
logits through the cache, in both layouts, for a ternary and a bfloat16
model."""
import jax
import numpy as np
import pytest

from bench.harness import session, spec
from bench.harness.weights import make_weights
from bench.tests import tiny

# |engine - reference| over the largest |reference| logit.  The ternary
# model computes in float32, but its KV is stored in bfloat16 and each
# linear re-quantizes its input to int8, which turns that rounding into
# whole int8 steps now and then.  The bfloat16 model carries bfloat16
# activations through every layer (2^-8 relative per rounding).
TOL = {"bitnet-730m": 0.03, "qwen2.5-14b": 0.06}

CASES = [
    ("bitnet-730m", dict(tiny.CONTIGUOUS)),
    ("bitnet-730m", dict(tiny.PAGED, prefill_chunk=None)),
    ("qwen2.5-14b", dict(tiny.CONTIGUOUS, prefill_chunk=None)),
    ("qwen2.5-14b", dict(tiny.PAGED)),
]


def served_logits(name, engine, prompt, max_new):
    """The engine's greedy tokens and the logits each was taken from."""
    c = tiny.config(name)
    params = make_weights(c, 5, jax.devices()[0])
    eng = session.build_engine(spec.model_config(c), params, engine)
    seen = []
    first, batch = eng.runner.sample_first, eng.runner.sample_batch
    eng.runner.sample_first = lambda lg, req: seen.append(np.asarray(lg[0])) or first(lg, req)
    eng.runner.sample_batch = lambda lg, inf: seen.append(np.asarray(lg[0])) or batch(lg, inf)
    from repro.serving import Request

    req = Request("r0", prompt, max_new=max_new)
    eng.submit(req)
    eng.run()
    return c, params, req.out_tokens, np.stack(seen)


@pytest.mark.parametrize("name,engine", CASES,
                         ids=[f"{n}-{e['cache_layout']}-{'chunked' if e['prefill_chunk'] else 'mono'}"
                              for n, e in CASES])
def test_engine_matches_reference(name, engine):
    prompt = np.random.default_rng(0).integers(0, 250, size=45).astype(np.int32)
    c, params, out, got = served_logits(name, engine, prompt, max_new=8)
    seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
    pos = np.arange(len(prompt) - 1, len(seq))
    want = np.asarray(spec.family(c).logits(params, c, seq, pos))[: len(pos)]
    assert got.shape == want.shape
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < TOL[name], err
    # the served tokens are the reference's best up to near ties
    gaps = want.max(-1) - want[np.arange(len(out)), out]
    assert gaps.max() < tiny.LIMIT


# Where each configuration's control separates from the engine at a size a
# test run holds: int4 activations already at the tiny size; int8 W8A8 only
# at a wider, deeper bfloat16 model (512 x 8 reads at most 0.0093 served
# against 0.036 and more for the control over seeds 21-24 on the CPU).
CONTROL_SIZES = {
    "bitnet-730m": {},
    "qwen2.5-14b": dict(hidden_size=512, num_hidden_layers=8, head_dim=64,
                        intermediate_size=1024, vocab_size=4000, num_attention_heads=8,
                        num_key_value_heads=4),
}


@pytest.mark.parametrize("name", ["bitnet-730m", "qwen2.5-14b"])
def test_control_fails_the_limit(name):
    """Each configuration's control (one precision step below what it
    states), put in the program's place in the comparison a run makes,
    reads above the limit that the engine's served tokens keep."""
    from repro.serving import Request

    from bench.harness import check

    c = tiny.config(name, **CONTROL_SIZES[name])
    params = make_weights(c, 6, jax.devices()[0])
    eng = session.build_engine(spec.model_config(c), params,
                               dict(tiny.CONTIGUOUS, n_slots=4, max_len=128))
    rng = np.random.default_rng(1)
    reqs = [Request(f"r{i}", rng.integers(0, c["vocab_size"], size=40 + 7 * i).astype(np.int32),
                    max_new=48) for i in range(4)]
    for req in reqs:
        eng.submit(req)
    eng.run()
    v = check.verdict(params, c, reqs, control=c["control"])
    assert v["served"]["tokens"] == v["control"]["tokens"] == 4 * 48
    assert v["served"]["logit_gap"] <= tiny.LIMIT < v["control"]["logit_gap"], v
