"""The configuration's family module (``bench.harness.spec.family``): the
loader, the dense family giving what the harness gave before it moved
there, and a block the harness has never seen (``bench/tests/windowed.py``,
sliding-window attention) served and checked through the harness unchanged."""
import hashlib
import re
import time

import jax
import numpy as np
import pytest

from bench.harness import cell_run, check, session, spec
from bench.harness.weights import make_weights
from bench.tests import tiny

WINDOWED = "bench/tests/windowed.py"
WINDOW = 24  # under every prompt below, and not a multiple of the chunk or page


@pytest.mark.parametrize("reference,error,match", [
    (None, KeyError, "no 'reference' key"),
    ("bench/reference/absent.py", FileNotFoundError, "absent.py' is missing"),
    ("src/repro/models/transformer.py", ValueError, "not a .py file under"),
    ("bench/../src/repro/models/transformer.py", ValueError, "not a .py file under"),
], ids=["no-key", "no-file", "outside-bench", "dot-dot"])
def test_loader_refuses(reference, error, match):
    c = tiny.config("bitnet-730m")
    if reference is None:
        del c["reference"]
    else:
        c["reference"] = reference
    with pytest.raises(error, match=match) as e:
        spec.family(c)
    assert "bitnet-730m" in str(e.value)


def test_loader_imports_each_module_once():
    c = tiny.config("bitnet-730m")
    assert spec.family(c) is spec.family(dict(c, name="other"))
    assert spec.family(dict(c, reference=WINDOWED)).__name__ == "bench.tests.windowed"


def _digest(tree) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves, key=lambda x: jax.tree_util.keystr(x[0])):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


# Computed on the parent commit, before the layout moved from the harness
# into the dense family: seed 5, the tiny configurations, the CPU.
WEIGHTS = {
    "bitnet-730m": "1a251a147a9b9ae3589205f393126e8d042543e040b5b8678e131003df29e903",
    "qwen2.5-14b": "592d10b769d3b503901bc194a1ad17cd9b87c6c13407db3759e51c882fc2334a",
}


@pytest.mark.parametrize("name", sorted(WEIGHTS))
def test_weights_are_bitwise_as_before(name):
    assert _digest(make_weights(tiny.config(name), 5, jax.devices()[0])) == WEIGHTS[name]


# sha256 of repr(spec.model_config(c)), computed on the parent commit, before
# the fields moved into the dense family: an equal ModelConfig compiles the
# same phase programs.
MODEL_CONFIGS = {
    ("bitnet-730m", "tiny"): "d790c32e24f7d2acac61fbd3240b45493e6761289b1140903aa45d4f562e5434",
    ("bitnet-730m", "full"): "65007825e79ca3c8b63cafce0e320c8fa9874304f3c3903656ee402842403517",
    ("qwen2.5-14b", "tiny"): "2d27ef5f429c20b0187c1a8c92be698948ac7d73403c1078f56cb4f083dd99cf",
    ("qwen2.5-14b", "full"): "915d4fee1375da4f9093e217fe9d10c7e4f0308e8808ef55668d02d57e68bd1e",
}


CELLS = {"bitnet-730m": "bitnet-730m.longdoc_decode", "qwen2.5-14b": "qwen2.5-14b.chat"}


@pytest.mark.parametrize("name,size", sorted(MODEL_CONFIGS))
def test_model_config_is_as_before(name, size):
    c = tiny.config(name) if size == "tiny" else spec.load_cell(CELLS[name]).config
    got = hashlib.sha256(repr(spec.model_config(c)).encode()).hexdigest()
    assert got == MODEL_CONFIGS[(name, size)]


# the keys of the dense block's configurations: only its family reads them
MODEL_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
              "head_dim", "intermediate_size", "vocab_size", "tie_word_embeddings",
              "attention_bias", "rope_theta", "rms_norm_eps", "hidden_act")


def test_harness_names_no_model_key():
    files = sorted((tiny.BENCH / "harness").glob("*.py")) + sorted(
        (tiny.BENCH / "metrics").glob("*.py"))
    quoted = re.compile(r"""["'](%s)["']""" % "|".join(MODEL_KEYS))
    for path in files:
        text = path.read_text()
        assert not quoted.search(text), (path, quoted.search(text).group(0))
        assert "bench.reference" not in text and "bench/reference" not in text, path


def windowed_config(**sizes) -> dict:
    return tiny.config("qwen2.5-14b", reference=WINDOWED, sliding_window=WINDOW, **sizes)


@pytest.mark.parametrize("engine", [tiny.CONTIGUOUS, tiny.PAGED], ids=["contiguous", "paged"])
def test_windowed_block_served_and_checked(engine):
    """Prompts longer than the window, through ``EngineCore`` in each cache
    layout: the engine's logits match the windowed reference as closely as
    ``test_bench_reference`` holds the dense block to its own (bfloat16
    activations through every layer), the served tokens pass
    ``check.verdict``, and the dense reference on the same tokens does not,
    so the window is what the check saw."""
    from repro.serving import Request

    c = windowed_config()
    cfg = spec.model_config(c)
    assert cfg.sliding_window == WINDOW
    params = make_weights(c, 7, jax.devices()[0])
    eng = session.build_engine(cfg, params, engine)
    seen = []
    first, batch = eng.runner.sample_first, eng.runner.sample_batch
    eng.runner.sample_first = lambda lg, req: seen.append(np.asarray(lg[0])) or first(lg, req)
    eng.runner.sample_batch = lambda lg, inf: seen.append(np.asarray(lg[0])) or batch(lg, inf)
    rng = np.random.default_rng(3)
    reqs = [Request(f"r{i}", rng.integers(0, c["vocab_size"], size=40 + 9 * i).astype(np.int32),
                    max_new=12) for i in range(3)]
    eng.submit(reqs[0])
    eng.run()  # alone first: row 0 of every logits batch is this request's
    for req in reqs[1:]:
        eng.submit(req)
    eng.run()

    r0 = reqs[0]
    seq = np.concatenate([r0.prompt, np.asarray(r0.out_tokens[:-1], np.int32)])
    pos = np.arange(len(r0.prompt) - 1, len(seq))
    want = np.asarray(spec.family(c).logits(params, c, seq, pos))[: len(pos)]
    got = np.stack(seen[: len(pos)])
    assert np.abs(got - want).max() / np.abs(want).max() < 0.06

    v = check.verdict(params, c, reqs)
    assert v["served"]["tokens"] == 3 * 12
    assert v["served"]["logit_gap"] <= tiny.LIMIT, v
    dense = check.verdict(params, dict(c, reference="bench/reference/dense.py"), reqs)
    assert dense["served"]["logit_gap"] > tiny.LIMIT, dense


def test_windowed_block_whole_run_is_correct():
    """A whole run of a tiny cell naming the windowed family, past the
    harness's look for a chip: the weights, the engine, the traffic and the
    check all come from the family module."""
    cell = tiny.cell("qwen2.5-14b", reference=WINDOWED, sliding_window=WINDOW)
    assert cell.traffic["prompt_len"]["lo"] > WINDOW
    r = cell_run.execute(cell, 2**31 + 91, 1.0, False, t_start=time.perf_counter(),
                         require_tpu=False)
    assert r["correct"], r["check"]
    assert r["failed"] == 0 and r["attempted"] > 0


def test_windowed_work():
    """The window's work by hand: position i of a prefill attends min(i + 1,
    W) positions; a decode stream-step past the window reads W cached rows."""
    from bench.reference import dense
    from bench.tests import windowed

    c = windowed_config()
    per_pair = 4.0 * 2 * 4 * 16  # layers x heads x head_dim, QK and PV
    n = 40
    pairs = WINDOW * (WINDOW + 1) / 2 + (n - WINDOW) * WINDOW
    assert windowed.prefill(c, n).bf16_flops == pytest.approx(
        dense.prefill(c, n).bf16_flops - per_pair * (n * (n + 1) / 2 - pairs))
    stats = {"decode_rounds": 10, "slot_rounds": 40, "decode_ctx_tokens": 40 * 100}
    w, d = windowed.decode(c, stats), dense.decode(c, stats)
    assert w.bf16_flops == pytest.approx(d.bf16_flops - per_pair * (40 * 101 - 40 * WINDOW))
    assert w.bytes == pytest.approx(d.bytes - (40 * 101 - 40 * WINDOW)
                                    * dense.kv_bytes_per_token(c))
