"""The engine on the profiler's clock: live spans that enter
``jax.profiler.TraceAnnotation`` only while tracing, the nested host phases
of one ``step()``, the always-on host counters, phase programs named after
their keys, and the layer scopes the model's operations carry."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs.trace as obs_trace
from repro.configs import reduced_config
from repro.core.phase_engine import program_name
from repro.models import get_model
from repro.obs.trace import TRACER
from repro.serving import EngineCore, Request

SCOPES = ("embed", "norm", "attention", "kv_write", "linear", "weight_quant",
          "act_quant", "mlp", "lm_head")


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced_config("bitnet-730m", num_layers=2, d_model=64, vocab_size=256,
                         num_heads=4, num_kv_heads=2)
    assert cfg.quant.ternary  # packed linears: act_quant and the 2-bit decode
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


@pytest.fixture
def tracer():
    TRACER.enable()
    yield TRACER
    TRACER.disable()
    TRACER.clear()


def _submit(eng, n=2, prompt_len=12, max_new=5):
    rng = np.random.default_rng(0)
    for i in range(n):
        eng.submit(Request(f"t{i}", rng.integers(0, 256, prompt_len).astype(np.int32),
                           max_new=max_new))


def _spans(tracer):
    """(name, start, end) of the recorded spans, by start."""
    return sorted(((e[1], e[2], e[2] + e[3]) for e in tracer.events() if e[0] == "X"),
                  key=lambda s: (s[1], -s[2]))


def _covers(outer, inner) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_disabled_tracer_builds_no_annotation(tiny, monkeypatch):
    built = []

    class Counting(obs_trace.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(obs_trace, "TraceAnnotation", Counting)
    cfg, params = tiny
    eng = EngineCore(cfg, params, n_slots=2, max_len=32, prompt_len=16, prefill_chunk=8)
    assert not TRACER.enabled
    _submit(eng)
    eng.run()
    assert eng.stats.decode_rounds > 0 and built == []

    TRACER.enable()
    try:
        _submit(eng)
        eng.run()
        spans = [e[1] for e in TRACER.events() if e[0] == "X"]
    finally:
        TRACER.disable()
        TRACER.clear()
    # every span the engine recorded entered an annotation of its name
    assert spans and sorted(built) == sorted(spans)


def test_decode_step_spans_nest_in_order(tiny, tracer):
    cfg, params = tiny
    eng = EngineCore(cfg, params, n_slots=2, max_len=32, prompt_len=16, prefill_chunk=8)
    _submit(eng, n=1)
    while not eng.scheduler.inflight:
        eng.step()
    tracer.clear()
    eng.step()
    spans = _spans(tracer)
    assert [s[0] for s in spans] == [
        "engine.step", "engine.schedule", "decode.round", "decode.prepare",
        "decode.dispatch", "decode.wait", "decode.outputs"]
    step, rnd = spans[0], spans[2]
    assert all(_covers(step, s) for s in spans[1:])
    assert all(_covers(rnd, s) for s in spans[3:])
    for a, b in zip(spans[3:], spans[4:]):  # the round's phases follow one another
        assert a[2] <= b[1]


def test_chunk_step_holds_dispatch_and_wait(tiny, tracer):
    cfg, params = tiny
    eng = EngineCore(cfg, params, n_slots=2, max_len=32, prompt_len=16, prefill_chunk=8)
    _submit(eng, n=1)
    eng.step()  # admission and the first chunk
    spans = _spans(tracer)
    names = [s[0] for s in spans]
    assert names[:5] == ["engine.step", "engine.schedule", "prefill.chunk",
                         "prefill.dispatch", "prefill.wait"]
    chunk = spans[2]
    assert _covers(chunk, spans[3]) and _covers(chunk, spans[4])


def test_monolithic_swap_is_a_span(tiny, tracer):
    cfg, params = tiny
    eng = EngineCore(cfg, params, n_slots=2, max_len=32, prompt_len=16, mode="pdswap")
    _submit(eng, n=1)
    eng.step()
    spans = _spans(tracer)
    prefill = next(s for s in spans if s[0] == "prefill")
    swap = next(s for s in spans if s[0] == "swap")
    assert _covers(prefill, swap)
    # the swap covers the relayout's dispatch and the waits that end it
    assert any(s[0] == "prefill.wait" and _covers(swap, s) for s in spans)
    assert not any(e[0] == "i" and e[1] == "swap" for e in tracer.events())


def test_step_counters(tiny):
    cfg, params = tiny
    eng = EngineCore(cfg, params, n_slots=2, max_len=32, prompt_len=16, prefill_chunk=8)
    _submit(eng, n=3)
    calls = 0
    while eng.has_unfinished():
        eng.step()
        calls += 1
    st = eng.stats
    assert st.steps == calls
    assert st.t_step >= st.t_wait > 0
    assert st.t_step >= st.t_decode + st.t_prefill
    snap = eng.snapshot()
    assert {"steps", "t_step", "t_wait"} <= set(snap)
    assert "mean_hidden_fraction" not in snap["swap_agg"]
    text = eng.metrics_registry().prometheus_text()
    for name in ("repro_engine_steps_total", "repro_engine_step_seconds_total",
                 "repro_engine_wait_seconds_total"):
        assert name in text
    assert "repro_swap_hidden_fraction" not in text


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_programs_are_named_after_their_keys(tiny, layout):
    cfg, params = tiny
    eng = EngineCore(cfg, params, n_slots=2, max_len=32, prompt_len=16, mode="pdswap",
                     cache_layout=layout, block_size=8, prefill_chunk=8, spec_decode=2)
    runner = eng.runner
    runner.build_serving_grid()
    progs = runner.program_signatures()
    assert any(p.phase == "swap" for p in progs.values())
    for key, prog in progs.items():
        assert prog.abstract_inputs, key
        text = prog.fn.lower(*prog.abstract_inputs).as_text()
        module = re.search(r"module @(\S+)", text).group(1)
        assert module == f"jit_{program_name(key, prog.phase)}", key
        assert "jit_fn" not in module
        if prog.phase == "swap":
            assert module.startswith("jit_swap_")


def test_program_names():
    assert program_name("decode:4x9216", "decode") == "decode_4x9216"
    assert program_name("relayout:1x512->4160", "swap") == "swap_relayout_1x512_4160"
    assert program_name("prefill_chunk:512+2048@4x9216", "prefill") == \
        "prefill_chunk_512_2048_4x9216"


def test_decode_program_carries_every_layer_scope(tiny):
    cfg, params = tiny
    eng = EngineCore(cfg, params, n_slots=2, max_len=32, prompt_len=16)
    prog = eng.runner.decode_prog
    sig = eng.runner.abstract_signature(prog.name)
    text = prog.fn.lower(*sig).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    assert any(n.startswith("jit(decode_2x32)/") for n in op_names)
    assert not any("jit(fn)" in n for n in op_names)
    seen = {p for n in op_names for p in n.split("/") if p in SCOPES}
    assert seen == set(SCOPES)
    assert any("/linear/weight_quant/" in n for n in op_names)
    assert any("/linear/act_quant/" in n for n in op_names)
    assert any("/mlp/linear/" in n for n in op_names)
