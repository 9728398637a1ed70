"""Engine spans and layer scopes read from a profiler trace
(``bench.harness.layers``): idle gaps named by the innermost span, device
time by program and scope, the HLO op names a trace carries, and the
per-layer readers built on them."""
import glob
import gzip
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import cell_run, layers, trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def nested(shift=0):
    """One 10 ms step: a decode program, its argmax, then the host phases
    that follow the round's wait, all inside ``bench.step``; the next
    round's dispatch starts as its program does.  ``shift`` ns is taken off
    every device timestamp."""
    attn = "jit(decode_4x64)/while/body/attention/dot_general"
    quant = "jit(decode_4x64)/while/body/mlp/linear/weight_quant/abs"
    ev = {
        "ops": {"0": [("%while.1 = ()", 1 * MS, 6 * MS, ""),
                      ("%fusion.1 = f32[4]", 1 * MS, 3 * MS, attn),
                      ("%fusion.2 = f32[]", 3 * MS, 5 * MS, quant),
                      ("%fusion.3 = f32[4]", 5 * MS, 6 * MS, ""),
                      ("%argmax.1 = s32[4]", 6 * MS, 7 * MS, "jit(argmax)/argmax"),
                      ("%fusion.1 = f32[4]", 10 * MS, 11 * MS, attn)]},
        "modules": {"0": [("jit_decode_4x64(77)", 1 * MS, 6 * MS),
                          ("jit_argmax(5)", 6 * MS, 7 * MS),
                          ("jit_decode_4x64(77)", 10 * MS, 11 * MS)]},
        "host": [("bench.trace_window", 0, 11 * MS),
                 ("bench.step", 0, 10 * MS),
                 ("engine.step", 0.1 * MS, 9.9 * MS),
                 ("decode.round", 0.5 * MS, 9.5 * MS),
                 ("decode.dispatch", 0.5 * MS, 1.2 * MS),
                 ("decode.wait", 1.2 * MS, 7.2 * MS),
                 ("decode.outputs", 7.2 * MS, 9.5 * MS),
                 ("decode.dispatch", 10 * MS, 10.2 * MS),
                 ("decode.wait", 10.2 * MS, 11 * MS)],
    }
    ev["ops"]["0"] = [(n, a - shift, b - shift, o) for n, a, b, o in ev["ops"]["0"]]
    ev["modules"]["0"] = [(n, a - shift, b - shift) for n, a, b in ev["modules"]["0"]]
    return ev


def test_gaps_are_named_by_the_innermost_span():
    r = layers.reduce_events(nested())
    # idle [0, 1): dispatch covers 0.5 of 1.0 ms, not most: decode.round
    # covers 0.5 too and is longer, engine.step 0.9; [7, 10): decode.outputs
    # covers 2.3 of 3 ms
    assert sorted(r["idle_gaps"]) == [["decode.outputs", pytest.approx(3e-3)],
                                      ["engine.step", pytest.approx(1e-3)]]
    assert r["spans"]["decode.wait"] == [2, pytest.approx(6.8e-3)]


def test_idle_is_split_by_the_innermost_open_span():
    r = layers.reduce_events(nested())
    ms = {k: round(v * 1e3, 6) for k, v in r["idle_by_span"].items()}
    # [0, 1): bench.step, engine.step from 0.1, dispatch from 0.5;
    # [7, 10): the wait's end, the outputs, engine.step and bench.step after
    assert ms == {"bench.step": 0.1 + 0.1, "engine.step": 0.4 + 0.4,
                  "decode.dispatch": 0.5, "decode.wait": 0.2, "decode.outputs": 2.3}
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_device_clock_is_put_on_the_host_clock():
    assert layers.reduce_events(nested())["clock_shift_ms"] == [0.0, 0.0]
    # the device's timestamps 1 ms behind: the same gaps, once moved back
    late = layers.reduce_events(nested(shift=1 * MS))
    assert late["clock_shift_ms"] == [pytest.approx(1.0), pytest.approx(1.0)]
    assert late["idle_by_span"] == pytest.approx(layers.reduce_events(nested())["idle_by_span"])


def test_clock_shift_bounds_leave_out_untraced_runs():
    """Clocks that agree; rounds every 20 ms whose programs start 1-1.2 ms
    after their dispatch began and whose waits end 0.3-0.5 ms after them;
    the first run's dispatch was not traced.  The shift lies between minus
    the fastest launch and the fastest wake-up."""
    runs, host = [("jit_decode_4x64(7)", -20 * MS, -5 * MS)], []
    for k in range(5):
        t, launch, wake = 20 * MS * k, (1 + 0.05 * k) * MS, (0.3 + 0.05 * k) * MS
        runs.append(("jit_decode_4x64(7)", t, t + 15 * MS))
        host += [("decode.dispatch", t - launch, t - launch + 0.5 * MS),
                 ("decode.wait", t - launch + 0.5 * MS, t + 15 * MS + wake)]
    ev = {"ops": {"0": []}, "modules": {"0": runs}, "host": host}
    lower, upper = layers.clock_shift(ev, "0")
    assert (lower, upper) == (pytest.approx(-1.0 * MS), pytest.approx(0.3 * MS))
    del ev["host"][0::2]  # no dispatch traced
    assert layers.clock_shift(ev, "0") is None


def test_scopes_sum_leaf_time_by_program_and_scope():
    r = layers.reduce_events(nested())
    scopes = {(p, s): t for p, s, t in r["scopes"]}
    assert scopes == {("jit_decode_4x64", "attention"): pytest.approx(3e-3),
                      ("jit_decode_4x64", "weight_quant"): pytest.approx(2e-3),
                      ("jit_decode_4x64", ""): pytest.approx(1e-3),
                      ("jit_argmax", ""): pytest.approx(1e-3)}
    assert r["runs"] == {"jit_decode_4x64": 2, "jit_argmax": 1}
    assert layers.decode_scope_ms(r, "attention") == pytest.approx(1.5)
    assert layers.decode_scope_ms(r, "weight_quant", gone=0.0) == pytest.approx(1.0)
    assert layers.decode_scope_ms(r, "kv_write") is None
    assert layers.decode_scope_ms(r, "kv_write", gone=0.0) == 0.0


def test_no_scoped_operation_reads_nothing():
    ev = nested()
    ev["ops"]["0"] = [op[:3] + ("",) for op in ev["ops"]["0"]]
    r = layers.reduce_events(ev)
    assert layers.decode_scope_ms(r, "weight_quant", gone=0.0) is None
    assert layers.decode_scope_ms(None, "attention") is None


def test_scope_of_takes_the_innermost():
    assert layers.scope_of("jit(d)/while/body/mlp/linear/weight_quant/abs") == "weight_quant"
    assert layers.scope_of("jit(d)/while/body/attention/linear/dot_general") == "linear"
    assert layers.scope_of("jit(d)/while/body/add") == ""


def test_recorded_trace_reduces_as_before():
    """The first recorded slice (three-field operations, harness spans only):
    the numbers of ``trace.reduce_events`` stand, its gaps stay
    ``bench.step``, and nothing carries a scope."""
    with gzip.open(DATA / "trace_longdoc_decode.json.gz", "rt") as f:
        ev = json.load(f)
    old, new = trace.reduce_events(ev), layers.reduce_events(ev)
    for k in ("busy_s", "window_s", "chips", "device_ops"):
        assert new[k] == old[k]
    assert {n for n, _ in new["idle_gaps"]} == {"bench.step"}
    assert sorted(new["idle_gaps"]) == sorted(old["idle_gaps"])
    assert all(s == "" for _, s, _ in new["scopes"])
    assert layers.decode_scope_ms(new, "attention") is None


def test_cpu_trace_holds_engine_spans_and_op_names(tmp_path):
    """On the CPU the trace has no device plane, but the host spans and the
    HLO op names it carries are read the same way."""
    from repro.configs import reduced_config
    from repro.models import get_model
    from repro.obs.trace import TRACER
    from repro.serving import EngineCore, Request

    cfg = reduced_config("bitnet-730m", num_layers=2, d_model=64, vocab_size=256,
                         num_heads=4, num_kv_heads=2)
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = EngineCore(cfg, params, n_slots=2, max_len=32, prompt_len=16, prefill_chunk=8)
    eng.submit(Request("r0", np.arange(12, dtype=np.int32), max_new=4))
    eng.step()
    eng.step()  # compiled: the traced step dispatches, it does not compile
    jax.profiler.start_trace(str(tmp_path))
    TRACER.enable()
    try:
        eng.step()
    finally:
        TRACER.disable()
        TRACER.clear()
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    ev = layers.events_from_xplane(path)
    names = {h[0] for h in ev["host"]}
    assert {"engine.step", "engine.schedule", "decode.round", "decode.dispatch",
            "decode.wait", "decode.outputs"} <= names
    with open(path, "rb") as f:
        ops = layers.hlo_op_names(f.read())
    decode = [v for k, v in ops.items() if k.startswith("jit_decode_2x32(")]
    assert decode, sorted(ops)
    scopes = {layers.scope_of(n) for n in decode[0].values()}
    assert {"attention", "weight_quant", "act_quant", "mlp", "lm_head"} <= scopes


def _ctx(stats=None, reduced=None):
    return types.SimpleNamespace(stats=stats or {}, trace=reduced)


def test_metric_readers():
    step_host = cell_run.reader("step_host_ms")
    assert step_host(_ctx({"steps": 4, "t_step": 0.1, "t_wait": 0.06})) == pytest.approx(10.0)
    assert step_host(_ctx({"decode_rounds": 3})) is None  # the counters are absent
    r = layers.reduce_events(nested())
    assert cell_run.reader("decode_attention_ms")(_ctx(reduced=r)) == pytest.approx(1.5)
    assert cell_run.reader("weight_quant_ms")(_ctx(reduced=r)) == pytest.approx(1.0)
    assert cell_run.reader("weight_quant_ms")(_ctx()) is None


def test_recorded_chip_trace_with_engine_spans():
    """A 0.3 s slice of bitnet-730m.longdoc_decode recorded on one TPU v5e
    with the engine's spans and the model's scopes (16 engine steps): the
    idle time falls in engine spans, the device's clock is put 1.05 ms
    later, and the whole-cache attention and the per-call quantization of
    the latent weights lead the decode program."""
    with gzip.open(DATA / "trace_longdoc_decode_layers.json.gz", "rt") as f:
        ev = json.load(f)
    r = layers.reduce_events(ev)
    assert r["chips"] == 1 and r["window_s"] == pytest.approx(0.3)
    assert r["busy_s"] == pytest.approx(0.265485067)
    assert not any(n.startswith("jit_fn") for n, _ in r["device_ops"])
    assert all(n.startswith("jit_decode_4x9216(") for n, _ in r["device_ops"][:3])
    assert r["runs"]["jit_decode_4x9216"] == 15 and r["spans"]["engine.step"][0] == 16
    assert r["clock_shift_ms"] == [pytest.approx(1.051879), pytest.approx(1.952173)]
    idle = sum(r["idle_by_span"].values())
    engine = sum(t for n, t in r["idle_by_span"].items()
                 if n.split(".")[0] in layers.ENGINE_SPANS)
    assert engine >= 0.9 * idle
    assert all(n.split(".")[0] in layers.ENGINE_SPANS for n, _ in r["idle_gaps"])
    decode = {s: t for p, s, t in r["scopes"] if p == "jit_decode_4x9216"}
    assert sum(t for s, t in decode.items() if s) >= 0.9 * sum(decode.values())
    lead = sorted(decode, key=decode.get, reverse=True)[:2]
    assert set(lead) == {"attention", "weight_quant"}
    assert layers.decode_scope_ms(r, "attention") == pytest.approx(7.73, abs=0.01)
    assert layers.decode_scope_ms(r, "weight_quant", gone=0.0) == pytest.approx(8.16, abs=0.01)
