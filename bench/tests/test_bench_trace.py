"""Trace reduction: busy and idle share, top operations, idle gaps named by
what the host was doing."""
import gzip
import json
from pathlib import Path

import pytest

from bench.harness import trace

DATA = Path(__file__).resolve().parent / "data"


def synthetic():
    ms = 1_000_000
    return {
        "ops": {"0": [("while.9", 0 * ms, 4 * ms), ("fusion.1", 0 * ms, 2 * ms),
                      ("fusion.2", 2 * ms, 4 * ms), ("copy.3", 6 * ms, 7 * ms),
                      ("fusion.1", 9 * ms, 12 * ms)]},
        "modules": {"0": [("jit_decode", 0, 4 * ms), ("jit_sample", 6 * ms, 7 * ms)]},
        "host": [("bench.trace_window", 1 * ms, 10 * ms),
                 ("bench.step", 1 * ms, 5.5 * ms), ("bench.outputs", 5.5 * ms, 6 * ms),
                 ("bench.step", 6 * ms, 7.5 * ms), ("bench.wait_arrival", 7.5 * ms, 10 * ms)],
    }


def test_busy_is_the_union_inside_the_slice():
    r = trace.reduce_events(synthetic())
    # busy: [1, 4) + [6, 7) + [9, 10) = 5 ms of a 9 ms slice
    assert r["window_s"] == pytest.approx(9e-3)
    assert r["busy_s"] == pytest.approx(5e-3)
    assert r["chips"] == 1


def test_idle_gaps_are_named_by_the_host_span():
    r = trace.reduce_events(synthetic())
    # idle [4, 6): 1.5 ms under a step, 0.5 under outputs; [7, 9): 0.5 under
    # a step, 1.5 waiting for an arrival
    got = sorted((n, round(d * 1e3, 9)) for n, d in r["idle_gaps"])
    assert got == [("bench.step", 2.0), ("bench.wait_arrival", 2.0)]
    assert sum(d for _, d in r["idle_gaps"]) == pytest.approx(r["window_s"] - r["busy_s"])


def test_ops_are_named_by_their_program():
    r = trace.reduce_events(synthetic())
    ops = dict(r["device_ops"])
    assert "jit_decode/while.9" not in ops  # counted through the operations inside
    assert ops["jit_decode/fusion.1"] == pytest.approx(1e-3)  # clipped at the slice start
    assert ops["jit_decode/fusion.2"] == pytest.approx(2e-3)
    assert ops["jit_sample/copy.3"] == pytest.approx(1e-3)
    assert ops["fusion.1"] == pytest.approx(1e-3)  # outside any module


def test_op_names_keep_types_and_drop_layouts():
    text = ("%fusion.106 = f32[4,24,64]{2,1,0:T(8,128)S(1)} fusion(f32[4,24,9216]{2,1,0} "
            "%get-tuple-element.540, s32[]{:T(128)S(6)} %select_n.61), kind=kLoop, "
            "calls=%fused_computation.10")
    assert trace.op_name(text) == "%fusion.106 = f32[4,24,64] fusion(f32[4,24,9216], s32[])"
    assert trace.op_name("copy-start") == "copy-start"


def test_no_window_or_no_device_work_is_an_error():
    ev = synthetic()
    with pytest.raises(ValueError, match="no bench.trace_window"):
        trace.reduce_events(dict(ev, host=ev["host"][1:]))
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce_events(dict(ev, ops={"0": []}))


def test_recorded_chip_trace():
    """A 0.46 s slice of bitnet-730m.longdoc_decode recorded on one TPU v5e
    (24 engine steps): the attention over the whole 9216-row cache and the
    per-call re-quantization of the latent weights lead; every idle gap
    falls inside ``EngineCore.step``, between its programs."""
    with gzip.open(DATA / "trace_longdoc_decode.json.gz", "rt") as f:
        ev = json.load(f)
    r = trace.reduce_events(ev)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.459356575)
    assert r["busy_s"] == pytest.approx(0.406505303)
    top = [name.split("/", 1)[1] for name, _ in r["device_ops"][:3]]
    assert top[0].startswith("%fusion.103 = f32[4,24,9216] fusion(bf16[4,24,24,9216,64]")
    assert top[2].startswith("%abs_reduce_fusion")
    assert r["device_ops"][0][1] == pytest.approx(0.08899, abs=1e-5)
    assert {n for n, _ in r["idle_gaps"]} == {"bench.step"}
    assert len(r["idle_gaps"]) == 10
