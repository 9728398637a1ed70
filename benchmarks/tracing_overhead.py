"""Tracing overhead: the tracer must cost little on the decode loop.

The instrumentation contract (``repro.obs.trace``): a disabled span site is
one attribute check and a shared no-op context, so the DISABLED cost per
decode round is a handful of branches; an enabled span also enters a
``jax.profiler.TraceAnnotation``.  Every span of a step counts here:
``engine.step``, ``engine.schedule``, the chunk's and the decode round's
phases (``decode.prepare`` / ``dispatch`` / ``wait`` / ``outputs``).  This
benchmark measures the enabled tracer against the disabled one and gates
it — the observability PR must not tax serving when nobody is watching.

Protocol: one warm engine, one seeded workload replayed as K segments per
mode, modes INTERLEAVED (disabled, enabled, disabled, enabled, ...) so slow
ambient drift (noisy neighbors, thermal) hits both alike instead of landing
on whichever ran last.  Per segment the cost per decode round is the
engine's whole-step wall time (``EngineStats.t_step``, which holds every
span site) over its decode rounds, from the stats delta; per mode the
MEDIAN segment cost is compared.

Gate: enabled-median overhead < 3 % of the disabled median, OR the absolute
delta is under 150 us/round — on a tiny CI model a decode round is sub-ms,
where 3 % is below timer/scheduler noise; on any real model the relative
gate is the binding one.  Enabled-mode tracing also exercises the ring
bound (capacity is set small enough that long runs wrap) to show overhead
does not grow when the buffer is full.

    PYTHONPATH=src python -m benchmarks.tracing_overhead [--tiny]
"""
from __future__ import annotations

import sys

import numpy as np

from .common import markdown_table, save_result

# absolute floor under which the relative gate is timer noise, not cost
ABS_FLOOR_S = 150e-6
REL_GATE = 0.03


def _decode_cost_segment(eng, prompts, *, max_new, tag):
    """Replay one workload segment; return (step seconds, decode rounds)
    from the engine's own stats delta."""
    from repro.serving import Request

    t0, r0 = eng.stats.t_step, eng.stats.decode_rounds
    for i, p in enumerate(prompts):
        eng.submit(Request(f"{tag}-{i}", p.copy(), max_new=max_new))
    eng.run()
    rounds = eng.stats.decode_rounds - r0
    return eng.stats.t_step - t0, max(rounds, 1)


def run(tiny: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import reduced_config
    from repro.models import get_model
    from repro.obs.trace import TRACER
    from repro.serving import EngineCore

    if tiny:
        cfg = reduced_config("bitnet-730m", num_layers=2, d_model=64,
                             vocab_size=256, num_heads=4, num_kv_heads=2)
        n_req, max_new, segments = 2, 24, 5
    else:
        cfg = reduced_config("bitnet-730m", num_layers=4, d_model=256,
                             vocab_size=512, num_heads=4, num_kv_heads=2)
        n_req, max_new, segments = 3, 64, 9
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    # chunked prefill, so the chunk's spans are among the sites measured
    eng = EngineCore(cfg, params, n_slots=n_req, max_len=16 + max_new + 8,
                     prefill_chunk=8)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
               for _ in range(n_req)]

    was_enabled = TRACER.enabled
    TRACER.disable()
    _decode_cost_segment(eng, prompts, max_new=max_new, tag="warm")  # compile
    eng.reset_stats()

    per_round = {"disabled": [], "enabled": []}
    seg = 0
    for _ in range(segments):
        for mode in ("disabled", "enabled"):
            if mode == "enabled":
                # small capacity on purpose: segments wrap the ring, so the
                # measured enabled cost includes full-buffer eviction
                TRACER.enable(capacity=4096)
            else:
                TRACER.disable()
            t, rounds = _decode_cost_segment(
                eng, prompts, max_new=max_new, tag=f"{mode[:3]}{seg}")
            per_round[mode].append(t / rounds)
            seg += 1
    TRACER.disable()
    events_recorded = TRACER._emitted  # last enabled segment's total
    if was_enabled:  # an outer --trace-out run owns the tracer
        TRACER.enable()

    med = {m: float(np.median(v)) for m, v in per_round.items()}
    delta = med["enabled"] - med["disabled"]
    rel = delta / med["disabled"] if med["disabled"] > 0 else 0.0
    ok = rel < REL_GATE or delta < ABS_FLOOR_S

    rows = [{
        "mode": m,
        "segments": len(per_round[m]),
        "round_cost_us_median": 1e6 * med[m],
        "round_cost_us_min": 1e6 * float(np.min(per_round[m])),
        "round_cost_us_max": 1e6 * float(np.max(per_round[m])),
    } for m in ("disabled", "enabled")]
    rows.append({"mode": "overhead", "segments": "",
                 "round_cost_us_median": 1e6 * delta,
                 "round_cost_us_min": f"{100 * rel:+.2f}%",
                 "round_cost_us_max": ""})

    result = {
        "name": "tracing_overhead" + ("_tiny" if tiny else ""),
        "rows": rows,
        "overhead": {"relative": rel, "absolute_s": delta,
                     "rel_gate": REL_GATE, "abs_floor_s": ABS_FLOOR_S},
        "checks": {
            f"tracing disabled costs < {100 * REL_GATE:.0f}% per decode round "
            f"(or < {1e6 * ABS_FLOOR_S:.0f}us absolute)": bool(ok),
            "enabled segments recorded events": events_recorded > 0,
        },
        "notes": (
            f"Median step time per decode round over {segments} interleaved "
            f"segments per mode ({n_req} streams x {max_new} tokens each, "
            f"chunked prefill, warm engine, stats-delta timing, every span "
            f"site of the step included).  enabled runs with a 4096-event "
            f"ring so eviction cost is included.  Overhead "
            f"{100 * rel:+.2f}% ({1e6 * delta:+.1f} us/round) — gate: "
            f"< {100 * REL_GATE:.0f}% relative or "
            f"< {1e6 * ABS_FLOOR_S:.0f} us absolute."),
        "columns": ["mode", "segments", "round_cost_us_median",
                    "round_cost_us_min", "round_cost_us_max"],
    }
    save_result(result)
    return result


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--tiny", action="store_true",
                   help="CI smoke: small model, fewer segments")
    args = p.parse_args(argv)
    res = run(tiny=args.tiny)
    print(markdown_table(res["rows"], res.get("columns")))
    print()
    print(res["notes"])
    return 0 if all(res["checks"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())
