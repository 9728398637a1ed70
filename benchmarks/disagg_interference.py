"""Decode interference under concurrent long-prompt prefill: disagg vs colocated.

THE payoff measurement for the disaggregated two-pool runtime
(``repro.serving.disagg``): on a single engine, every prefill chunk of a
long prompt runs on the same device as the decode round next to it, so
concurrent admissions inflate the inter-token latency (ITL) of every
in-flight decode stream — chunked prefill bounds the stall to one chunk,
but the stall is still there.  With the pools split, chunks compute on the
PREFILL device while decode rounds run on the DECODE device; finished-chunk
KV ships eagerly over the ``KVHandoffChannel`` and its decode-side install
is deferred until the final chunk, so a decode round never acquires a data
dependency on the in-flight prefill and its ITL barely moves.

Protocol (same seeded workload against both engines, same step loop):

1. warm both engines' XLA programs on a throwaway pass (all shape buckets);
2. **baseline phase** — K short-prompt decode streams, no other traffic;
   per-stream ITL is stamped benchmark-side from ``step()`` deltas;
3. **interference phase** — the same K streams, plus long chunked-prefill
   prompts injected on a stagger while they decode.

The claim: disagg decode ITL p95 under interference stays within ~1.1x of
its own no-prefill baseline, while the colocated engine clearly degrades
(its interference p95 >= ~1.25x baseline).  Both ratio checks are
wall-clock and gate only the full run; ``--tiny`` (CI smoke on forced host
devices) keeps the structural checks — gaps recorded, every request
finished, KV actually crossed the channel.

Needs two devices and runs in the calling process, which holds them.  On
the CPU, direct runs and the harness (``benchmarks.run``) force
``--xla_force_host_platform_device_count=2`` before JAX starts; on an
accelerator with fewer than two devices the run raises.

    PYTHONPATH=src python -m benchmarks.disagg_interference [--tiny]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

from .common import (LATENCY_COLUMNS, add_trace_arg, finish_trace,
                     latency_rows, markdown_table, save_result, start_trace)


def ensure_host_devices(n: int = 2) -> None:
    """Force ``n`` CPU host devices.  Effective only before JAX starts its
    backends, and only on the CPU backend."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={n} {flags}".strip()


def _drive_phase(eng, decoders, longs, *, max_new_dec, stagger, tag):
    """Submit K decode streams (plus staggered long prompts), step the
    engine to completion, and return the pooled decoder inter-token gaps
    stamped around ``step()`` — the engine's own ``stats.itl`` would mix in
    the long prompts' deltas, so the decoders are timed benchmark-side."""
    from repro.serving import Request

    dec_ids = [f"{tag}-dec{i}" for i in range(len(decoders))]
    for rid, p in zip(dec_ids, decoders):
        eng.submit(Request(rid, p.copy(), max_new=max_new_dec))
    stamps = {rid: [] for rid in dec_ids}

    def absorb():
        outs = eng.step()
        t = time.perf_counter()
        for o in outs:
            if o.request_id in stamps and o.new_token_ids:
                stamps[o.request_id].append(t)

    # first tokens out: every decoder is mid-decode when the storm starts
    while any(not stamps[r] for r in dec_ids) and eng.has_unfinished():
        absorb()
    steps, pending = 0, list(longs)
    while eng.has_unfinished():
        if pending and steps % stagger == 0:
            eng.submit(Request(f"{tag}-long{len(longs) - len(pending)}",
                               pending.pop(0), max_new=2))
        absorb()
        steps += 1
    gaps = [g for rid in dec_ids for g in np.diff(stamps[rid])]
    assert all(len(stamps[rid]) >= max_new_dec for rid in dec_ids)
    return np.asarray(gaps, float)


def _measure(tiny: bool) -> dict:
    import jax

    # The prefill pool's dispatch thread holds the GIL for the Python
    # portion of each chunk dispatch; with CPython's default 5ms switch
    # interval the engine thread can stall that long waiting for it, which
    # is the same order as a whole decode round.  GIL handoff is not
    # priority-aware, so the pool's idle scheduling class can't help here —
    # shorten the interval instead.
    sys.setswitchinterval(5e-4)

    # On a shared-CPU host, XLA's async dispatch executes BOTH pools'
    # programs on one normal-priority helper thread, letting chunk compute
    # steal cycles mid-decode-round no matter how the pools prioritize
    # their dispatch.  Synchronous dispatch runs each program on the thread
    # that called it, so the prefill pool's self-deprioritized dispatch
    # thread (see PrefillPool) really does yield the core to decode — the
    # single-host analogue of prefill owning its own devices.
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    import jax.numpy as jnp

    from repro.configs import reduced_config
    from repro.models import get_model
    from repro.serving import DisaggEngine, EngineCore, Request, make_disagg_meshes

    if tiny:
        cfg = reduced_config("bitnet-730m", num_layers=2, d_model=64,
                             vocab_size=256, num_heads=4, num_kv_heads=2)
        n_dec, max_new_dec, long_len, n_long, chunk = 2, 12, 48, 2, 16
        max_len, stagger, rounds = 64, 3, 1
    else:
        cfg = reduced_config("bitnet-730m", num_layers=4, d_model=512,
                             vocab_size=512, num_heads=8, num_kv_heads=4)
        n_dec, max_new_dec, long_len, n_long, chunk = 3, 100, 192, 2, 16
        max_len, stagger, rounds = 256, 4, 3
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(0)
    decoders = [rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
                for _ in range(n_dec)]
    longs = [rng.integers(0, cfg.vocab_size, long_len).astype(np.int32)
             for _ in range(n_long)]
    knobs = dict(n_slots=n_dec + 1, max_len=max_len, prompt_len=long_len,
                 prefill_chunk=chunk)

    devices = jax.devices()
    if len(devices) < 2:
        raise RuntimeError(
            f"disagg_interference needs two devices for its two pools; this "
            f"process has {len(devices)} {devices[0].platform} device(s).  "
            "On the CPU, start it with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=2.")
    pmesh, dmesh = make_disagg_meshes()
    engines = {
        "colocated": EngineCore(cfg, params, **knobs),
        "disagg": DisaggEngine(cfg, params, prefill_mesh=pmesh,
                               decode_mesh=dmesh, **knobs),
    }

    from repro.obs.trace import TRACER

    rows, lat_rows, itl, handoff = [], [], {}, None
    for mode, eng in engines.items():
        if TRACER.enabled:
            # one engine per trace window: warmup ids repeat across engines
            # and the tracer's exactly-once finish assertion is per-process,
            # so each mode starts a fresh buffer (the export keeps the LAST
            # mode — disagg, the one whose lane overlap the trace is for)
            TRACER.clear()
        # warmup hits every shape bucket the measured phases use (decoder
        # prompt, full + final chunk, decode round), on THIS engine's
        # program caches
        for i, p in enumerate(decoders):
            eng.submit(Request(f"warm-dec{i}", p.copy(), max_new=2))
        eng.submit(Request("warm-long", longs[0].copy(), max_new=2))
        eng.run()
        eng.reset_stats()
        # baseline and interference alternate round-robin, and each phase
        # pools its gaps across rounds: slow ambient drift (a shared host's
        # noisy neighbors, thermal throttling) hits both phases alike
        # instead of landing entirely on whichever was measured last
        per_phase = {"baseline": [], "interference": []}
        for r in range(rounds):
            for phase, storm in (("baseline", []), ("interference", longs)):
                per_phase[phase].append(_drive_phase(
                    eng, decoders, storm, tag=f"{mode[:3]}-{phase[:5]}-r{r}",
                    max_new_dec=max_new_dec, stagger=stagger))
        for phase, storm in (("baseline", []), ("interference", longs)):
            gaps = np.concatenate(per_phase[phase])
            itl[(mode, phase)] = gaps
            rows.append({
                "mode": mode, "phase": phase,
                "concurrent_prefill_tokens": len(storm) * long_len,
                "decode_gaps": len(gaps),
                "itl_p50_ms": 1e3 * float(np.percentile(gaps, 50)),
                "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)),
                "itl_max_ms": 1e3 * float(np.max(gaps)),
            })
        lat_rows.extend(latency_rows(eng, label=mode))
        if mode == "disagg":
            handoff = eng.snapshot()["disagg"]["handoff"]

    def ratio(mode):
        base = float(np.percentile(itl[(mode, "baseline")], 95))
        storm = float(np.percentile(itl[(mode, "interference")], 95))
        return storm / max(base, 1e-9)

    ratios = {m: ratio(m) for m in engines}
    for m in engines:
        rows.append({"mode": m, "phase": "p95 ratio (interference/baseline)",
                     "concurrent_prefill_tokens": n_long * long_len,
                     "decode_gaps": len(itl[(m, "interference")]),
                     "itl_p50_ms": "", "itl_p95_ms": round(ratios[m], 3),
                     "itl_max_ms": ""})

    checks = {
        "ITL gaps recorded in every phase": all(len(g) > 0 for g in itl.values()),
        "KV crossed the handoff channel": bool(
            handoff and handoff["segments"] > 0 and handoff["pending"] == 0),
        "eager chunk segments shipped": bool(
            handoff and handoff["eager_segments"] > 0),
    }
    timing = {
        "disagg interference p95 <= 1.1x its baseline": ratios["disagg"] <= 1.1,
        "colocated clearly degraded (>= 1.25x baseline)": ratios["colocated"] >= 1.25,
        "disagg degrades less than colocated": ratios["disagg"] < ratios["colocated"],
    }
    if not tiny:
        # full scale is where the claim is made: the ratio checks gate
        checks.update(timing)
    return {
        "name": "disagg_interference" + ("_tiny" if tiny else ""),
        "rows": rows,
        "latency_rows": lat_rows,
        "handoff": handoff,
        "ratios": ratios,
        "notes": (
            f"Decode ITL of {n_dec} streams (max_new={max_new_dec}) without vs "
            f"with {n_long} concurrent {long_len}-token chunked prefills "
            f"(chunk={chunk}), colocated single engine vs two-pool "
            f"DisaggEngine on forced host devices (prefill pool "
            f"{pmesh.devices.size} dev, decode pool "
            f"{dmesh.devices.size} dev); {rounds} alternating "
            f"baseline/interference round(s) pooled per phase.  Checks: "
            + ", ".join(
                f"{k}={'PASS' if v else 'FAIL'}"
                for k, v in {**checks, **timing}.items())),
        "checks": checks,
        "timing_checks": timing,
        "columns": ["mode", "phase", "concurrent_prefill_tokens", "decode_gaps",
                    "itl_p50_ms", "itl_p95_ms", "itl_max_ms"],
    }


def run(tiny: bool = False) -> dict:
    """Harness entry: measures in this process, on its devices, and puts
    back the process-wide dispatch settings ``_measure`` changes."""
    import jax

    interval = sys.getswitchinterval()
    async_dispatch = jax.config.read("jax_cpu_enable_async_dispatch")
    try:
        result = _measure(tiny=tiny)
    finally:
        sys.setswitchinterval(interval)
        jax.config.update("jax_cpu_enable_async_dispatch", async_dispatch)
    save_result(result)
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--tiny", action="store_true",
                   help="CI smoke: small model/workload, structural checks only")
    add_trace_arg(p)
    args = p.parse_args(argv)
    ensure_host_devices(2)
    start_trace(args.trace_out)
    result = _measure(tiny=args.tiny)
    finish_trace(args.trace_out)
    save_result(result)
    print(markdown_table(result["rows"], result.get("columns")))
    print()
    print("engine latency (metrics registry — the /metrics summaries):")
    print(markdown_table(result["latency_rows"], list(LATENCY_COLUMNS)))
    print()
    print(result["notes"])
    return 0 if all(result["checks"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())
