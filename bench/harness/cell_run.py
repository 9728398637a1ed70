"""One run of one cell: set-up, the measured window, the metrics, and the
comparison that decides ``correct``.  ``bench/run.py`` is its command line;
``bench/calibrate.py`` and the tests call :func:`execute` directly."""
from __future__ import annotations

import gc
import importlib.util
import os
import shutil
import sys
import time
import types
from pathlib import Path

from bench.harness import check, layers, session, spec, traffic
from bench.harness import window as win
from bench.harness.peaks import peak_for

TRACE_SECONDS = 4.0  # the traced slice that follows a --trace 1 window
TRACE_DIR = spec.ROOT / "bench_out" / "trace"
CACHE_DIR = spec.ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX has no accelerator of the kind, or not as many as, the cell needs."""


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"the benchmark measures a TPU; JAX's devices are "
                     f"{devs[0].platform}: {devs}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX has {len(devs)}")
    return devs[:chips]


def use_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, or where
    ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def reader(name: str, metrics_dir: Path = spec.BENCH / "metrics"):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = metrics_dir / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


class WindowHooks(session.Hooks):
    """Counters and compiles at the window's edges; with a tracer, the
    traced slice right after the window, so that writing the trace out
    never lands inside it."""

    def __init__(self, eng, compiles, tracer=None, slice_s=TRACE_SECONDS):
        self.eng, self.compiles, self.tracer = eng, compiles, tracer
        self.after_s = slice_s if tracer else 0.0
        self.host = session.HostWatch()

    def open(self, t):
        self.c0 = session.counters(self.eng)
        self.n0 = self.compiles.n
        self.e0 = {k: list(v) for k, v in self.compiles.seen.items()}
        self.host.start()

    def close(self, t):
        self.host.stop()
        self.c1 = session.counters(self.eng)
        self.n1 = self.compiles.n
        self.events = {k: (v[0] - self.e0.get(k, [0, 0.0])[0], v[1] - self.e0.get(k, [0, 0.0])[1])
                       for k, v in self.compiles.seen.items()}
        self.events = {k: v for k, v in self.events.items() if v[0]}

    def after_open(self, t):
        self.tracer.start()

    def after_close(self):
        self.tracer.stop()


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def execute(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
            t_start: float, require_tpu: bool = True, patch=None, control=None) -> dict:
    """Run the cell once; returns the result line's object.  ``patch(eng)``
    may alter the engine before warm-up (the fault tests break it so).
    With ``control`` (one of the family's ``CONTROLS``), that
    lower-precision reference takes the program's place in the comparison:
    ``correct`` is decided on the tokens it ranks first at the served
    positions, and the program's own reading is kept under ``readings``."""
    import jax

    devices = devices_for(cell.chips, require_tpu)
    dev = devices[0]
    if require_tpu:
        log(f"compile cache: {use_compile_cache()}")
    compiles = session.CompileCount()
    c = cell.config
    mix = cell.traffic

    t_w0 = t = time.perf_counter()
    cfg = spec.model_config(c)
    from bench.harness.weights import make_weights

    params = make_weights(c, seed, dev)
    jax.block_until_ready(params)
    t_weights = time.perf_counter() - t

    t = time.perf_counter()
    eng = session.build_engine(cfg, params, cell.engine)
    if patch is not None:
        patch(eng)
    sess = session.Session(eng, seed, cfg.vocab_size)
    n_warm = sess.warm_up(mix)
    t_warm = time.perf_counter() - t
    n_setup_compiles = compiles.n

    tracer = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracer = session.TraceSlice(str(TRACE_DIR))
    hooks = WindowHooks(eng, compiles, tracer)
    # what set-up made lives to the end: the collector need not scan it
    gc.collect()
    gc.freeze()
    t_pre = time.perf_counter()
    if mix["loop"] == "closed":
        t0, t1 = session.run_closed(sess, traffic.closed_loop(mix), seconds, hooks)
    elif mix["loop"] == "open":
        items = traffic.open_loop(mix, seconds)
        t0, t1 = session.run_open(sess, items, float(mix["pre_roll_s"]), seconds, hooks)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    gc.unfreeze()
    setup_s = t0 - t_start
    log(f"set-up {setup_s:.3f}s: process start to weights "
        f"{t_w0 - t_start:.3f}s, weights {t_weights:.3f}s, engine and "
        f"warm-up ({n_warm} requests) {t_warm:.3f}s, pre-roll {t0 - t_pre:.3f}s; "
        f"{n_setup_compiles} backend compiles ({compiles.seconds:.1f}s) in set-up")
    log(f"window {t1 - t0:.3f}s: {hooks.n1 - hooks.n0} backend compiles inside it")
    log("jax events in the window: " + (", ".join(
        f"{k} x{n} ({sec:.3f}s)" for k, (n, sec) in sorted(hooks.events.items())) or "none"))
    log("host in the window: " + hooks.host.summary(t0))
    steps = sorted(((b - a, a, cpu) for a, b, cpu in sess.steps if t0 <= a < t1), reverse=True)
    log("longest steps: " + ", ".join(f"{d * 1e3:.3f} ms at {a - t0:.3f}s ({cpu * 1e3:.3f} ms "
                                      f"of CPU)" for d, a, cpu in steps[:3]))

    streams = list(sess.streams.values())
    stats = {k: hooks.c1[k] - hooks.c0[k] for k in hooks.c0}
    late = win.lateness(streams, t0, t1) if mix["loop"] == "open" else []
    if late:
        log(f"generator lateness: median {win.percentile(late, 50) * 1e3:.3f} ms, "
            f"max {max(late) * 1e3:.3f} ms over {len(late)} requests")
    in_window = [s for s in streams if s.due < t1 and (s.done is None or s.done > t0)]
    memory_peak = _memory_peak(devices)

    reduced = None
    if trace:
        files = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
        reduced = layers.reduce_events(layers.events_from_xplane(str(files[-1])))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    ctx = types.SimpleNamespace(
        cell=cell, config=c, engine=cell.engine, streams=streams, t0=t0, t1=t1,
        stats=stats, setup_s=setup_s, trace=reduced,
        peak=peak_for(dev.device_kind) if dev.platform == "tpu" else None)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    log("window counters: " + ", ".join(f"{k}={v}" for k, v in stats.items()))

    # the engine's device state goes before the reference runs
    requests = sess.requests
    session.free(eng)
    del eng, sess, hooks
    gc.collect()

    rng = traffic.rng_for(seed, 9)
    rids = check.sample(streams, requests, int(cell.check["requests"]), rng)
    reqs = [requests[r] for r in rids]
    verdicts = check.verdict(params, c, reqs, control)
    compared = verdicts["control" if control else "served"]
    limit = cell.check["limit"]
    gap = compared["logit_gap"]
    correct = gap is not None and limit is not None and gap <= limit
    log(f"checked {len(reqs)} requests, {compared['tokens']} served tokens"
        + (f", control {control}" if control else ""))

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {
        "correct": correct,
        "attempted": len(in_window),
        "failed": sum(s.failed for s in in_window),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        # idle by innermost span as [name, seconds] pairs, at most 10, as the other lists
        by_span = [[n, t] for n, t in reduced["idle_by_span"].items()][:10]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"], "idle_by_span": by_span}
    result["readings"] = verdicts
    name = "control_gap" if control else "logit_gap"
    result["check"] = {name: {"value": gap, "limit": limit}}
    log(f"check {name} {gap} limit {limit}")
    return result
