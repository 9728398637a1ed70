"""Smoke run of the serving path on a TPU, at bitnet-730m's full width.

    python chip_smoke.py            # one chip
    python chip_smoke.py --disagg   # four chips: disaggregated serving only

One chip:

1. ``repro.launch.serve.main`` serves 8 requests (512-token prompts, 32 new
   tokens, 4 slots, max_len 1024) in pdswap mode, once per cache layout
   (contiguous; paged with 16-token pages), on the default XLA attention;
   the packed ternary linears run the compiled TLMM kernel in every engine
   on the chip (``tlmm_matmul`` decides from the backend, not a knob).
2. The same engines with ``use_pallas=True`` (the attention kernels) for
   {contiguous, paged} x {fp, int8} KV, beside the XLA engines of the same
   layout and KV dtype: every request must finish, every prefill and
   decode logit must be finite, each kernel-path program must hold compiled
   Pallas kernels (``tpu_custom_call``), and the kernel path's prefill
   last-token logits must match the XLA path's within
   ``PREFILL_LOGIT_RTOL``.  Greedy token
   agreement between the two paths is reported, not gated: the paths
   round differently, and a random-weight model's near-ties can flip.

``--disagg`` runs only ``DisaggEngine`` over ``make_disagg_meshes(tp=2)``
against ``EngineCore`` on one device, with the same requests: the two pools
must sit on disjoint devices and KV must cross the handoff channel; greedy
agreement is reported.

Weights are random, from ``--seed``.  Everything runs in this process, which
holds the chip; it starts no other.  The script exits non-zero, and prints
no result line, unless JAX's devices are TPUs and every phase passed.  The
last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ARCH = "bitnet-730m"
# |kernel - XLA| over the prefill last-token logits, as a fraction of the
# XLA logits' largest magnitude.  Both paths compute in f32 from the same
# f32 weights; they differ in matmul passes and summation order, and the
# per-token int8 activation quantizer turns some of that into whole steps.
PREFILL_LOGIT_RTOL = 5e-2


@dataclasses.dataclass(frozen=True)
class Workload:
    requests: int = 8
    slots: int = 4
    prompt_len: int = 512
    max_new: int = 32
    max_len: int = 1024
    block_size: int = 16
    seed: int = 0


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events (a persistent-cache hit skips the backend compile)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
            self.backend_compiles += event == self.EVENTS[-1]


def tpu_devices(need: int):
    """JAX's devices; an error unless they are at least ``need`` TPUs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(f"chip_smoke needs a TPU; JAX's devices are "
                           f"{devices[0].platform}: {devices}")
    if len(devices) < need:
        raise RuntimeError(f"this phase needs {need} TPU chips, found {len(devices)}")
    return devices


def prompts(cfg, wl: Workload):
    """The prompts ``repro.launch.serve`` draws for the same seed and sizes."""
    import numpy as np

    rng = np.random.default_rng(wl.seed)
    return [rng.integers(0, cfg.vocab_size, size=wl.prompt_len).astype(np.int32)
            for _ in range(wl.requests)]


def serve_cli(arch_args, wl: Workload, clock: CompileClock) -> None:
    """Phase 1: the serving CLI in both cache layouts."""
    from repro.launch import serve

    for layout in ("contiguous", "paged"):
        argv = [*arch_args, "--mode", "pdswap", "--requests", str(wl.requests),
                "--slots", str(wl.slots), "--prompt-len", str(wl.prompt_len),
                "--max-new", str(wl.max_new), "--max-len", str(wl.max_len),
                "--seed", str(wl.seed), "--cache-layout", layout]
        if layout == "paged":
            argv += ["--block-size", str(wl.block_size)]
        c0, t0 = clock.seconds, time.perf_counter()
        rc = serve.main(argv)
        print(f"[serve {layout}] exit {rc}, {time.perf_counter() - t0:.1f}s wall, "
              f"{clock.seconds - c0:.1f}s compiling", flush=True)
        if rc != 0:
            raise RuntimeError(f"repro.launch.serve {' '.join(argv)} exited {rc}: "
                               "not every request finished")


def _engine(cfg, params, wl: Workload, **kw):
    from repro.serving import EngineCore

    return EngineCore(cfg, params, n_slots=wl.slots, max_len=wl.max_len,
                      prompt_len=wl.prompt_len, mode="pdswap",
                      block_size=wl.block_size, **kw)


def serve_checked(eng, prompts_, wl: Workload):
    """Serve the prompts greedily; returns ({id: tokens}, all logits finite).
    The runner's prefill and decode calls are wrapped to keep a device-side
    finiteness flag of every logits array the engine samples from."""
    import jax.numpy as jnp

    from repro.serving import Request

    flags = []
    runner = eng.runner
    prefill, decode_logits = runner.prefill, runner.decode_logits

    def checked_prefill(*a, **k):
        logits = prefill(*a, **k)
        flags.append(jnp.isfinite(logits).all())
        return logits

    def checked_decode(*a, **k):
        logits = decode_logits(*a, **k)
        flags.append(jnp.isfinite(logits).all())
        return logits

    runner.prefill, runner.decode_logits = checked_prefill, checked_decode
    for i, p in enumerate(prompts_):
        eng.submit(Request(f"r{i}", p.copy(), max_new=wl.max_new))
    eng.run()
    done = {k: r.out_tokens for k, r in eng.finished.items()}
    if len(done) != wl.requests or any(len(t) != wl.max_new for t in done.values()):
        raise RuntimeError(f"{len(done)}/{wl.requests} requests finished, token "
                           f"counts {sorted(len(t) for t in done.values())}")
    return done, bool(jnp.all(jnp.stack(flags)))


def holds_pallas_kernel(compiled) -> bool:
    """Whether a compiled program calls a Mosaic (Pallas TPU) kernel."""
    return "tpu_custom_call" in compiled.as_text()


def kernel_programs(eng, wl: Workload) -> dict:
    """The engine's prefill body and decode programs, compiled for the
    engine's own buffers (lowering runs nothing)."""
    import jax.numpy as jnp

    r = eng.runner
    bucket = r.bucket(wl.prompt_len)
    tokens = jnp.zeros((1, bucket), jnp.int32)
    lengths = jnp.zeros((wl.slots,), jnp.int32)
    if r.cache_layout == "paged":
        dec_args = (r.params, r.last_tokens, r.paged.kv, r.paged.block_tables_array(), lengths)
    else:
        dec_args = (r.params, r.last_tokens, r.cache, lengths)
    return {
        "prefill": r.progs(bucket)["body"].fn.lower(r.params, tokens).compile(),
        "decode": r.decode_prog.fn.lower(*dec_args).compile(),
    }


def agreement(ref: dict, got: dict) -> str:
    same = sum(a == b for k in ref for a, b in zip(ref[k], got[k]))
    total = sum(len(t) for t in ref.values())
    return f"{same}/{total} greedy tokens agree"


def compiled_kernels(cfg, params, wl: Workload, clock: CompileClock) -> None:
    """Phase 2: the use_pallas engines against the XLA engines."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.phase_engine import PhaseEngine

    kcfg = dataclasses.replace(cfg, use_pallas=True)
    ps = prompts(cfg, wl)
    pa = jax.eval_shape(lambda: params)
    tokens = jnp.asarray(np.stack(ps))
    logits = {}
    for name, c in (("xla", cfg), ("pallas", kcfg)):
        prog = PhaseEngine(c).prefill_program(pa, wl.requests, wl.prompt_len)
        out, _ = prog.fn(params, tokens)
        logits[name] = np.asarray(out[:, : cfg.vocab_size], np.float32)
        if name == "pallas" and not holds_pallas_kernel(prog.fn.lower(params, tokens).compile()):
            raise RuntimeError("the use_pallas prefill program holds no Pallas kernel")
    for name, lg in logits.items():
        if not np.isfinite(lg).all():
            raise RuntimeError(f"{name} prefill logits are not all finite")
    scale = float(np.abs(logits["xla"]).max())
    err = float(np.abs(logits["pallas"] - logits["xla"]).max())
    top1 = int((logits["pallas"].argmax(-1) == logits["xla"].argmax(-1)).sum())
    print(f"[prefill logits] max|pallas - xla| = {err:.5f}, max|xla| = {scale:.4f}, "
          f"ratio {err / scale:.5f} (tolerance {PREFILL_LOGIT_RTOL}); "
          f"top-1 agrees on {top1}/{wl.requests} prompts", flush=True)
    if not err <= PREFILL_LOGIT_RTOL * scale:
        raise RuntimeError("kernel-path prefill logits are outside the tolerance")

    for layout in ("contiguous", "paged"):
        for kv_dtype in ("fp", "int8"):
            tag = f"{layout}/{kv_dtype}"
            outs = {}
            for name, c in (("xla", cfg), ("pallas", kcfg)):
                c0, t0 = clock.seconds, time.perf_counter()
                eng = _engine(c, params, wl, cache_layout=layout, kv_dtype=kv_dtype)
                outs[name], finite = serve_checked(eng, ps, wl)
                if not finite:
                    raise RuntimeError(f"[{tag} {name}] non-finite logits")
                if name == "pallas":
                    missing = [k for k, c in kernel_programs(eng, wl).items()
                               if not holds_pallas_kernel(c)]
                    if missing:
                        raise RuntimeError(f"[{tag}] {missing} hold no Pallas kernel")
                print(f"[{tag} {name}] {wl.requests} requests x {wl.max_new} tokens, "
                      f"logits finite, {time.perf_counter() - t0:.1f}s wall, "
                      f"{clock.seconds - c0:.1f}s compiling"
                      + (", tpu_custom_call in prefill and decode" if name == "pallas" else ""),
                      flush=True)
                del eng
            print(f"[{tag}] kernel vs XLA: {agreement(outs['xla'], outs['pallas'])}",
                  flush=True)


def disagg(cfg, params, wl: Workload, clock: CompileClock) -> None:
    """The four-chip phase: two tp=2 pools against one device."""
    from repro.serving import DisaggEngine, make_disagg_meshes

    pmesh, dmesh = make_disagg_meshes(tp=2)
    pre, dec = set(pmesh.devices.flat), set(dmesh.devices.flat)
    print(f"[disagg] prefill pool {sorted(d.id for d in pre)}, "
          f"decode pool {sorted(d.id for d in dec)}", flush=True)
    if pre & dec:
        raise RuntimeError("the prefill and decode pools share devices")
    ps = prompts(cfg, wl)
    outs = {}
    for name in ("one device", "disagg"):
        c0, t0 = clock.seconds, time.perf_counter()
        if name == "disagg":
            eng = DisaggEngine(cfg, params, prefill_mesh=pmesh, decode_mesh=dmesh,
                               n_slots=wl.slots, max_len=wl.max_len,
                               prompt_len=wl.prompt_len, mode="pdswap")
        else:
            eng = _engine(cfg, params, wl)
        outs[name], finite = serve_checked(eng, ps, wl)
        if not finite:
            raise RuntimeError(f"[{name}] non-finite logits")
        print(f"[{name}] {wl.requests} requests x {wl.max_new} tokens, logits finite, "
              f"{time.perf_counter() - t0:.1f}s wall, {clock.seconds - c0:.1f}s compiling",
              flush=True)
        if name == "disagg":
            ho = eng.snapshot()["disagg"]["handoff"]
            print(f"[disagg] handoff: {ho['segments']} segments, "
                  f"{ho['bytes_shipped'] / 2**20:.1f} MiB shipped, "
                  f"{ho['installs']} installs", flush=True)
            if not (ho["segments"] > 0 and ho["bytes_shipped"] > 0):
                raise RuntimeError("no KV crossed the handoff channel")
        del eng
    print(f"[disagg] vs one device: {agreement(outs['one device'], outs['disagg'])}",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--disagg", action="store_true",
                    help="run only the four-chip disaggregated-serving phase")
    ap.add_argument("--seed", type=int, default=0, help="seeds weights and prompts")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax
    import jax.numpy as jnp

    from repro.common.compile_cache import enable_compile_cache
    from repro.common.hardware import chip_for_kind
    from repro.configs import get_config
    from repro.models import get_model

    cache_dir = enable_compile_cache()
    devices = tpu_devices(4 if args.disagg else 1)
    dev = devices[0]
    chip = chip_for_kind(dev.device_kind)
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} count={len(devices)} "
          f"({chip.name}: {chip.peak_flops_bf16 / 1e12:.0f} TFLOP/s bf16, "
          f"{chip.hbm_bw / 1e9:.0f} GB/s HBM); compile cache {cache_dir}", flush=True)

    clock = CompileClock()
    wl = Workload(seed=args.seed)
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(args.seed), dtype=jnp.float32)
    jax.block_until_ready(params)
    print(f"set-up: {ARCH} {cfg.num_layers} x {cfg.d_model}, vocab {cfg.vocab_size}, "
          f"{cfg.param_count() / 1e6:.0f}M params (f32) in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)

    if args.disagg:
        disagg(cfg, params, wl, clock)
    else:
        serve_cli(["--arch", ARCH], wl, clock)
        compiled_kernels(cfg, params, wl, clock)
    print(f"compile total: {clock.seconds:.1f}s over {clock.backend_compiles} "
          f"backend compiles", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
