"""Cluster serving entrypoint: the step-driven engine under a synthetic load.

    python -m repro.launch.serve --arch smollm-135m --reduced \
        --requests 8 --mode pdswap --swap-policy swap-aware \
        --temperature 0.8 --top-k 40 --top-p 0.95

Drives ``EngineCore.step()`` (the paper's single-RP temporal logic swap, or
the static TeLLMe-style baseline with --mode static) with per-request
``SamplingParams`` and a pluggable ``SwapPolicy``, and prints per-phase
stats including the measured overlap of the swap and per-request TTFT /
queue wait.  Requests arrive on a seeded Poisson process
(``--arrival-rate R`` requests/s, via ``repro.serving.arrivals``) or on the
legacy step grid (``--arrival-every N`` submits one request every N steps)
so the swap policy actually has transitions to schedule.

With ``--serve`` the same engine runs behind an HTTP front-end on stdlib
asyncio streams (no web framework): ``POST /generate`` streams each token
delta as a server-sent event, ``GET /stats`` returns the engine snapshot as
JSON (``GET /stats/v2`` the typed registry form), ``GET /metrics`` serves
the Prometheus text exposition, and saturation surfaces as ``429`` with the
admission-reject reason.  ``--trace-out trace.json`` records the run's
lifecycle/engine spans and writes a Chrome trace (chrome://tracing,
https://ui.perfetto.dev) on exit — batch and server modes both.

    python -m repro.launch.serve --arch smollm-135m --reduced --serve --port 8035
    curl -N -d '{"prompt": [3, 1, 4, 1, 5, 9], "max_new": 8}' \
        http://127.0.0.1:8035/generate
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs import ALL_ARCHS, get_config, reduced_config
from repro.models import get_model
from repro.serving import (
    AdmissionRejected,
    AsyncEngine,
    DisaggEngine,
    EngineCore,
    Request,
    SamplingParams,
    make_disagg_meshes,
)
from repro.serving.arrivals import poisson_times
from repro.serving.policy import POLICIES


def _http_payload(writer, status: str, body: bytes,
                  ctype: str = "application/json") -> None:
    writer.write(
        f"HTTP/1.1 {status}\r\nContent-Type: {ctype}\r\n"
        f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n".encode()
        + body)


@dataclasses.dataclass
class ServerState:
    """Shared handler state: once ``draining`` flips, new ``POST /generate``
    submits answer ``503`` while ``GET /stats`` keeps serving, so a load
    balancer sees the instance leave rotation without losing observability."""

    draining: bool = False


async def handle_connection(eng: AsyncEngine, default_params: SamplingParams,
                            reader: asyncio.StreamReader,
                            writer: asyncio.StreamWriter,
                            state: "ServerState | None" = None) -> None:
    """One HTTP exchange on raw asyncio streams (no web framework).

    ``POST /generate`` takes a JSON body — ``prompt`` (token ids, required),
    optional ``max_new``, ``tenant``, ``weight``, ``temperature``, ``top_k``,
    ``top_p``, ``seed``, ``stop_tokens`` — and streams one server-sent event
    per ``RequestOutput`` delta.  A saturated admission queue answers ``429``
    with the reject reason instead of hanging the client.  ``GET /stats``
    returns ``AsyncEngine.snapshot()``.
    """
    try:
        request_line = await reader.readline()
        parts = request_line.decode("latin-1").split()
        if len(parts) < 2:
            return
        method, path = parts[0], parts[1]
        headers = {}
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            key, _, val = line.decode("latin-1").partition(":")
            headers[key.strip().lower()] = val.strip()
        body = b""
        length = int(headers.get("content-length", "0") or 0)
        if length:
            body = await reader.readexactly(length)

        if method == "GET" and path == "/stats":
            _http_payload(writer, "200 OK", json.dumps(eng.snapshot()).encode())
        elif method == "GET" and path == "/stats/v2":
            _http_payload(writer, "200 OK",
                          json.dumps(eng.snapshot_v2()).encode())
        elif method == "GET" and path == "/metrics":
            from repro.obs.metrics import PROMETHEUS_CONTENT_TYPE

            _http_payload(writer, "200 OK",
                          eng.metrics_registry().prometheus_text().encode(),
                          ctype=PROMETHEUS_CONTENT_TYPE)
        elif method == "POST" and path == "/generate":
            if state is not None and state.draining:
                _http_payload(writer, "503 Service Unavailable", json.dumps(
                    {"error": "shutting down: server is draining"}).encode())
                return
            try:
                spec = json.loads(body or b"{}")
                prompt = np.asarray(spec["prompt"], np.int32)
            except (ValueError, KeyError, TypeError) as e:
                _http_payload(writer, "400 Bad Request",
                              json.dumps({"error": f"bad request body: {e}"}).encode())
                return
            sp = default_params
            if any(k in spec for k in
                   ("temperature", "top_k", "top_p", "seed", "stop_tokens")):
                sp = SamplingParams(
                    temperature=float(spec.get("temperature", default_params.temperature)),
                    top_k=int(spec.get("top_k", default_params.top_k)),
                    top_p=float(spec.get("top_p", default_params.top_p)),
                    seed=int(spec.get("seed", default_params.seed or 0)),
                    stop_tokens=tuple(spec.get("stop_tokens",
                                               default_params.stop_tokens)),
                )
            try:
                stream = await eng.submit(
                    prompt, sp,
                    request_id=spec.get("request_id"),
                    max_new=spec.get("max_new"),
                    tenant=str(spec.get("tenant", "default")),
                    weight=float(spec.get("weight", 1.0)),
                )
            except AdmissionRejected as e:
                _http_payload(writer, "429 Too Many Requests",
                              json.dumps({"error": e.reason}).encode())
                return
            writer.write(b"HTTP/1.1 200 OK\r\nContent-Type: text/event-stream\r\n"
                         b"Cache-Control: no-cache\r\nConnection: close\r\n\r\n")
            await writer.drain()
            async for out in stream:
                event = {
                    "request_id": out.request_id,
                    "new_token_ids": list(out.new_token_ids),
                    "finished": out.finished,
                    "finish_reason": out.finish_reason,
                }
                writer.write(b"data: " + json.dumps(event).encode() + b"\n\n")
                await writer.drain()
        else:
            _http_payload(writer, "404 Not Found",
                          json.dumps({"error": f"no route {method} {path}"}).encode())
    except (ConnectionResetError, asyncio.IncompleteReadError):
        pass  # client went away mid-exchange; the engine keeps its own state
    finally:
        try:
            await writer.drain()
            writer.close()
            await writer.wait_closed()
        except (ConnectionResetError, BrokenPipeError):
            pass


async def serve_http(core: EngineCore, default_params: SamplingParams,
                     host: str, port: int, *, max_queue: int = 64,
                     ready: "asyncio.Event | None" = None,
                     stop: "asyncio.Event | None" = None,
                     grace_s: float = 5.0) -> int:
    """Run the engine behind the asyncio-streams HTTP front-end until asked
    to stop, then shut down gracefully.

    ``ready`` (tests) is set once the socket is listening.  SIGINT/SIGTERM —
    or ``stop`` being set, the test hook — starts the drain: new
    ``POST /generate`` submits answer ``503`` (``GET /stats`` stays up),
    in-flight streams get up to ``grace_s`` seconds to finish naturally, and
    whatever is still running at the deadline is aborted by the engine
    shutdown with a terminal ``finish_reason="abort"`` delta, so no client
    reader ever hangs on a half-open stream.
    """
    if stop is None:
        stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    state = ServerState()
    hooked = []
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
            hooked.append(sig)
        except (NotImplementedError, RuntimeError, ValueError):
            pass  # non-main thread or a platform without loop signal support
    try:
        async with AsyncEngine(core, max_queue=max_queue) as eng:
            server = await asyncio.start_server(
                lambda r, w: handle_connection(eng, default_params, r, w,
                                               state=state),
                host, port)
            bound = server.sockets[0].getsockname()
            print(f"serving on http://{bound[0]}:{bound[1]}  "
                  f"(POST /generate streams SSE, GET /stats, GET /metrics)")
            if ready is not None:
                ready.set()
            try:
                try:
                    await stop.wait()
                except asyncio.CancelledError:
                    pass
                state.draining = True
                print(f"draining: rejecting new work (503), waiting up to "
                      f"{grace_s:.1f}s for in-flight streams")
                deadline = loop.time() + grace_s
                while loop.time() < deadline and (
                        core.has_unfinished()
                        or eng.snapshot()["frontend"]["open_streams"]):
                    await asyncio.sleep(0.02)
                # abort whatever is still running and route each stream its
                # terminal delta BEFORE closing the server: since Python
                # 3.12 Server.wait_closed() also waits for open connections,
                # and a stream's handler returns only after that delta
                await eng.shutdown()
            finally:
                server.close()
                await server.wait_closed()
    finally:
        for sig in hooked:
            loop.remove_signal_handler(sig)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=ALL_ARCHS, default="smollm-135m")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--mode", default="pdswap", choices=["pdswap", "static"])
    p.add_argument("--cache-layout", default="contiguous", choices=["contiguous", "paged"])
    p.add_argument("--block-size", type=int, default=16,
                   help="tokens per KV page (paged layout)")
    p.add_argument("--num-blocks", type=int, default=None,
                   help="KV pool pages (paged layout; default = full provisioning)")
    p.add_argument("--kv-dtype", default="fp", choices=["fp", "int8", "int4"],
                   help="KV-cache precision: packed int8/int4 payload + fp32 "
                        "scale planes (fused dequant in the decode kernels)")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   help="tokens per prefill quantum: run long prompts as "
                        "bounded chunks with a decode round between each, "
                        "instead of one atomic burst (None = monolithic; "
                        "paged layout needs a multiple of --block-size)")
    p.add_argument("--spec-decode", type=int, default=0, metavar="K",
                   help="speculative decoding draft depth: each decode round "
                        "drafts up to K tokens by prompt lookup (n-gram match "
                        "against the request's own history) and verifies all "
                        "K+1 positions in one forward pass (0 = off); greedy "
                        "streams stay bit-identical to plain decode")
    p.add_argument("--spec-ngram", type=int, default=3, metavar="N",
                   help="prompt-lookup n-gram size for --spec-decode drafting")
    p.add_argument("--disagg", action="store_true",
                   help="disaggregated serving: prefill and decode run as "
                        "two phase-specialized pools with KV handoff between "
                        "them (uses the first two local devices as 1-wide "
                        "pools when available, else colocates both pools on "
                        "the default device; greedy outputs stay "
                        "bit-identical to the single engine)")
    p.add_argument("--ragged", action="store_true",
                   help="draw prompt lengths uniformly in [4, prompt_len]")
    p.add_argument("--requests", type=int, default=6)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--max-len", type=int, default=128)
    p.add_argument("--no-overlap", action="store_true",
                   help="serialize the swap after the prefill tail (ablation)")
    p.add_argument("--seed", type=int, default=0,
                   help="seeds the params, the workload, and sampling")
    # --- step-driven serving API ---
    p.add_argument("--swap-policy", default="drain", choices=sorted(POLICIES),
                   help="prefill<->decode transition policy (paper: drain)")
    p.add_argument("--arrival-every", type=int, default=0,
                   help="submit one request every N steps (0 = all up front; "
                        "ignored when --arrival-rate is set)")
    p.add_argument("--arrival-rate", type=float, default=0.0,
                   help="seeded Poisson arrivals at R requests/s wall clock "
                        "(0 = use --arrival-every)")
    # --- HTTP/SSE server mode ---
    p.add_argument("--serve", action="store_true",
                   help="run as an HTTP server instead of a batch drive: "
                        "POST /generate streams SSE token deltas, GET /stats "
                        "returns the engine snapshot")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8035)
    p.add_argument("--max-queue", type=int, default=64,
                   help="server mode: admission backlog bound before "
                        "submits are rejected with 429")
    p.add_argument("--grace", type=float, default=5.0,
                   help="server mode: seconds to let in-flight streams "
                        "finish after SIGINT/SIGTERM before aborting them")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="record per-request lifecycle + engine spans and "
                        "write a Chrome trace-event JSON here on exit "
                        "(open in chrome://tracing or ui.perfetto.dev)")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="sampling temperature (0 = greedy, the paper setting)")
    p.add_argument("--top-k", type=int, default=0, help="top-k truncation (0 = off)")
    p.add_argument("--top-p", type=float, default=1.0, help="nucleus mass (1.0 = off)")
    p.add_argument("--stop-token", type=int, action="append", default=None,
                   help="token id that ends generation (repeatable)")
    args = p.parse_args(argv)
    enable_compile_cache()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    assert cfg.family == "transformer", "serving engine drives the transformer family"
    api = get_model(cfg)
    params = api.init(cfg, jax.random.PRNGKey(args.seed), dtype=jnp.float32)

    kw = dict(n_slots=args.slots, max_len=args.max_len,
              prompt_len=args.prompt_len, mode=args.mode,
              cache_layout=args.cache_layout, block_size=args.block_size,
              num_blocks=args.num_blocks, kv_dtype=args.kv_dtype,
              overlap=not args.no_overlap, swap_policy=args.swap_policy,
              prefill_chunk=args.prefill_chunk,
              spec_decode=args.spec_decode or None,
              spec_ngram=args.spec_ngram)
    if args.disagg:
        try:
            pmesh, dmesh = make_disagg_meshes()
        except ValueError:
            pmesh = dmesh = None
            print("disagg: fewer than 2 local devices, colocating both pools "
                  "(set XLA_FLAGS=--xla_force_host_platform_device_count=2 "
                  "for real two-pool overlap on CPU)")
        eng = DisaggEngine(cfg, params, prefill_mesh=pmesh,
                           decode_mesh=dmesh, **kw)
    else:
        eng = EngineCore(cfg, params, **kw)
    sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                        top_p=args.top_p, seed=args.seed,
                        stop_tokens=tuple(args.stop_token or ()))
    if args.trace_out:
        from repro.obs.trace import TRACER

        TRACER.enable()
    if args.serve:
        try:
            return asyncio.run(serve_http(eng, sp, args.host, args.port,
                                          max_queue=args.max_queue,
                                          grace_s=args.grace))
        except KeyboardInterrupt:
            return 0
        finally:
            if args.trace_out:
                trace = TRACER.export_chrome_trace(args.trace_out)
                print(f"trace: {len(trace['traceEvents'])} events -> "
                      f"{args.trace_out} ({TRACER.dropped} dropped)")

    rng = np.random.default_rng(args.seed)
    ragged_lo = max(1, min(4, args.prompt_len))  # keep low < high for tiny prompt-len
    pending = []
    for i in range(args.requests):
        n = int(rng.integers(ragged_lo, args.prompt_len + 1)) if args.ragged else args.prompt_len
        prompt = rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
        pending.append(Request(f"req-{i}", prompt, max_new=args.max_new, params=sp))

    if args.arrival_rate > 0.0:
        # seeded Poisson arrivals in wall-clock time: submit each request
        # once its sampled arrival instant has passed, sleeping only when
        # the engine is otherwise idle
        times = poisson_times(args.arrival_rate, len(pending),
                              np.random.default_rng(args.seed + 1))
        arrivals = list(zip(times.tolist(), pending))
        pending = []
        t0 = time.perf_counter()
        while eng.has_unfinished() or arrivals:
            now = time.perf_counter() - t0
            while arrivals and arrivals[0][0] <= now:
                eng.submit(arrivals.pop(0)[1])
            if eng.has_unfinished():
                eng.step()
            elif arrivals:
                time.sleep(max(0.0, arrivals[0][0] - (time.perf_counter() - t0)))
    else:
        if args.arrival_every <= 0:
            for r in pending:
                eng.submit(r)
            pending = []
        step = 0
        while eng.has_unfinished() or pending:
            step += 1
            if pending and (step - 1) % args.arrival_every == 0:
                eng.submit(pending.pop(0))
            eng.step()
    stats = eng.stats

    sampled = "greedy" if sp.greedy else (
        f"T={sp.temperature} top_k={sp.top_k} top_p={sp.top_p} seed={sp.seed}")
    print(f"\nmode={args.mode} overlap={not args.no_overlap} "
          f"policy={args.swap_policy} sampling={sampled}")
    print(f"  requests finished : {len(eng.finished)}/{args.requests}")
    print(f"  prefill tokens    : {stats.prefill_tokens}  ({stats.t_prefill:.2f}s)")
    print(f"  decode tokens     : {stats.decode_tokens}  ({stats.t_decode:.2f}s, "
          f"{stats.decode_tput():.1f} tok/s on this host)")
    print(f"  logic swaps       : {stats.swaps}  in {stats.prefill_bursts} "
          f"prefill bursts (fabric flips)")
    if stats.prefill_chunks:
        print(f"  prefill chunks    : {stats.prefill_chunks}  "
              f"(chunk={args.prefill_chunk} tokens, decode interleaved between chunks)")
    if stats.verify_rounds:
        print(f"  speculative decode: k={args.spec_decode} ngram={args.spec_ngram}  "
              f"{stats.accepted_tokens}/{stats.draft_tokens} drafts accepted "
              f"({100*stats.acceptance_rate():.0f}%), "
              f"{stats.tokens_per_round():.2f} tokens/round over "
              f"{stats.verify_rounds} verify rounds")
    # client-visible TTFT: arrival (submit) to first token, queueing included
    ttfts = [r.first_token_t - r.arrival_time_s
             for r in eng.finished.values() if r.first_token_t]
    if ttfts:
        print(f"  TTFT              : mean {1e3*float(np.mean(ttfts)):.1f} ms, "
              f"p max {1e3*float(np.max(ttfts)):.1f} ms")
    if stats.queue_wait.count:
        print(f"  queue wait        : p50 {1e3*stats.queue_wait.p50:.1f} ms, "
              f"p95 {1e3*stats.queue_wait.p95:.1f} ms over "
              f"{stats.queue_wait.count} admissions")
    if stats.itl.count:
        print(f"  ITL               : p50 {1e3*stats.itl.p50:.1f} ms, "
              f"p95 {1e3*stats.itl.p95:.1f} ms")
    reasons = {}
    for r in eng.finished.values():
        reasons[r.finish_reason] = reasons.get(r.finish_reason, 0) + 1
    print(f"  finish reasons    : {reasons}")
    if args.cache_layout == "paged":
        kb = eng.kv_bytes()
        print(f"  KV pool           : {kb['allocated']/2**20:.2f} MiB allocated, "
              f"{kb['peak_in_use']/2**20:.2f} MiB peak in use "
              f"(kv_dtype={kb['kv_dtype']}, payload {kb['payload']/2**20:.2f} MiB)")
        print(f"  prefix cache      : {stats.prefix_hits} page hits / "
              f"{stats.prefix_misses} misses ({stats.prefix_hit_tokens} tokens reused)")
        print(f"  preemptions       : {stats.preemptions}  "
              f"admission blocks: {stats.admission_blocks}")
    if args.disagg:
        ho = eng.snapshot()["disagg"]["handoff"]
        print(f"  KV handoff        : {ho['segments']} segments "
              f"({ho['eager_segments']} eager), "
              f"{ho['bytes_shipped']/2**20:.2f} MiB shipped, "
              f"{ho['installs']} installs")
    if stats.swap_agg.count:
        print(f"  swap mean exposed cost: {1e3*stats.swap_agg.mean_cost:.2f} ms")
    drift = eng.snapshot().get("roofline_drift", {})
    for phase, d in drift.items():
        print(f"  roofline [{phase:>11}]: measured "
              f"{1e6*d['measured_s_per_token']:.2f} us/tok vs bound "
              f"{1e6*d['bound_s_per_token']:.3f} us/tok "
              f"(residency {d['residency_ratio']:.4f})")
    for rid in sorted(eng.finished)[:3]:
        print(f"  {rid}: {eng.finished[rid].out_tokens[:8]}...")
    if args.trace_out:
        trace = TRACER.export_chrome_trace(args.trace_out)
        print(f"  trace             : {len(trace['traceEvents'])} events -> "
              f"{args.trace_out} ({TRACER.dropped} dropped)")
    return 0 if len(eng.finished) == args.requests else 1


if __name__ == "__main__":
    raise SystemExit(main())
