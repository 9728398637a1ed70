"""Step-driven serving core: ``EngineCore.step() -> list[RequestOutput]``.

The PR-1 ``ServingEngine`` was a monolith: one ``run()`` method owned
admission, the prefill<->decode transition, phase-program dispatch, greedy
argmax, and finish bookkeeping.  This module splits it into three layers
around an incremental core:

* ``Scheduler`` — the wait queue, admission validation, preemption victim
  selection, and the *swap decision*: a pluggable ``SwapPolicy``
  (``repro.serving.policy``) is consulted once per step to decide whether to
  pay the reconfiguration cost and flip into the prefill phase (paper §3.4).
  ``DrainPolicy`` reproduces the paper's drain-queue-then-decode loop;
  ``SwapCostAwarePolicy`` defers the flip while the queue is shallow
  relative to the measured/modeled swap cost.

* ``ModelRunner`` — owns everything compiled and everything device-resident:
  phase programs and compile buckets (built on ``core.phase_engine``), the
  contiguous or paged KV cache, the slot manager, per-slot sampling state,
  and the vectorized on-device sampler program.  It executes prefill (with
  the latency-overlapped swap), decode rounds, and preemption replay.

* ``OutputProcessor`` — turns raw sampled tokens into streaming
  ``RequestOutput`` deltas and owns finish semantics (stop tokens vs the
  token budget), TTFT stamping included.

``EngineCore.step()`` advances the engine by one scheduling quantum — at
most one prefill burst (policy-gated) followed by at most one decode round —
and returns the outputs produced.  ``run()`` survives as a thin
compatibility loop over ``step()`` and, with greedy sampling and the default
``DrainPolicy``, reproduces the PR-1 engine token-for-token.
``generate()`` streams one request's outputs as an iterator.

With ``prefill_chunk=N`` the prefill burst becomes CHUNKED: ``step()`` runs
at most one N-token chunk of pending prefill per quantum (continue the
partially-prefilled request, else admit the queue head and run its first
chunk), then the decode round — so a long prompt no longer stalls every
active stream for its whole prefill; decode interleaves between chunks.
Greedy streams are bit-identical to monolithic prefill for every layout x
kv_dtype (chunk-size invariance; in the jnp reference regime — past the
reference path's 1024-token cutoff or under the Pallas prefill kernel the
monolithic summation order differs, so agreement is to float rounding),
and chunk boundaries are a pure function of (prompt length, chunk size)
so preemption replay stays bit-identical.
See ``PrefillProgress``, ``ModelRunner.run_prefill_chunk`` and the chunk
phase programs in ``core.phase_engine``.

With ``spec_decode=k`` every decode round becomes a speculative VERIFY
round: each decoding slot proposes up to ``k`` draft tokens by matching its
recent suffix against its own prompt + output history (host-side prompt
lookup, ``serving.spec_decode`` — no draft model, nothing extra resident),
one batched verify program scores all ``k + 1`` positions in a single
forward pass, the longest confirmed draft prefix plus one correction token
is emitted (multi-token ``RequestOutput`` deltas), and rejected rows are
rolled back by truncating the slot length (contiguous) / releasing the
overshoot pages (paged).  Decode is memory-bandwidth-bound (Eq. 5 — each
token streams the whole KV cache + weights), so every accepted draft token
amortizes a stream the round already paid for.  Greedy targets are the
verify logits' argmax and sampled targets reuse the sequential
``fold_in(seed, token_index)`` key stream, so emitted streams match the
non-speculative engine token-for-token and preemption replay is unchanged
(recorded tokens teacher-force through the decode program; drafts are a
pure function of the token history, so no speculation state survives a
restart).  ``EngineStats`` reports ``draft_tokens`` / ``accepted_tokens``
/ ``acceptance_rate()`` / ``tokens_per_round()``.

Faithful mode (``mode="pdswap"``) and the static baseline, and the
contiguous vs paged cache layouts, keep their PR-1 semantics — see
``repro.serving.engine`` for the original mode/layout notes.  Sampling is
per-request (``SamplingParams``): temperature / top-k / top-p with per-slot
PRNG keys derived as ``fold_in(PRNGKey(seed), token_index)``, so preemption
replay (teacher-forced recorded tokens) resumes the key stream exactly and
stays bit-identical under non-greedy sampling.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, Dict, Iterator, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels
from repro.common.tree import tree_bytes
from repro.configs.base import ModelConfig
from repro.core.kv_cache import KVSlotManager, insert_prefill_kv
from repro.core.swap import SwapAggregates, SwapController, SwapTiming
from repro.models import get_model
from repro.obs.trace import TRACER
from repro.serving.outputs import OutputProcessor, RequestOutput
from repro.serving.fair_queue import WeightedFairQueue
from repro.serving.paging import PagedKVCache, PoolExhausted, PrefixMatch, cdiv
from repro.serving.policy import DrainPolicy, SchedulerView, SwapPolicy, make_policy
from repro.serving.sampling import SamplingParams
from repro.serving.slo import LatencyStat

# Raw SwapTiming records kept for inspection; older records collapse into
# EngineStats.swap_agg (running aggregates the SwapCostAwarePolicy reads).
SWAP_TIMING_WINDOW = 64


@dataclasses.dataclass
class Request:
    request_id: str
    prompt: np.ndarray  # (S,) int32 — any length with S + max_new <= max_len
    max_new: int
    priority: int = 0  # larger = more important; lowest goes first on preemption
    params: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    # multi-tenant fair queueing: requests are drained from per-tenant FIFO
    # lanes in weighted deficit-round-robin order (serving.fair_queue), so
    # one tenant's burst cannot starve the others
    tenant: str = "default"
    weight: float = 1.0  # fair-queue share relative to other tenants
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    # Arrival (client submit) time — stamped at the FIRST submit and never
    # overwritten, so TTFT = first_token_t - arrival_time_s includes every
    # queueing delay (front-end admission queue + scheduler wait queue).
    arrival_time_s: float = 0.0
    enqueue_t: float = 0.0  # scheduler-queue entry (re-stamped on requeue)
    first_token_t: float = 0.0
    last_emit_t: float = 0.0  # previous delta's emit time (ITL tracking)
    queue_wait_s: Optional[float] = None  # arrival -> first successful admission
    done_t: float = 0.0
    finish_reason: Optional[str] = None  # "stop" | "length" | "abort" once finished
    # Set on preemption.  The restart re-prefills the prompt, then REPLAYS
    # the recorded out_tokens through the decode program (teacher-forcing),
    # reproducing the exact pre-eviction cache state — the same kernels run
    # on the same inputs, and the sampler's key stream is a pure function of
    # (seed, token index), so the continuation is bit-identical to a run
    # that was never preempted, greedy or sampled alike.
    preempted: bool = False


@dataclasses.dataclass
class PrefillProgress:
    """Host-side state of one partially-prefilled request (chunked prefill).

    Chunk boundaries (``sizes``) are a pure function of (prompt length,
    chunk size) — a preemption-restart re-prefills through the exact same
    chunk programs, which is what keeps replay bit-identical under
    chunking.  Paged prompts allocate ALL their pages at admission
    (``match``); each chunk then writes only its own page span.
    """

    req: Request
    slot: int
    resuming: bool  # restart with recorded tokens: replay them after prefill
    restarted: bool  # ANY preemption restart (even mid-prefill, no tokens yet):
    # its re-prefill is recompute overhead (t_replay), never offered load —
    # prefill_tokens / swaps / prefix counters are charged once per request
    sizes: List[int]  # real (unpadded) chunk sizes, in order
    ci: int = 0  # next chunk index
    pos: int = 0  # tokens already prefilled (real, unpadded)
    match: Optional[PrefixMatch] = None

    @property
    def remaining_chunks(self) -> int:
        return len(self.sizes) - self.ci


@dataclasses.dataclass
class EngineStats:
    prefill_tokens: int = 0
    decode_tokens: int = 0
    decode_rounds: int = 0
    swaps: int = 0
    prefill_bursts: int = 0  # prefill phases entered (fabric flips, not swaps)
    prefill_chunks: int = 0  # chunked-prefill quanta executed (0 = monolithic)
    swap_timings: Deque[SwapTiming] = dataclasses.field(
        default_factory=lambda: deque(maxlen=SWAP_TIMING_WINDOW)
    )
    swap_agg: SwapAggregates = dataclasses.field(default_factory=SwapAggregates)
    t_prefill: float = 0.0
    t_decode: float = 0.0
    # paged-layout counters
    prefix_hits: int = 0  # prompt pages served from the prefix cache
    prefix_misses: int = 0  # full prompt pages that had to be written
    prefix_hit_tokens: int = 0  # tokens covered by cache-hit pages
    preemptions: int = 0  # requests evicted to free pool capacity
    admission_blocks: int = 0  # prefill attempts deferred on pool pressure
    replayed_tokens: int = 0  # recompute overhead paid by preemption restarts
    t_replay: float = 0.0  # wall time of restart replays (kept out of t_decode)
    # speculative-decoding counters (spec_decode=k)
    draft_tokens: int = 0  # prompt-lookup draft tokens proposed to verify
    accepted_tokens: int = 0  # draft tokens the verify pass confirmed
    verify_rounds: int = 0  # decode rounds run through the verify program
    slot_rounds: int = 0  # sum over decode rounds of active slots — the
    # per-slot normalizer (a plain batched round is batch-many slot-rounds)
    decode_ctx_tokens: int = 0  # context tokens streamed per decode pass,
    # summed over slot-rounds — decode_ctx_tokens / slot_rounds is the mean
    # context the Eq. (5) KV-stream bound is evaluated at (obs.drift)
    # client-visible latency aggregates (bounded windows, see serving.slo):
    # queue wait (arrival -> first successful admission), TTFT (arrival ->
    # first token), ITL (gap between consecutive streamed deltas).  The
    # SLOAwareSwapPolicy binds to these.
    queue_wait: LatencyStat = dataclasses.field(default_factory=LatencyStat)
    ttft: LatencyStat = dataclasses.field(default_factory=LatencyStat)
    itl: LatencyStat = dataclasses.field(default_factory=LatencyStat)
    # per-tenant queue-wait aggregates (same bounded windows), keyed by
    # Request.tenant — pairs with the fair queue's lane depths in
    # EngineCore.snapshot()["tenants"] so WFQ behavior is observable
    tenant_queue_wait: Dict[str, LatencyStat] = dataclasses.field(default_factory=dict)
    aborts: int = 0  # requests cancelled mid-flight or while queued
    sheds: int = 0  # queued requests dropped by SLO admission control
    # host overhead: step() calls, wall time inside them, and the part of it
    # spent in the engine's own block_until_ready calls (decode, verify,
    # chunk, prefill, replay) — t_step - t_wait is the host's time per step
    steps: int = 0
    t_step: float = 0.0
    t_wait: float = 0.0
    # weights as the runner holds them, fixed when it is built
    # (ModelRunner.weight_residency): ternary linear matrices served from
    # packed 2-bit weights, those re-quantized from latent weights in every
    # call, the packed ones a decode round runs through the compiled TLMM
    # kernel, and the bytes of the whole resident weight tree
    packed_linears: int = 0
    latent_linears: int = 0
    kernel_linears: int = 0
    weight_bytes: int = 0

    def decode_tput(self) -> float:
        return self.decode_tokens / self.t_decode if self.t_decode else 0.0

    def acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens the verify pass accepted."""
        return self.accepted_tokens / self.draft_tokens if self.draft_tokens else 0.0

    def tokens_per_round(self) -> float:
        """Mean tokens emitted per SLOT per decode round — exactly 1.0
        without speculation regardless of batch size (normalizing by
        ``slot_rounds``, not rounds, keeps batch width out of the
        number); every accepted draft raises it (the per-stream Eq. (5)
        amortization factor)."""
        return self.decode_tokens / self.slot_rounds if self.slot_rounds else 0.0

    def decode_round_cost(self) -> float:
        return self.t_decode / self.decode_rounds if self.decode_rounds else 0.0

    def record_swap(self, timing: SwapTiming) -> None:
        self.swaps += 1
        self.swap_timings.append(timing)
        self.swap_agg.update(timing)

    def snapshot(self) -> dict:
        """One JSON-serializable stats block — the consistent surface every
        benchmark (and the SSE server's /stats endpoint) reports.  Raw
        counters plus the derived rates and the bounded-window latency
        aggregates; the raw ``swap_timings`` window is summarized, not
        dumped."""
        counters = (
            "prefill_tokens", "decode_tokens", "decode_rounds", "swaps",
            "prefill_bursts", "prefill_chunks", "t_prefill", "t_decode",
            "prefix_hits", "prefix_misses", "prefix_hit_tokens",
            "preemptions", "admission_blocks", "replayed_tokens", "t_replay",
            "draft_tokens", "accepted_tokens", "verify_rounds", "slot_rounds",
            "decode_ctx_tokens", "aborts", "sheds", "steps", "t_step", "t_wait",
            "packed_linears", "latent_linears", "kernel_linears", "weight_bytes",
        )
        snap = {k: getattr(self, k) for k in counters}
        snap.update(
            decode_tput=self.decode_tput(),
            decode_round_cost=self.decode_round_cost(),
            spec_acceptance_rate=self.acceptance_rate(),
            spec_tokens_per_round=self.tokens_per_round(),
            swap_agg={
                "count": self.swap_agg.count,
                "mean_exposed_cost_s": self.swap_agg.mean_cost,
            },
            queue_wait_s=self.queue_wait.snapshot(),
            ttft_s=self.ttft.snapshot(),
            itl_s=self.itl.snapshot(),
        )
        return snap


def timed_wait(x, stats: EngineStats, span: str) -> float:
    """``jax.block_until_ready(x)`` inside the trace span ``span``, its wall
    time counted in ``stats.t_wait``; returns the ``perf_counter`` stamp
    taken when the wait ended."""
    t0 = time.perf_counter()  # analysis: allow(det:wallclock) — wait wall time feeds t_wait stats and a trace span only
    with TRACER.span(span):
        jax.block_until_ready(x)
    t1 = time.perf_counter()  # analysis: allow(det:wallclock) — wait wall time feeds t_wait stats and a trace span only
    stats.t_wait += t1 - t0
    return t1


class ModelRunner:
    """Owns phase programs, compile buckets, caches, and the sampler."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        n_slots: int = 4,
        max_len: int = 256,
        prompt_len: int = 32,
        mode: str = "pdswap",  # "pdswap" | "static"
        cache_layout: str = "contiguous",  # "contiguous" | "paged"
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        kv_dtype: str = "fp",  # "fp" | "int8" | "int4" — quantized KV cache
        mesh=None,
        overlap: bool = True,
        prefill_chunk: Optional[int] = None,  # tokens per prefill quantum (None = monolithic)
        spec_decode: Optional[int] = None,  # draft depth k (None/0 = speculation off)
        spec_ngram: int = 3,  # prompt-lookup n-gram size
    ):
        from repro.quant.kv_quant import assert_kv_dtype, quantize_kv_tree

        assert cfg.family == "transformer", "serving engine drives the transformer family"
        assert mode in ("pdswap", "static"), mode
        assert cache_layout in ("contiguous", "paged"), cache_layout
        assert_kv_dtype(kv_dtype)
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
            if cache_layout == "paged" and prefill_chunk % block_size:
                raise ValueError(
                    f"prefill_chunk ({prefill_chunk}) must be a multiple of "
                    f"block_size ({block_size}) so chunk boundaries align with "
                    "page boundaries (each chunk writes whole pages)")
        if spec_decode is not None and spec_decode < 1:
            if spec_decode == 0:
                spec_decode = None  # 0 = off, the CLI's natural spelling
            else:
                raise ValueError(f"spec_decode must be >= 1 (or 0/None = off), got {spec_decode}")
        if spec_ngram < 1:
            raise ValueError(f"spec_ngram must be >= 1, got {spec_ngram}")
        self.prefill_chunk = prefill_chunk
        self.spec_decode = spec_decode
        self.spec_ngram = spec_ngram
        from repro.core.phase_engine import PhaseEngine
        from repro.models import transformer as T

        self.cfg = cfg
        self.engine = PhaseEngine(
            cfg, mesh, max_len=max_len, cache_layout=cache_layout, kv_dtype=kv_dtype
        )
        # ternary linears are packed once here, never re-quantized per call
        params = T.convert_for_inference(cfg, params)
        if mesh is not None:  # the layout the phase programs take
            params = jax.device_put(
                params, self.engine.param_shardings(jax.eval_shape(lambda: params)))
        self.params = params
        packed, latent = T.linear_residency(cfg, params)
        with kernels.partitioned(self.engine.spmd):
            served = T.kernel_linears(cfg, params)
        self.weight_residency = dict(packed_linears=packed, latent_linears=latent,
                                     kernel_linears=served, weight_bytes=tree_bytes(params))
        self.api = get_model(cfg)
        self.mode = mode
        self.cache_layout = cache_layout
        self.kv_dtype = kv_dtype
        self.overlap = overlap and mode == "pdswap"
        self.max_len = max_len
        self.prompt_len = prompt_len
        self.block_size = block_size
        self.slots = KVSlotManager(n_slots)
        self._pa = jax.eval_shape(lambda: params)
        self._bucket_progs: Dict[int, dict] = {}  # bucket len -> phase programs
        self._chunk_progs: Dict[tuple, object] = {}  # (padded len, prefix width) -> program

        if cache_layout == "paged":
            if num_blocks is None:
                # full provisioning: every slot can grow to max_len
                num_blocks = n_slots * cdiv(max_len, block_size)
            pool_kv = T.init_paged_pool(cfg, num_blocks, block_size, kv_dtype=kv_dtype)
            if mesh is not None:  # the layout the decode program takes
                pool_kv = jax.device_put(pool_kv, self.engine.page_pool_shardings())
            self.paged = PagedKVCache(
                pool_kv, n_slots=n_slots, max_len=max_len, block_size=block_size
            )
            self.decode_prog = self.engine.paged_decode_program(
                self._pa, n_slots, self.paged.max_pages
            )
            self.cache = None
        else:
            self.paged = None

            def relay_static(kv):  # static engine: pad + layout only, no
                # phase-specialized resharding / program swap (but the
                # quantized cache still quantizes on write — storage
                # precision is a cache property, not a phase program)
                def pad(x):
                    p = [(0, 0)] * x.ndim
                    p[-2] = (0, max_len - x.shape[-2])
                    return jnp.moveaxis(jnp.pad(x, p), 0, 1)  # -> (B, L, ...)

                return quantize_kv_tree(jax.tree.map(pad, kv), kv_dtype)

            self.relay_static = jax.jit(relay_static)
            self.decode_prog = self.engine.decode_program(self._pa, n_slots, max_len)
            self.cache = T.init_cache(cfg, n_slots, max_len, kv_dtype=kv_dtype)
            if mesh is not None:  # slot installs keep this layout
                self.cache = jax.device_put(
                    self.cache, self.engine.cache_shardings(n_slots, max_len))
        self.last_tokens = jnp.zeros((n_slots,), jnp.int32)

        # Speculative decoding: ONE verify program shape (n_slots, k+1)
        # serves every round — per-slot draft depth varies at runtime via
        # the traced n_tokens operand, never by recompilation.
        self.verify_prog = None
        if spec_decode is not None:
            width = spec_decode + 1
            if cache_layout == "paged":
                self.verify_prog = self.engine.paged_verify_program(
                    self._pa, n_slots, self.paged.max_pages, width)
            else:
                self.verify_prog = self.engine.verify_program(
                    self._pa, n_slots, max_len, width)

        # Chunked prefill keeps an fp mirror of the in-flight prompt's KV
        # (prefill layout, bounded at the cache capacity) so every chunk
        # attends the exact values monolithic prefill would — see
        # transformer._prefill_chunk_body.  One buffer suffices: the
        # engine runs at most one chunked prefill at a time.
        self.chunk_prefix = None
        self.chunk_cap = None  # mirror capacity (valid when prefill_chunk set)
        if prefill_chunk is not None:
            from repro.layers.attention import KVCache as _KVCache

            cap = self.chunk_cap = (
                cdiv(max_len, block_size) * block_size
                if cache_layout == "paged" else max_len)
            shape = (cfg.num_layers, 1, cfg.num_kv_heads, cap, cfg.head_dim)
            self.chunk_prefix = _KVCache(jnp.zeros(shape, jnp.float32),
                                         jnp.zeros(shape, jnp.float32))

        # Per-slot sampling state, refreshed on slot assignment.  The fold_in
        # step index is recomputed from each request's out_tokens at sample
        # time, so there is no mutable PRNG state to checkpoint or restore.
        self._seeds = np.zeros(n_slots, np.int32)
        self._temps = np.zeros(n_slots, np.float32)
        self._top_ks = np.zeros(n_slots, np.int32)
        self._top_ps = np.ones(n_slots, np.float32)

    # ------------------------------------------------------------- buckets --

    def bucket(self, n: int) -> int:
        """Compile-bucket length for an n-token prompt (right-padded).

        Fine-grained (one quantum) up to 4 quanta, then geometric (quantum x
        power of two) — bounds distinct XLA prefill compilations at
        O(log(max_len / quantum)) instead of max_len / quantum for ragged
        workloads, at the cost of some padding compute."""
        q = self.block_size if self.cache_layout == "paged" else self.prompt_len
        b = cdiv(n, q) * q
        if b > 4 * q:
            g = 4 * q
            while g < b:
                g *= 2
            b = g
        # clamp to max_len: the paged bound stays a multiple of the quantum
        # (page-write reshape needs it, and never pads to max_len); the
        # contiguous bound clamps to the largest quantum-aligned length
        # <= max_len so bucket shapes stay consistent when max_len is not a
        # multiple of the quantum — only a prompt too long for that aligned
        # cap falls back to the single exact max_len shape (relayout pads
        # bucket -> max_len, so the bound may never exceed max_len)
        if self.cache_layout == "paged":
            b = min(b, cdiv(self.max_len, q) * q)
        else:
            cap = self.max_len - self.max_len % q
            b = min(b, cap) if n <= cap else self.max_len
        return max(b, q)

    def progs(self, bucket: int) -> dict:
        """Phase programs for one prompt bucket, built once and cached."""
        if bucket in self._bucket_progs:
            return self._bucket_progs[bucket]
        p: dict = {}
        if self.mode == "pdswap":
            p["body"], p["tail"] = self.engine.prefill_split_programs_varlen(self._pa, 1, bucket)
        else:
            p["full"] = self.engine.prefill_program_varlen(self._pa, 1, bucket)
        if self.cache_layout == "paged":
            p["write"] = self.engine.page_write_program(bucket, self.block_size)
        elif self.mode == "pdswap":
            p["relayout"] = self.engine.relayout_program(1, bucket, self.max_len)
        self._bucket_progs[bucket] = p
        return p

    # ------------------------------------------------------ chunked prefill --

    def chunk_sizes(self, n: int) -> List[int]:
        """Real (unpadded) chunk sizes for an n-token prompt — a pure
        function of (n, prefill_chunk), so a preemption-restart re-prefills
        through the exact same chunk boundaries and compiled programs
        (replay bit-identity under chunking)."""
        c = self.prefill_chunk
        sizes = [c] * (n // c)
        if n % c:
            sizes.append(n % c)
        return sizes

    def chunk_bucket(self, size: int, start: int) -> int:
        """Compile bucket for one chunk: every full chunk shares the single
        chunk-shaped compilation; the tail rounds up to the layout quantum
        (ONE tail bucket per prompt), replacing the power-of-two bucket
        ladder.  The contiguous tail additionally clamps to ``max_len -
        start`` so the in-place install window never overflows the cache
        (dynamic_update_slice would silently shift an overflowing write)."""
        c = self.prefill_chunk
        if size == c:
            return c
        if self.cache_layout == "paged":
            return cdiv(size, self.block_size) * self.block_size
        q = max(1, min(self.prompt_len, c))
        return max(min(cdiv(size, q) * q, self.max_len - start), size)

    def prefix_width(self, start: int) -> int:
        """Compile-time width of the prefix the chunk's attention sees:
        0 for the first chunk, else the chunk-based geometric ladder bucket
        >= start, clamped to the mirror capacity — O(log(cap / chunk))
        distinct widths, and a short prompt's chunks never attend over the
        mirror's full max_len capacity."""
        cap = self.chunk_cap
        if start == 0:
            return 0
        g = self.prefill_chunk
        while g < start:
            g *= 2
        return min(g, cap)

    def chunk_prog(self, padded: int, prefix_width: int):
        """The chunk-shaped phase program for one (padded chunk length,
        prefix width) pair."""
        key = (padded, prefix_width)
        if key in self._chunk_progs:
            return self._chunk_progs[key]
        if self.cache_layout == "paged":
            prog = self.engine.paged_prefill_chunk_program(
                padded, self.paged.max_pages, self.block_size, prefix_width)
        else:
            prog = self.engine.prefill_chunk_program(
                padded, self.slots.n_slots, self.max_len, prefix_width)
        self._chunk_progs[key] = prog
        return prog

    # ------------------------------ program registry (analysis surface) --
    #
    # The `program` analysis pass (repro.analysis.progcheck) audits the
    # traced phase programs against the roofline contract.  The methods
    # below are its interface: the statically-enumerable shape sets the
    # bucketing functions promise, and the registry with each program's
    # abstract input signature — so the auditor traces EXACTLY the
    # signatures serving dispatches, not a parallel reconstruction.

    def reachable_buckets(self) -> List[int]:
        """Every distinct prefill compile bucket reachable from a prompt of
        1..max_len tokens — the finite shape set ``bucket()`` promises.  A
        ``bucket()`` regression that leaks per-prompt shapes shows up here
        as an unbounded / misaligned set (the coverage gate's input)."""
        return sorted({self.bucket(n) for n in range(1, self.max_len + 1)})

    def reachable_chunk_shapes(self) -> List[tuple]:
        """Every (padded chunk length, prefix width) pair chunked prefill
        can request for prompts of 1..max_len tokens — pure functions of
        (n, prefill_chunk), so enumerable without running anything."""
        if self.prefill_chunk is None:
            return []
        shapes = set()
        for n in range(1, self.max_len + 1):
            start = 0
            for size in self.chunk_sizes(n):
                shapes.add((self.chunk_bucket(size, start),
                            self.prefix_width(start)))
                start += size
        return sorted(shapes)

    def build_serving_grid(self) -> None:
        """Instantiate every program the serving grid can reach — per-bucket
        prefill/swap programs, per-(chunk, prefix) chunk programs, the
        samplers — so ``program_signatures()`` covers the full surface.
        Construction is lazy-jit only: nothing traces or compiles here."""
        for b in self.reachable_buckets():
            self.progs(b)
        for padded, pw in self.reachable_chunk_shapes():
            self.chunk_prog(padded, pw)
        self.engine.sampler_program(self.slots.n_slots)
        self.engine.sampler_program(1)
        if self.spec_decode:
            self.engine.block_sampler_program(
                self.slots.n_slots, self.spec_decode + 1)

    def program_signatures(self) -> Dict[str, object]:
        """The engine's program registry with each program's
        ``abstract_inputs`` filled in (``jax.ShapeDtypeStruct`` trees) —
        the exact traced surface of the `program` analysis pass."""
        out = {}
        for key, prog in self.engine.programs.items():
            if not prog.abstract_inputs:
                sig = self.abstract_signature(key)
                if sig is not None:
                    prog.abstract_inputs = sig
            out[key] = prog
        return out

    def abstract_signature(self, key: str) -> Optional[tuple]:
        """Abstract (ShapeDtypeStruct) inputs for the program registered
        under ``key`` — the same shapes/dtypes ``EngineCore.step()``
        dispatches.  Returns None for programs this runner never
        dispatches (e.g. the disaggregated pools' split programs)."""
        import re as _re

        from repro.layers.attention import KVCache as _KVCache

        sds = jax.ShapeDtypeStruct
        i32, f32 = jnp.int32, jnp.float32
        abstract = lambda tree: jax.tree.map(  # noqa: E731
            lambda x: sds(x.shape, x.dtype), tree)
        cfg = self.cfg
        scalar = sds((), i32)

        def prefill_kv(s):  # one prompt's prefill-layout fp KV
            shape = (cfg.num_layers, 1, cfg.num_kv_heads, s, cfg.head_dim)
            return _KVCache(sds(shape, f32), sds(shape, f32))

        def vec(n, dt=i32):
            return sds((n,), dt)

        m = _re.fullmatch(r"prefill_varlen:(\d+)x(\d+)", key)
        if m:
            b, s = map(int, m.groups())
            return (self._pa, sds((b, s), i32), scalar)
        m = _re.fullmatch(r"prefill_split_varlen:(\d+)x(\d+)(:tail)?", key)
        if m:
            b, s = int(m.group(1)), int(m.group(2))
            tokens = sds((b, s), i32)
            if not m.group(3):
                return (self._pa, tokens)
            body = self.engine.programs[key[: -len(":tail")]]
            x_mid, _ = jax.eval_shape(body.fn, self._pa, tokens)
            return (self._pa, x_mid, scalar)
        m = _re.fullmatch(r"prefill_chunk:(\d+)\+(\d+)@(\d+)x(\d+)", key)
        if m:
            c = int(m.group(1))
            return (self._pa, sds((1, c), i32), abstract(self.cache),
                    abstract(self.chunk_prefix), scalar, scalar, scalar)
        m = _re.fullmatch(r"prefill_chunk_paged:(\d+)\+(\d+)@(\d+)x(\d+)", key)
        if m:
            c, bs = int(m.group(1)), int(m.group(4))
            return (self._pa, sds((1, c), i32), abstract(self.paged.kv),
                    abstract(self.chunk_prefix), vec(c // bs), scalar, scalar)
        m = _re.fullmatch(r"relayout:(\d+)x(\d+)->(\d+)", key)
        if m:
            return (prefill_kv(int(m.group(2))),)
        m = _re.fullmatch(r"page_write:(\d+)@(\d+)", key)
        if m:
            s, bs = map(int, m.groups())
            return (abstract(self.paged.kv), prefill_kv(s), vec(s // bs))
        m = _re.fullmatch(r"decode:(\d+)x(\d+)", key)
        if m:
            b = int(m.group(1))
            return (self._pa, vec(b), abstract(self.cache), vec(b))
        m = _re.fullmatch(r"decode_paged:(\d+)x(\d+)", key)
        if m:
            n, mp = map(int, m.groups())
            return (self._pa, vec(n), abstract(self.paged.kv),
                    sds((n, mp), i32), vec(n))
        m = _re.fullmatch(r"verify:(\d+)x(\d+)@(\d+)", key)
        if m:
            b, w = int(m.group(1)), int(m.group(2))
            return (self._pa, sds((b, w), i32), abstract(self.cache),
                    vec(b), vec(b))
        m = _re.fullmatch(r"verify_paged:(\d+)x(\d+)@(\d+)", key)
        if m:
            n, w, mp = map(int, m.groups())
            return (self._pa, sds((n, w), i32), abstract(self.paged.kv),
                    sds((n, mp), i32), vec(n), vec(n))
        m = _re.fullmatch(r"sampler:(\d+)", key)
        if m:
            b = int(m.group(1))
            return (sds((b, cfg.padded_vocab()), f32), vec(b), vec(b),
                    vec(b, jnp.float32), vec(b), vec(b, jnp.float32))
        m = _re.fullmatch(r"block_sampler:(\d+)x(\d+)", key)
        if m:
            b, w = map(int, m.groups())
            return (sds((b, w, cfg.padded_vocab()), f32), vec(b), vec(b),
                    vec(b, jnp.float32), vec(b), vec(b, jnp.float32))
        return None

    def run_prefill_chunk(
        self,
        req: Request,
        slot: int,
        start: int,
        size: int,
        match: Optional[PrefixMatch],
        restarted: bool,
        stats: EngineStats,
    ):
        """Run ONE chunk ``[start, start + size)`` of a request's prefill
        and install its KV (quantize-on-write) — the bounded prefill
        quantum.  Returns the chunk's last-token logits (meaningful only
        for the final chunk).  The install is fused into the chunk program,
        so there is no separate relayout swap to overlap: the fabric flips
        back to decode right after each chunk."""
        with TRACER.span("prefill.chunk", request_id=req.request_id,
                         start=start, size=size):
            padded = self.chunk_bucket(size, start)
            prog = self.chunk_prog(padded, self.prefix_width(start))
            buf = np.zeros((padded,), np.int32)
            buf[:size] = np.asarray(req.prompt[start : start + size], np.int32)
            tokens = jnp.asarray(buf[None])
            t0 = time.perf_counter()  # analysis: allow(det:wallclock) — chunk wall time feeds t_prefill/t_replay stats only
            with TRACER.span("prefill.dispatch"):
                if self.cache_layout == "paged":
                    bs = self.block_size
                    # start is page-aligned (chunk % bs == 0); prefix-cache
                    # hits and padding pages arrive as the OOB skip sentinel
                    # and are dropped
                    ids = self.paged.page_ids_for_write(
                        match, padded // bs, first_page=start // bs)
                    logits, self.paged.kv, self.chunk_prefix = prog.fn(
                        self.params, tokens, self.paged.kv, self.chunk_prefix,
                        ids, start, size - 1)
                else:
                    logits, self.cache, self.chunk_prefix = prog.fn(
                        self.params, tokens, self.cache, self.chunk_prefix, slot,
                        start, size - 1)
            t1 = timed_wait(logits, stats, "prefill.wait")
        if restarted:  # restart re-prefill is recompute overhead, not load
            stats.t_replay += t1 - t0
        else:
            stats.t_prefill += t1 - t0
        stats.prefill_chunks += 1
        return logits

    # ------------------------------------------------------------- prefill --

    def restart_headroom_ok(self, req: Request) -> bool:
        """Admit a restart only when the pool can hold its FULL replayed
        state (prompt + already-generated tokens).  Without this, two
        restarts admitted back to back each preempt the other during replay
        and the admission loop livelocks with zero decode progress.
        (Conservative: prefix hits on live pages would reduce the true
        need.)"""
        need = cdiv(len(req.prompt) + len(req.out_tokens) - 1, self.block_size)
        return self.paged.pool.num_free >= need

    def prefill(self, req: Request, slot: int, resuming: bool, stats: EngineStats):
        """Run the prefill phase for one admitted request and install its KV
        into the decode cache (the swap, latency-overlapped in pdswap mode).
        Returns the prompt's last-token logits, shape (1, V).  Raises
        ``PoolExhausted`` (after full rollback) when the paged pool cannot
        hold the prompt."""
        with TRACER.span("prefill", request_id=req.request_id,
                         tokens=len(req.prompt), resuming=resuming):
            return self._prefill(req, slot, resuming, stats)

    def _prefill(self, req: Request, slot: int, resuming: bool, stats: EngineStats):
        """``prefill`` inside its span (the disaggregated runner replaces
        this body)."""
        tokens_np = np.asarray(req.prompt, np.int32)
        n = len(tokens_np)
        bucket = self.bucket(n)
        progs = self.progs(bucket)

        match = None
        if self.cache_layout == "paged":
            match = self.paged.allocate_prompt(slot, tokens_np)  # may raise
            if not resuming:
                # engine-level counters reflect the OFFERED load; a restart's
                # self-hits on its own just-evicted pages would inflate them
                # (pool.stats keeps the raw counts)
                n_full = n // self.block_size
                stats.prefix_hits += match.cached_pages
                stats.prefix_misses += n_full - match.cached_pages
                stats.prefix_hit_tokens += match.cached_pages * self.block_size

        padded = np.zeros((bucket,), np.int32)
        padded[:n] = tokens_np
        tokens = jnp.asarray(padded[None])
        last_pos = jnp.int32(n - 1)

        def swap_write(kv):
            """Install prefilled KV into the decode cache — the swap payload
            whose dispatch the overlap hides behind the prefill tail."""
            if self.cache_layout == "paged":
                ids = self.paged.page_ids_for_write(match, bucket // self.block_size)
                self.paged.kv = progs["write"].fn(self.paged.kv, kv, ids)
                return self.paged.kv
            if self.mode == "pdswap":
                relayed = progs["relayout"].fn(kv)
            else:
                relayed = self.relay_static(kv)
            self.cache = insert_prefill_kv(self.cache, relayed, slot, n)
            return self.cache

        t0 = time.perf_counter()  # analysis: allow(det:wallclock) — prefill wall time feeds t_prefill/t_replay stats only
        if self.mode == "pdswap":
            # SwapController owns the overlap protocol (dispatch the swap
            # first, decode waits for both — paper §3.4); swap_write is this
            # request's relayout payload.
            ctl = SwapController(
                progs["body"].fn,
                lambda p, x: progs["tail"].fn(p, x, last_pos),
                swap_write,
                wait=lambda x: timed_wait(x, stats, "prefill.wait"),
            )
            logits, _, timing = ctl.prefill_and_swap(
                self.params, tokens, overlap=self.overlap
            )
            if not resuming:
                stats.record_swap(timing)
        else:
            with TRACER.span("prefill.dispatch"):
                logits, kv = progs["full"].fn(self.params, tokens, last_pos)
                swap_write(kv)
        # restarts are recompute overhead, not offered load: their prefill
        # time joins t_replay and they never re-count prefill_tokens/swaps
        t1 = time.perf_counter()  # analysis: allow(det:wallclock) — prefill wall time feeds t_prefill/t_replay stats only
        if resuming:
            stats.t_replay += t1 - t0
        else:
            stats.t_prefill += t1 - t0
            stats.prefill_tokens += n

        if self.cache_layout == "paged":
            self.paged.register_prompt_pages(match)
        return logits

    # -------------------------------------------------------------- decode --

    def decode_logits(self, lengths) -> jnp.ndarray:
        """One decode round through the phase program; updates the cache in
        place (donated) and returns the (B, V) logits."""
        if self.cache_layout == "paged":
            tables = self.paged.block_tables_array()
            logits, self.paged.kv = self.decode_prog.fn(
                self.params, self.last_tokens, self.paged.kv, tables, lengths
            )
        else:
            logits, self.cache = self.decode_prog.fn(
                self.params, self.last_tokens, self.cache, lengths
            )
        return logits

    # -------------------------------------------------- speculative decode --

    def draft_for(self, req: Request, slot: int) -> np.ndarray:
        """Clamped prompt-lookup draft for one DECODING slot (host-side).

        The proposal depth is ``spec_decode`` clamped to the slot's real
        headroom, so a verify round can never write live KV where it must
        not land:

        * budget — at most ``max_new - generated - 1`` drafts are useful
          (the round's last emitted token never becomes an input, so its
          KV is never needed — exactly the non-speculative invariant);
        * cache — live verify rows must stay ``<= max_len - 2``: row
          ``max_len - 1`` is the chunked-prefill parked-write row, whose
          whole trick is that live KV NEVER occupies it (a k-token append
          would otherwise break the invariant silently — satellite fix,
          asserted again at round build time).

        The paged trajectory bound needs no extra clamp: with the budget
        clamp the deepest verify write is position ``prompt + max_new - 2``,
        inside the pages the admission trajectory check already reserved.
        """
        s = self.slots.slots[slot]
        k = min(
            self.spec_decode,
            req.max_new - s.generated - 1,
            self.max_len - 2 - s.length,
        )
        if k <= 0:
            return np.zeros((0,), np.int32)
        from repro.serving.spec_decode import find_draft

        ctx = np.concatenate(
            [np.asarray(req.prompt, np.int32),
             np.asarray(req.out_tokens, np.int32)]) if req.out_tokens else (
            np.asarray(req.prompt, np.int32))
        return find_draft(ctx, k, self.spec_ngram)

    def run_verify(self, tokens, lengths, n_tokens) -> jnp.ndarray:
        """One speculative verify round: score every slot's (last token +
        draft) block in one forward, install the block KV in place
        (quantize-on-write; rows past ``n_tokens`` dropped).  Returns the
        (B, W, V) logits — the per-position verify targets."""
        if self.cache_layout == "paged":
            tables = self.paged.block_tables_array()
            logits, self.paged.kv = self.verify_prog.fn(
                self.params, tokens, self.paged.kv, tables, lengths, n_tokens
            )
        else:
            logits, self.cache = self.verify_prog.fn(
                self.params, tokens, self.cache, lengths, n_tokens
            )
        return logits

    def rollback_overshoot(self, slot: int, length: int) -> None:
        """Roll rejected verify rows back.  Contiguous: a no-op — rows past
        the slot length are garbage the per-slot masking never reads, and
        any position is rewritten before the length grows past it.  Paged:
        release the overshoot pages so rejections cannot leak pool
        capacity (or hold COW forks alive) across rounds."""
        if self.cache_layout == "paged":
            self.paged.truncate_slot(slot, length)

    def select_targets(self, logits, inflight: Dict[int, Request]) -> jnp.ndarray:
        """Per-position verify targets, (B, W) int32 — what sequential
        decode would have produced at each block position.  All-greedy
        batches take the direct argmax (the decode hot path); any sampling
        request routes through the vectorized block sampler, whose PRNG
        key for (slot, position i) is ``fold_in(seed, generated + i)`` —
        the sequential stream's exact keys."""
        if all(r.params.greedy for r in inflight.values()):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        step0s = np.zeros(self.slots.n_slots, np.int32)
        for s, r in inflight.items():
            step0s[s] = len(r.out_tokens)
        prog = self.engine.block_sampler_program(self.slots.n_slots, logits.shape[1])
        return prog.fn(
            logits, jnp.asarray(self._seeds), jnp.asarray(step0s),
            jnp.asarray(self._temps), jnp.asarray(self._top_ks),
            jnp.asarray(self._top_ps),
        )

    # ------------------------------------------------------------- sampler --

    def set_slot_sampling(self, slot: int, req: Request) -> None:
        p = req.params
        self._seeds[slot] = p.seed32
        self._temps[slot] = p.temperature
        self._top_ks[slot] = p.top_k
        self._top_ps[slot] = p.top_p

    def sample_batch(self, logits, inflight: Dict[int, Request]) -> jnp.ndarray:
        """Next token for every slot, (B,) int32.  All-greedy batches take
        the direct argmax path (the PR-1 hot path); any sampling request
        routes the whole batch through the vectorized sampler program
        (greedy slots still resolve to argmax inside it)."""
        if all(r.params.greedy for r in inflight.values()):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        steps = np.zeros(self.slots.n_slots, np.int32)
        for s, r in inflight.items():
            steps[s] = len(r.out_tokens)
        prog = self.engine.sampler_program(self.slots.n_slots)
        return prog.fn(
            logits, jnp.asarray(self._seeds), jnp.asarray(steps),
            jnp.asarray(self._temps), jnp.asarray(self._top_ks),
            jnp.asarray(self._top_ps),
        )

    def sample_first(self, logits, req: Request) -> int:
        """The prompt's first generated token, from the prefill logits."""
        if req.params.greedy:
            return int(jnp.argmax(logits[0]))
        p = req.params
        prog = self.engine.sampler_program(1)
        tok = prog.fn(
            logits[:1],
            jnp.asarray([p.seed32], jnp.int32),
            jnp.asarray([len(req.out_tokens)], jnp.int32),
            jnp.asarray([p.temperature], jnp.float32),
            jnp.asarray([p.top_k], jnp.int32),
            jnp.asarray([p.top_p], jnp.float32),
        )
        return int(tok[0])

    # -------------------------------------------------- paged bookkeeping --

    def append_page(self, slot: int, length: int) -> None:
        """Make position ``length`` writable, forking shared (copy-on-write)
        pages.  Raises ``PoolExhausted`` when the pool cannot grow — the
        EngineCore preemption loop handles that."""
        copy = self.paged.ensure_append_page(slot, length)
        if copy is not None:
            dst, src = copy
            # device copy of every page plane — payload AND (quantized) the
            # fp32 scale rows travel together, so the fork is exact
            self.paged.kv = jax.tree.map(
                lambda a: a.at[dst].set(a[src]), self.paged.kv
            )

    def replay(self, slot: int, req: Request, stats: EngineStats) -> bool:
        """Teacher-force the recorded tokens of a preemption restart through
        the decode program.  All other slots are masked (length 0): the paged
        scatter drops them, their pages and outputs are untouched.

        Replay never preempts — the admission headroom check reserved its
        pages; only decode-time growth (which generates NEW tokens every
        round, so it always makes progress) may evict.  Returns False if the
        pool is unexpectedly short anyway; the caller backs off.

        Replay wall time lands in ``stats.t_replay`` — blocking here keeps
        the async-dispatched replay compute from leaking into the next
        decode round's ``t_decode`` (it would skew decode_tput)."""
        with TRACER.span("replay", request_id=req.request_id,
                         tokens=max(len(req.out_tokens) - 1, 0)):
            return self._replay(slot, req, stats)

    def _replay(self, slot: int, req: Request, stats: EngineStats) -> bool:
        """``replay`` inside its span."""
        p = len(req.prompt)
        n_slots = self.slots.n_slots
        t0 = time.perf_counter()  # analysis: allow(det:wallclock) — replay wall time feeds t_replay stats only
        for j, tok in enumerate(req.out_tokens[:-1]):
            pos = p + j
            try:
                copy = self.paged.ensure_append_page(slot, pos)
            except PoolExhausted:
                return False
            assert copy is None  # replay appends past the prompt: no CoW
            tokens = np.zeros((n_slots,), np.int32)
            tokens[slot] = tok
            lengths = np.zeros((n_slots,), np.int32)
            lengths[slot] = pos
            tables = self.paged.block_tables_array()
            _, self.paged.kv = self.decode_prog.fn(
                self.params, jnp.asarray(tokens), self.paged.kv, tables,
                jnp.asarray(lengths),
            )
            stats.replayed_tokens += 1
        t1 = timed_wait(jax.tree.leaves(self.paged.kv), stats, "replay.wait")
        stats.t_replay += t1 - t0
        return True

    def release(self, slot: int) -> None:
        self.slots.release(slot)
        if self.cache_layout == "paged":
            self.paged.release_slot(slot)

    # ------------------------------------------------------------- metrics --

    def kv_bytes(self) -> dict:
        """KV memory accounting for the benchmark: bytes reserved up front vs
        the peak actually backing live tokens.  ``payload`` is the packed
        K/V bytes alone (scale planes excluded) — the term ``kv_dtype``
        shrinks 2x (int8) / 4x (int4) against the fp cache."""
        from repro.quant.kv_quant import payload_bytes, total_nbytes

        if self.cache_layout == "paged":
            return {
                "allocated": self.paged.pool_bytes(),
                "peak_in_use": self.paged.peak_live_pages * self.paged.page_bytes(),
                "page_bytes": self.paged.page_bytes(),
                "payload": self.paged.num_blocks * self.paged.page_payload_bytes(),
                "kv_dtype": self.kv_dtype,
            }
        nbytes = total_nbytes(self.cache)
        return {"allocated": nbytes, "peak_in_use": nbytes, "page_bytes": 0,
                "payload": payload_bytes(self.cache), "kv_dtype": self.kv_dtype}


class Scheduler:
    """Admission, preemption, fair queueing, and the swap decision."""

    def __init__(self, runner: ModelRunner, policy: SwapPolicy):
        self.runner = runner
        self.policy = policy
        # per-tenant weighted fair queue (deficit round robin); exact FIFO
        # with a single tenant, so the PR-2 scheduling is unchanged by
        # default — see serving.fair_queue
        self.queue = WeightedFairQueue()
        self.inflight: Dict[int, Request] = {}

    def validate(self, request: Request) -> None:
        """Admission validation, raising ``ValueError`` with the rejection
        reason.  Pure host arithmetic over engine constants — safe to call
        from the async front-end while a step runs."""
        if request.params.max_tokens is not None:
            request.max_new = request.params.max_tokens
        n = int(len(request.prompt))
        if n < 1:
            raise ValueError(f"{request.request_id}: empty prompt")
        if n + request.max_new > self.runner.max_len:
            raise ValueError(
                f"{request.request_id}: prompt ({n} tokens) + max_new "
                f"({request.max_new}) exceeds max_len={self.runner.max_len}; "
                "prompts are never truncated — raise max_len or split the request"
            )
        if self.runner.cache_layout == "paged":
            traj = cdiv(n + request.max_new - 1, self.runner.block_size)
            if traj > self.runner.paged.num_blocks:
                raise ValueError(
                    f"{request.request_id}: needs {traj} KV pages over its "
                    f"lifetime but the pool holds {self.runner.paged.num_blocks}; "
                    "raise num_blocks or lower max_new (a request that can "
                    "never fit would self-preempt forever)"
                )

    def submit(self, request: Request) -> None:
        self.validate(request)
        now = time.perf_counter()  # analysis: allow(det:wallclock) — arrival stamp meters queue wait / TTFT and feeds SLO pacing, never token values
        if request.arrival_time_s == 0.0:
            # the client-visible arrival: stamped ONCE at first submit, so
            # TTFT measured downstream includes all queueing delay (the
            # async front-end stamps even earlier, at its admission queue)
            request.arrival_time_s = now
        request.enqueue_t = now
        self.queue.append(request)
        if TRACER.enabled:
            TRACER.instant("req.submit", request_id=request.request_id,
                           tenant=request.tenant)

    def requeue_head(self, request: Request) -> None:
        self.queue.appendleft(request)

    def remove_queued(self, request_id: str) -> Optional[Request]:
        """Pull a request out of the wait queue (abort path)."""
        return self.queue.remove(request_id)

    def enter_prefill_phase(self, stats: EngineStats, *, pending_chunks: int = 0) -> bool:
        """The swap decision: flip into the prefill phase this step?  Called
        when work is queued and a slot is free, or (chunked prefill) when a
        partially-prefilled request has chunks pending — ``pending_chunks``
        carries that count into the view so a policy can reason about
        in-flight prefill work.  An empty DECODING set bypasses the policy —
        with nothing decoding the flip has no opportunity cost, and this
        guarantees progress under any policy.  (``active_slots`` counts
        decoding slots only; a mid-prefill slot is occupied but produces no
        tokens the flip could stall.)"""
        active = len(self.inflight)
        if active == 0:
            return True
        head = self.queue.peek()
        oldest = (time.perf_counter() - head.arrival_time_s  # analysis: allow(det:wallclock) — queue-age feeds the swap policy's pacing view, not any stream's token values
                  if head is not None and head.arrival_time_s else 0.0)
        view = SchedulerView(
            queue_depth=len(self.queue),
            free_slots=len(self.runner.slots.free_slots()),
            active_slots=active,
            swap_cost=stats.swap_agg.mean_cost,
            decode_round_cost=stats.decode_round_cost(),
            pending_chunks=pending_chunks,
            oldest_wait_s=oldest,
        )
        return self.policy.should_prefill(view)

    def pick_victim(self) -> Optional[int]:
        """Lowest-priority inflight slot; ties broken youngest-first."""
        if not self.inflight:
            return None
        return min(
            self.inflight,
            key=lambda s: (self.inflight[s].priority, -self.inflight[s].enqueue_t),
        )

    def preempt(self, slot: int, stats: EngineStats) -> None:
        """Evict one request: free its pages, requeue it for a deterministic
        restart (re-prefill the prompt, replay the generated tokens)."""
        req = self.inflight.pop(slot)
        req.preempted = True
        self.runner.release(slot)
        stats.preemptions += 1
        self.queue.appendleft(req)
        if TRACER.enabled:
            TRACER.instant("req.preempt", request_id=req.request_id, slot=slot)


class EngineCore:
    """The incremental serving core; one ``step()`` = one scheduling quantum."""

    # The runner to build — the one seam a subclass needs to change what is
    # compiled and device-resident while inheriting every scheduling,
    # preemption, chunking, and speculative-decode path unchanged (the
    # disaggregated engine swaps in a runner whose prefill computes on a
    # separate pool; see serving.disagg).
    runner_cls = ModelRunner

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        n_slots: int = 4,
        max_len: int = 256,
        prompt_len: int = 32,
        mode: str = "pdswap",  # "pdswap" | "static"
        cache_layout: str = "contiguous",  # "contiguous" | "paged"
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        kv_dtype: str = "fp",  # "fp" | "int8" | "int4" — quantized KV cache
        mesh=None,
        overlap: bool = True,
        swap_policy: Union[SwapPolicy, str, None] = None,
        prefill_chunk: Optional[int] = None,  # tokens per prefill quantum (None = monolithic)
        spec_decode: Optional[int] = None,  # speculative draft depth k (None/0 = off)
        spec_ngram: int = 3,  # prompt-lookup n-gram size
    ):
        self.cfg = cfg
        self.runner = self.runner_cls(
            cfg, params, n_slots=n_slots, max_len=max_len, prompt_len=prompt_len,
            mode=mode, cache_layout=cache_layout, block_size=block_size,
            num_blocks=num_blocks, kv_dtype=kv_dtype, mesh=mesh, overlap=overlap,
            prefill_chunk=prefill_chunk, spec_decode=spec_decode,
            spec_ngram=spec_ngram,
        )
        # slot -> partially-prefilled request state (chunked prefill only);
        # insertion order is admission order, so continuation is FIFO
        self._prefilling: Dict[int, PrefillProgress] = {}
        if swap_policy is None:
            swap_policy = DrainPolicy()
        elif isinstance(swap_policy, str):
            swap_policy = make_policy(swap_policy)
        self.scheduler = Scheduler(self.runner, swap_policy)
        self.stats = EngineStats(**self.runner.weight_residency)
        # latency-observing policies (SLOAwareSwapPolicy) read the engine's
        # own aggregates — bind() closes the control loop
        if hasattr(swap_policy, "bind"):
            swap_policy.bind(self.stats)
        self.out_proc = OutputProcessor(stats=self.stats)
        self.finished: Dict[str, Request] = {}
        self._gen_seq = 0

    # ------------------------------------------------------------- client --

    @property
    def mode(self) -> str:
        return self.runner.mode

    @property
    def cache_layout(self) -> str:
        return self.runner.cache_layout

    @property
    def kv_dtype(self) -> str:
        return self.runner.kv_dtype

    @property
    def prefill_chunk(self) -> Optional[int]:
        return self.runner.prefill_chunk

    @property
    def spec_decode(self) -> Optional[int]:
        return self.runner.spec_decode

    def submit(self, request: Request) -> None:
        self.scheduler.submit(request)

    def has_unfinished(self) -> bool:
        return bool(self.scheduler.queue or self.runner.slots.active_slots())

    def abort(self, request_id: str) -> Optional[RequestOutput]:
        """Cancel one request wherever it currently lives — the wait queue,
        mid-(chunked-)prefill, or decoding (plain or speculative) — and
        release everything it holds: the slot and, paged, every page its
        table references (prefix-cache pages it shares merely drop a
        refcount; pages it wrote exclusively return to the pool/evictable
        set, so pool accounting returns to its pre-request baseline).

        Returns the terminal zero-delta output (``finish_reason="abort"``)
        the stream is owed, or ``None`` when the id is unknown or already
        finished (abort after finish is a harmless no-op).  Call between
        ``step()`` calls — the async front-end serializes aborts onto the
        step loop for exactly that reason."""
        req = self.scheduler.remove_queued(request_id)
        if req is None:
            for slot, prog in list(self._prefilling.items()):
                if prog.req.request_id == request_id:
                    del self._prefilling[slot]
                    self.runner.release(slot)
                    req = prog.req
                    break
        if req is None:
            for slot, r in list(self.scheduler.inflight.items()):
                if r.request_id == request_id:
                    self.scheduler.inflight.pop(slot)
                    self.runner.release(slot)
                    req = r
                    break
        if req is None:
            return None
        self.stats.aborts += 1
        if TRACER.enabled:
            TRACER.instant("req.abort", request_id=request_id)
        out = self.out_proc.finalize_aborted(req)
        self.finished[req.request_id] = req
        return out

    def snapshot(self) -> dict:
        """The one stats block benchmarks and the /stats endpoint emit —
        built by ``obs.engine.engine_snapshot`` (the single builder every
        front-end shares): ``EngineStats.snapshot()`` plus KV accounting,
        the per-tenant fair-queue view, roofline drift, and any subclass
        sections (``snapshot_sections``)."""
        from repro.obs.engine import engine_snapshot

        return engine_snapshot(self)

    def snapshot_sections(self) -> dict:
        """Subclass hook: extra top-level sections for ``snapshot()``
        (the disagg engine adds its pool/handoff view here) — override
        THIS, not ``snapshot()``, so the block shape can't drift."""
        return {}

    def metrics_registry(self):
        """The typed metrics registry over this engine (built once; every
        metric is a live callback view, so one registry serves all
        scrapes — see ``obs.engine.engine_registry``)."""
        if getattr(self, "_metrics_registry", None) is None:
            from repro.obs.engine import engine_registry

            self._metrics_registry = engine_registry(self)
        return self._metrics_registry

    def snapshot_v2(self) -> dict:
        """Structured typed export (``{"schema": "v2", counters/gauges/
        histograms}``) of the same numbers ``/metrics`` serves."""
        from repro.obs.engine import snapshot_v2

        return snapshot_v2(self, registry=self.metrics_registry())

    def reset_stats(self) -> None:
        """Swap in a fresh ``EngineStats`` — benchmarks call this after a
        warmup pass so XLA compilation never lands in the measured
        aggregates.  Everything that holds the stats object is re-bound:
        the output processor and (when the policy observes, e.g.
        slo-aware) the swap policy, whose defer state is reset too."""
        self.stats = EngineStats(**self.runner.weight_residency)
        self.out_proc = OutputProcessor(stats=self.stats)
        policy = self.scheduler.policy
        if hasattr(policy, "bind"):
            policy.bind(self.stats)
        if hasattr(policy, "reset"):
            policy.reset()

    # --------------------------------------------------------------- step --

    def step(self) -> List[RequestOutput]:
        """Advance one scheduling quantum.

        Monolithic prefill (``prefill_chunk=None``): a policy-gated prefill
        burst (admitting queued requests into free slots, one swap each),
        then one decode round over the active slots — the PR-2 behavior,
        token-for-token.

        Chunked prefill: at most ONE chunk of pending prefill (continue the
        partially-prefilled request, or admit the queue head and run its
        first chunk), then one decode round over the DECODING slots — so a
        long prompt's prefill is spread over many quanta and active streams
        get a token between every pair of chunks instead of stalling for
        the whole burst.  Returns every streaming output the quantum
        produced."""
        t0 = time.perf_counter()  # analysis: allow(det:wallclock) — step wall time feeds t_step stats only
        with TRACER.span("engine.step"):
            outs = self._step()
        stats = self.stats
        stats.steps += 1
        stats.t_step += time.perf_counter() - t0  # analysis: allow(det:wallclock) — step wall time feeds t_step stats only
        return outs

    def _shed(self) -> List[RequestOutput]:
        """SLO admission control: a policy that knows the TTFT deadline may
        shed queue heads that can no longer meet it.  A doomed request
        counts against goodput whether it is served late or dropped — but
        serving it also queues everyone BEHIND it past their deadlines, so
        shedding converts one unavoidable miss into capacity for requests
        that can still hit their targets.  Only policies exposing
        ``should_shed`` participate; the static policies serve every
        admitted request, late or not."""
        sched = self.scheduler
        outs: List[RequestOutput] = []
        shed = getattr(sched.policy, "should_shed", None)
        if shed is None:
            return outs
        now = time.perf_counter()  # analysis: allow(det:wallclock) — shed deadline check paces admission (drop-or-serve), never token values
        while sched.queue:
            head = sched.queue[0]
            if head.out_tokens or getattr(head, "preempted", False):
                # a preempted / partially-served request is in-flight
                # state awaiting replay, not a new admission — dropping
                # it is not admission control
                break
            wait = (now - head.arrival_time_s) if head.arrival_time_s else 0.0
            if not shed(wait):
                break
            sched.queue.popleft()
            self.stats.sheds += 1
            if TRACER.enabled:
                TRACER.instant("req.shed", request_id=head.request_id,
                               wait_s=wait)
            outs.append(self.out_proc.finalize_dropped(head, "shed"))
            self.finished[head.request_id] = head
        return outs

    def _step(self) -> List[RequestOutput]:
        sched, runner = self.scheduler, self.runner
        chunked = runner.prefill_chunk is not None
        with TRACER.span("engine.schedule"):
            outs = self._shed()
            if chunked:
                prog, admitted = self._schedule_chunk()
                outs.extend(admitted)
            else:
                burst = (bool(sched.queue) and bool(runner.slots.free_slots())
                         and sched.enter_prefill_phase(self.stats))
        if chunked:
            # An SLO-aware policy can widen the EFFECTIVE prefill chunk by
            # granting several chunk quanta back to back before the decode
            # round (prefill_quanta > 1 when observed ITL has budget slack,
            # or TTFT is violating).  Greedy outputs are invariant to
            # chunking, so this steers latency only.  Default policies run
            # exactly one quantum — the PR-4 behavior.
            pq = getattr(sched.policy, "prefill_quanta", None)
            ran = 0
            while prog is not None:  # None: deferred, blocked, or no work
                outs.extend(self._advance_chunk(prog))
                ran += 1
                # re-consult AFTER each executed quantum: the policy's view
                # was refreshed by that quantum's should_prefill, so the
                # decision tracks the CURRENT decode set — deciding the
                # whole width up front from last step's state widened into
                # a set that had just started decoding (a one-step-stale
                # "empty set" stalls the new stream for the full width)
                if pq is None or ran >= max(1, int(pq())):
                    break
                with TRACER.span("engine.schedule"):
                    prog, admitted = self._schedule_chunk()
                outs.extend(admitted)
        elif burst:
            admitted = 0
            while sched.queue and runner.slots.free_slots():
                ok, out = self._admit_one(sched.queue.popleft())
                if out is not None:
                    outs.append(out)
                if not ok:
                    if not runner.slots.active_slots():
                        self._unblock_admission_or_raise()
                    break  # decode to drain capacity, then retry admission
                admitted += 1
            if admitted:
                self.stats.prefill_bursts += 1
        if sched.inflight:
            outs.extend(self._decode_round())
        if not self.has_unfinished():
            sched.policy.reset()
        return outs

    def _unblock_admission_or_raise(self) -> None:
        """The queue head failed admission with ZERO active slots — nothing
        is decoding, so no capacity will drain on its own.  Before
        declaring livelock, shed every refcount-0 prefix-cache page and
        let the next step retry: the old code raised unconditionally, an
        assertion of impossibility it never verified.  (``alloc()``
        already consumes the evictable LRU page by page, so today the
        retry mostly re-proves the failure — the eviction makes the raise
        correct by construction for ANY admission path, including future
        ones that reserve capacity via ``num_free`` checks rather than
        ``alloc()``.)"""
        runner = self.runner
        if runner.cache_layout == "paged" and runner.paged.pool.evict_all_cached():
            return
        head = self.scheduler.queue[0]
        raise RuntimeError(
            f"{head.request_id} can never be admitted: needs more "
            f"pages than the pool holds ({runner.paged.num_blocks} "
            f"blocks x {runner.block_size} tokens)"
        )

    # ----------------------------------------------------- chunked prefill --

    def _pending_chunks(self) -> int:
        return sum(p.remaining_chunks for p in self._prefilling.values())

    def _schedule_chunk(self):
        """At most one chunk of pending prefill per quantum: continue the
        oldest partially-prefilled request, or — none pending — admit the
        queue head (its first chunk runs next).  Both are policy-gated (the
        view carries the pending-chunk count), and each chunk executed is
        one fabric flip (``prefill_bursts``).  Returns ``(prefill progress
        whose next chunk runs now, or None; outputs the admission
        produced)``."""
        sched, runner = self.scheduler, self.runner
        if self._prefilling:
            if not sched.enter_prefill_phase(
                    self.stats, pending_chunks=self._pending_chunks()):
                return None, []
            return next(iter(self._prefilling.values())), []
        if not (sched.queue and runner.slots.free_slots()):
            return None, []
        if not sched.enter_prefill_phase(self.stats):
            return None, []
        ok, prog, outs = self._admit_one_chunked(sched.queue.popleft())
        if not ok and not sched.inflight:
            self._unblock_admission_or_raise()
        return prog, outs

    def _admit_one_chunked(self, req: Request):
        """Chunked admission: reserve the slot (and, paged, ALL prompt
        pages — chunk writes then land in a stable page plan).  Returns
        ``(ok, prefill progress or None, outputs)`` with the same blocked-
        admission contract as ``_admit_one``; the caller runs the first
        chunk."""
        runner, stats = self.runner, self.stats
        out = self._finish_resumed_at_budget(req)
        if out is not None:
            return True, None, [out]
        resuming = req.preempted and bool(req.out_tokens)
        restarted = req.preempted  # mid-prefill evictions restart with no tokens

        if runner.cache_layout == "paged" and resuming and not runner.restart_headroom_ok(req):
            self._block_admission(req)
            return False, None, []

        slot = runner.slots.assign(req.request_id, len(req.prompt), req.max_new)
        runner.set_slot_sampling(slot, req)
        match = None
        if runner.cache_layout == "paged":
            try:
                match = runner.paged.allocate_prompt(slot, np.asarray(req.prompt, np.int32))
            except PoolExhausted:
                self._block_admission(req, slot)
                return False, None, []
            if not restarted:
                n_full = len(req.prompt) // runner.block_size
                stats.prefix_hits += match.cached_pages
                stats.prefix_misses += n_full - match.cached_pages
                stats.prefix_hit_tokens += match.cached_pages * runner.block_size
        if not restarted:
            # Offered load is charged once, at the FIRST admission — a
            # restart (with or without recorded tokens) re-prefills as
            # recompute overhead (t_replay) and must not re-count.  One
            # logical swap per request, as in the monolithic path; the
            # install is fused into the chunk programs, so there is no
            # separate relayout latency to overlap/record (no SwapTiming).
            stats.prefill_tokens += len(req.prompt)
            stats.swaps += 1

        self._record_admission(req)
        # the shared fp prefix mirror (runner.chunk_prefix) supports exactly
        # one in-flight chunked prefill — _schedule_chunk only
        # admits when none is pending, and this guards the invariant
        assert not self._prefilling, "one chunked prefill in flight at a time"
        prog = PrefillProgress(req, slot, resuming, restarted,
                               sizes=runner.chunk_sizes(len(req.prompt)), match=match)
        self._prefilling[slot] = prog
        return True, prog, []

    def _advance_chunk(self, prog: PrefillProgress) -> List[RequestOutput]:
        """Run one chunk; on the final chunk, finish the prefill (first
        token / replay) and hand the slot to the decode set."""
        runner, stats = self.runner, self.stats
        size = prog.sizes[prog.ci]
        logits = runner.run_prefill_chunk(
            prog.req, prog.slot, prog.pos, size, prog.match, prog.restarted, stats)
        prog.ci += 1
        prog.pos += size
        stats.prefill_bursts += 1
        if prog.ci < len(prog.sizes):
            return []
        del self._prefilling[prog.slot]
        return self._finish_chunked_prefill(prog, logits)

    def _finish_chunked_prefill(self, prog: PrefillProgress, logits) -> List[RequestOutput]:
        """The post-prefill half of ``_admit_one`` for the chunked path:
        publish prefix pages, then the shared ``_finish_prefill`` handoff
        (restart replay or first-token sampling -> decode set)."""
        if self.runner.cache_layout == "paged":
            self.runner.paged.register_prompt_pages(prog.match)
        _, out = self._finish_prefill(prog.req, prog.slot, logits, prog.resuming)
        return [out] if out is not None else []

    def _preempt_prefilling(self, slot: int) -> None:
        """Evict a partially-prefilled request (decode growth exhausted the
        pool and every decoding request is already gone): requeue it for a
        deterministic chunked restart — same chunk boundaries, so the
        replayed trajectory stays bit-identical."""
        prog = self._prefilling.pop(slot)
        prog.req.preempted = True
        self.runner.release(slot)
        self.stats.preemptions += 1
        self.scheduler.queue.appendleft(prog.req)
        if TRACER.enabled:
            TRACER.instant("req.preempt", request_id=prog.req.request_id,
                           slot=slot, mid_prefill=True)

    def run(self, max_rounds: int = 10_000) -> EngineStats:
        """Compatibility loop: the PR-1 ``ServingEngine.run()`` drain-then-
        decode scheduling is ``step()`` under greedy + DrainPolicy."""
        rounds = 0
        while self.has_unfinished() and rounds < max_rounds:
            rounds += 1
            self.step()
        return self.stats

    def generate(
        self,
        prompt,
        params: Optional[SamplingParams] = None,
        *,
        request_id: Optional[str] = None,
        max_new: Optional[int] = None,
        priority: int = 0,
        max_steps: int = 10_000,
    ) -> Iterator[RequestOutput]:
        """Submit one request and stream its outputs as they are produced.

        Other queued/inflight requests keep being served by the same
        ``step()`` calls; their outputs are retained on their Request
        objects (and in ``finished``) as usual.
        """
        if params is None:
            params = SamplingParams()
        prompt = np.asarray(prompt, np.int32)
        if max_new is None:
            if params.max_tokens is not None:
                max_new = params.max_tokens  # submit() applies the override
            else:
                # default to the request's full slot headroom — the old
                # silent cap of 16 truncated any longer generation the
                # caller never asked to limit.  The paged layout further
                # clamps to what the pool can hold over the request's
                # lifetime (submit() rejects trajectories that can never
                # fit; an unbudgeted generate() should degrade, not raise)
                max_new = self.runner.max_len - len(prompt)
                if self.runner.cache_layout == "paged":
                    pool_tokens = (self.runner.paged.num_blocks
                                   * self.runner.block_size)
                    max_new = min(max_new, pool_tokens - len(prompt) + 1)
                max_new = max(1, max_new)
        self._gen_seq += 1
        rid = request_id or f"gen-{self._gen_seq}"
        req = Request(rid, prompt, max_new=max_new,
                      priority=priority, params=params)
        self.submit(req)
        for _ in range(max_steps):
            for out in self.step():
                if out.request_id == rid:
                    yield out
                    if out.finished:
                        return
        raise RuntimeError(f"{rid} did not finish within {max_steps} steps")

    # ---------------------------------------------------------- admission --

    def _finish_resumed_at_budget(self, req: Request) -> Optional[RequestOutput]:
        """A replayed request whose recorded trajectory already fills its
        ``max_new`` budget has nothing left to generate — finish it HERE,
        before admission burns a slot, a full prompt prefill and a
        teacher-forced replay just to discard the rebuilt cache state
        (the finished condition is pure host arithmetic on
        ``(len(out_tokens), max_new)``).  Returns the terminal zero-delta
        output, or None when the request really needs a slot."""
        if not (req.preempted and req.out_tokens
                and len(req.out_tokens) >= req.max_new):
            return None
        req.preempted = False
        if req.first_token_t == 0.0:
            # same safety net as the replay path: recorded tokens normally
            # carry a stamp from their original admission
            req.first_token_t = time.perf_counter()  # analysis: allow(det:wallclock) — TTFT safety-net stamp for pre-seeded resumes; stats only
        out = self.out_proc.finalize_resumed(req)
        self.finished[req.request_id] = req
        return out

    def _admit_one(self, req: Request):
        """Admit one request into a slot (the old ``_prefill_one``).
        Returns ``(ok, output)``: ``ok=False`` means admission is blocked
        (paged pool exhausted) — the request went back to the queue head and
        the engine should decode to drain capacity first."""
        runner, stats = self.runner, self.stats
        out = self._finish_resumed_at_budget(req)
        if out is not None:
            return True, out
        resuming = req.preempted and bool(req.out_tokens)

        if runner.cache_layout == "paged" and resuming and not runner.restart_headroom_ok(req):
            self._block_admission(req)
            return False, None

        slot = runner.slots.assign(req.request_id, len(req.prompt), req.max_new)
        runner.set_slot_sampling(slot, req)
        try:
            logits = runner.prefill(req, slot, resuming, stats)
        except PoolExhausted:
            self._block_admission(req, slot)
            return False, None
        self._record_admission(req)

        return self._finish_prefill(req, slot, logits, resuming)

    def _record_admission(self, req: Request) -> None:
        """Stamp arrival -> first-successful-admission queue wait, exactly
        once per request (a preemption restart keeps its original stamp —
        the client waited once, at the front of the stream)."""
        if req.queue_wait_s is None and req.arrival_time_s:
            req.queue_wait_s = time.perf_counter() - req.arrival_time_s  # analysis: allow(det:wallclock) — queue-wait metering stamp; stats only
            self.stats.queue_wait.record(req.queue_wait_s)
            self.stats.tenant_queue_wait.setdefault(
                req.tenant, LatencyStat()).record(req.queue_wait_s)
            if TRACER.enabled:
                TRACER.instant("req.admit", request_id=req.request_id,
                               queue_wait_s=req.queue_wait_s)

    def _block_admission(self, req: Request, slot: Optional[int] = None) -> None:
        """One admission attempt is blocked on pool pressure: roll the slot
        back (if one was taken), count the block, requeue at the head."""
        if slot is not None:
            self.runner.release(slot)
        self.stats.admission_blocks += 1
        self.scheduler.requeue_head(req)

    def _finish_prefill(self, req: Request, slot: int, logits, resuming: bool):
        """Post-prefill handoff shared by the monolithic and chunked paths.
        Returns ``(ok, output)``; ``ok=False`` means the restart replay lost
        a pool race — the request went back to the queue head, preempted.
        """
        runner, stats, sched = self.runner, self.stats, self.scheduler
        out = None
        if resuming:
            # Re-feed the already-generated tokens through the decode program
            # (other slots masked out): the cache comes back bit-identical to
            # its pre-eviction state, so the continuation is too.
            if not runner.replay(slot, req, stats):
                # pool raced away mid-replay: back off, stay preempted
                self._block_admission(req, slot)
                return False, None
            req.preempted = False
            if req.first_token_t == 0.0:
                # Safety net: a request can only reach here with recorded
                # tokens, which normally carry a TTFT stamp from
                # OutputProcessor at original admission — but a request
                # submitted with pre-seeded out_tokens (external replay,
                # checkpoint restore) would otherwise report TTFT 0.0.
                req.first_token_t = time.perf_counter()  # analysis: allow(det:wallclock) — TTFT safety-net stamp for pre-seeded resumes; stats only
            tok = req.out_tokens[-1]
            runner.slots.slots[slot].length = len(req.prompt) + len(req.out_tokens) - 1
            runner.slots.slots[slot].generated = len(req.out_tokens)
        else:
            req.preempted = False  # a mid-prefill eviction restarts token-free
            tok = runner.sample_first(logits, req)
            out = self.out_proc.process_token(req, tok)
            # the prefill already produced the first new token
            runner.slots.slots[slot].generated = 1

        finished = out.finished if out is not None else (
            runner.slots.slots[slot].generated >= req.max_new
        )
        if finished:
            if out is None:
                # Backstop for a replayed request finishing with nothing
                # left to emit (the common resume-exactly-at-budget case is
                # intercepted before admission by _finish_resumed_at_budget;
                # this guards any future path reaching here): the old code
                # finished it with finish_reason None and never emitted a
                # terminal delta — the stream just went dark.  Reconstruct
                # the reason from the recorded tail and emit the zero-delta
                # finished output the client is owed.
                out = self.out_proc.finalize_resumed(req)
            if req.done_t == 0.0:
                req.done_t = time.perf_counter()  # analysis: allow(det:wallclock) — completion stamp for latency stats only
            self.finished[req.request_id] = req
            runner.release(slot)
            return True, out
        runner.last_tokens = runner.last_tokens.at[slot].set(tok)
        sched.inflight[slot] = req
        return True, out

    # -------------------------------------------------- paged bookkeeping --

    def _grow_slot_page(self, slot: int, length: int) -> None:
        """Make position ``length`` writable, preempting under pool pressure."""
        while True:
            try:
                self.runner.append_page(slot, length)
                return
            except PoolExhausted:
                victim = self.scheduler.pick_victim()
                if victim is None:
                    if self._prefilling:
                        # nothing decoding left to evict, but a partially-
                        # prefilled request still holds pages — preempt the
                        # lowest-priority one (ties youngest-first)
                        pslot = min(self._prefilling, key=lambda s: (
                            self._prefilling[s].req.priority,
                            -self._prefilling[s].req.enqueue_t))
                        self._preempt_prefilling(pslot)
                        continue
                    raise RuntimeError(
                        "paged KV pool exhausted with nothing left to preempt; "
                        f"raise num_blocks (have {self.runner.paged.num_blocks})"
                    )
                self.scheduler.preempt(victim, self.stats)
                if victim == slot:
                    return  # this very slot was evicted; caller skips it

    def _ensure_append_pages(self) -> None:
        """Before a decode round, make every active slot's next position
        writable — growing tables at page boundaries and forking shared
        (copy-on-write) pages — preempting the lowest-priority request when
        the pool cannot serve the growth."""
        for slot in self.runner.slots.active_slots():
            s = self.runner.slots.slots[slot]
            if s.request_id is None:  # preempted earlier in this loop
                continue
            if slot in self._prefilling:  # mid-prefill: pages preallocated,
                continue  # and the slot sits out the decode round
            self._grow_slot_page(slot, s.length)

    # --------------------------------------------------------------- decode --

    def _decode_round(self) -> List[RequestOutput]:
        runner, stats, sched = self.runner, self.stats, self.scheduler
        if runner.spec_decode is not None:
            # host-side prompt lookup first: when at least one slot found a
            # draft the round goes through the k+1-wide verify program;
            # with NO drafts anywhere (incompressible streams, or every
            # slot still too young for its n-gram to repeat) the round
            # falls back to the plain single-token decode program — the
            # verify pass would do k+1x the work to emit the same one
            # token per slot
            drafts = {slot: runner.draft_for(sched.inflight[slot], slot)
                      for slot in sorted(sched.inflight)}
            if any(len(d) for d in drafts.values()):
                with TRACER.span("decode.verify", batch=len(sched.inflight)):
                    return self._verify_round(drafts)
        with TRACER.span("decode.round", batch=len(sched.inflight)):
            with TRACER.span("decode.prepare"):
                active, lengths = self._decode_inputs()
            if not active:
                return []
            t0 = time.perf_counter()  # analysis: allow(det:wallclock) — decode-round wall time feeds t_decode stats only
            with TRACER.span("decode.dispatch"):
                logits = runner.decode_logits(lengths)
                next_tokens = runner.sample_batch(logits, sched.inflight)
            t1 = timed_wait(next_tokens, stats, "decode.wait")
            stats.t_decode += t1 - t0
            stats.decode_rounds += 1
            stats.decode_tokens += len(active)

            stats.slot_rounds += len(active)
            stats.decode_ctx_tokens += int(
                sum(runner.slots.slots[i].length for i in active))
            with TRACER.span("decode.outputs"):
                return self._decode_outputs(active, next_tokens)

    def _decode_inputs(self):
        """Page growth and the lengths operand of a plain decode round:
        ``(active slots, lengths)``."""
        runner = self.runner
        if runner.cache_layout == "paged":
            self._ensure_append_pages()
        active = sorted(self.scheduler.inflight)
        if not active:
            return active, None
        if self._prefilling:
            # Mid-prefill slots sit the round out, but the batched decode
            # program still computes (and scatters) a row for them — park
            # that garbage write where it can never be read.  Paged: length
            # 0 routes the scatter to an out-of-bounds page id (dropped).
            # Contiguous: length >= max_len clamps the write to the cache's
            # last row, which live data never occupies (the last generated
            # token's KV lands at position n + max_new - 2 <= max_len - 2).
            lengths_np = np.asarray([s.length for s in runner.slots.slots], np.int32)
            park = 0 if runner.cache_layout == "paged" else runner.max_len
            for slot in self._prefilling:
                lengths_np[slot] = park
            return active, jnp.asarray(lengths_np)
        return active, runner.slots.lengths_array()

    def _decode_outputs(self, active: List[int], next_tokens) -> List[RequestOutput]:
        """Read a plain round's tokens back and stream them: per-slot output
        handling, slot advance, and release of finished requests."""
        runner, sched = self.runner, self.scheduler
        next_np = np.asarray(next_tokens)
        outs: List[RequestOutput] = []
        for i in active:
            req = sched.inflight[i]
            out = self.out_proc.process_token(req, int(next_np[i]))
            s = runner.slots.slots[i]
            s.length += 1
            s.generated += 1
            if out.finished:
                sched.inflight.pop(i)
                self.finished[req.request_id] = req
                runner.release(i)
            outs.append(out)
        runner.last_tokens = next_tokens
        return outs

    # -------------------------------------------------- speculative decode --

    def _grow_slot_span(self, slot: int, start: int, count: int) -> None:
        """Make positions ``[start, start + count)`` writable for one slot
        before a verify round — page growth + copy-on-write forks, with the
        same preempt-under-pressure loop the single-token path uses.  Stops
        early if the slot itself becomes the eviction victim."""
        for pos in range(start, start + count):
            self._grow_slot_page(slot, pos)
            if self.runner.slots.slots[slot].request_id is None:
                return  # this very slot was evicted mid-growth

    def _verify_round(self, drafts: Dict[int, np.ndarray]) -> List[RequestOutput]:
        """One decode quantum under speculative decoding: draft (host-side
        prompt lookup — ``drafts`` arrives from ``_decode_round``, which
        already fell back to plain decode when every slot came up empty),
        verify (one batched k+1-position forward), accept (longest
        confirmed draft prefix + one correction token), roll back
        (truncate slot length / release overshoot pages).

        Every emitted token is the token sequential decode would have
        produced at that position — greedy targets are the verify logits'
        argmax, sampled targets reuse the sequential PRNG key stream — so
        with greedy sampling the stream is bit-identical to the
        non-speculative engine for every layout x kv_dtype (pinned by
        tests/test_spec_decode.py), and preemption replay (which
        teacher-forces the recorded tokens) needs no speculation-specific
        state at all.
        """
        runner, stats, sched = self.runner, self.stats, self.scheduler
        n_slots = runner.slots.n_slots
        w = runner.spec_decode + 1
        with TRACER.span("decode.prepare"):
            # paged: make each slot's verify span writable (growth + COW;
            # may preempt victims — including, under pressure, a drafted slot)
            if runner.cache_layout == "paged":
                for slot in list(drafts):
                    if slot not in sched.inflight:
                        continue  # evicted by an earlier slot's growth
                    s = runner.slots.slots[slot]
                    if s.request_id is None:
                        continue
                    self._grow_slot_span(slot, s.length, len(drafts[slot]) + 1)
            active = sorted(sched.inflight)
            if not active:
                return []
            last_np = np.array(runner.last_tokens)  # writable copy (np.asarray of
            # a device array is a read-only view)
            tokens_np = np.zeros((n_slots, w), np.int32)
            n_tok_np = np.zeros((n_slots,), np.int32)
            lengths_np = np.asarray(
                [s.length for s in runner.slots.slots], np.int32)
            for slot in active:
                d = drafts[slot]
                tokens_np[slot, 0] = last_np[slot]
                tokens_np[slot, 1 : 1 + len(d)] = d
                n_tok_np[slot] = 1 + len(d)
                # satellite invariant: live verify rows stay clear of the
                # chunked-prefill parked-write row max_len - 1 (draft_for
                # clamps; this guards any future clamp regression)
                assert lengths_np[slot] + n_tok_np[slot] - 1 <= runner.max_len - 2, (
                    slot, int(lengths_np[slot]), int(n_tok_np[slot]), runner.max_len)
        # mid-prefill slots sit the round out: n_tokens 0 routes every one
        # of their rows (KV writes) out of bounds, and nothing reads their
        # logits — no parked-write trick needed on this path
        t0 = time.perf_counter()  # analysis: allow(det:wallclock) — verify-round wall time feeds t_decode stats only
        with TRACER.span("decode.dispatch"):
            logits = runner.run_verify(
                jnp.asarray(tokens_np), jnp.asarray(lengths_np), jnp.asarray(n_tok_np))
            targets = runner.select_targets(logits, sched.inflight)
        t1 = timed_wait(targets, stats, "decode.wait")
        stats.t_decode += t1 - t0
        stats.decode_rounds += 1
        stats.verify_rounds += 1
        stats.slot_rounds += len(active)
        stats.decode_ctx_tokens += int(sum(lengths_np[i] for i in active))
        with TRACER.span("decode.outputs"):
            return self._verify_outputs(active, drafts, targets, last_np)

    def _verify_outputs(self, active: List[int], drafts, targets,
                        last_np: np.ndarray) -> List[RequestOutput]:
        """Accept, stream and roll back one verify round's slots."""
        from repro.core.sampling import accept_length

        runner, stats, sched = self.runner, self.stats, self.scheduler
        targets_np = np.asarray(targets)
        outs: List[RequestOutput] = []
        for slot in active:
            req = sched.inflight[slot]
            d = drafts[slot]
            a = accept_length(d, targets_np[slot, : len(d)])
            stats.draft_tokens += len(d)
            stats.accepted_tokens += a
            # emit the confirmed prefix plus the correction/bonus token;
            # the output processor owns stop/budget truncation, so the
            # ACTUAL delta (and the state advance below) may be shorter
            emitted = [int(t) for t in targets_np[slot, : a + 1]]
            out = self.out_proc.process_tokens(req, emitted)
            e = len(out.new_token_ids)
            s = runner.slots.slots[slot]
            s.length += e
            s.generated += e
            stats.decode_tokens += e
            last_np[slot] = out.new_token_ids[-1]
            if out.finished:
                sched.inflight.pop(slot)
                self.finished[req.request_id] = req
                runner.release(slot)
            else:
                # roll rejected/truncated rows back: overshoot pages go
                # home, so a failed speculation never leaks pool capacity
                runner.rollback_overshoot(slot, s.length)
            outs.append(out)
        runner.last_tokens = jnp.asarray(last_np)
        return outs

    # -------------------------------------------------------------- metrics --

    def kv_bytes(self) -> dict:
        return self.runner.kv_bytes()
