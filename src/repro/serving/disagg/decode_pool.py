"""The decode pool: a ``ModelRunner`` whose prefill computes elsewhere.

``DisaggRunner`` inherits every decode-phase responsibility unchanged — the
decode/verify programs, the paged pool or contiguous cache, slot and
sampling state, preemption replay — all resident on the DECODE mesh (the
``mesh`` the base constructor received).  What it overrides is exactly the
prefill seam:

* ``_prefill``: the monolithic body/tail/full programs run on the attached
  ``PrefillPool``; the swap payload (contiguous: the relayouted — possibly
  quantized payload+scales — decode-layout tree, built prefill-side; paged:
  the raw fp prefill-layout KV) crosses the ``KVHandoffChannel`` inside
  ``swap_write``, whose dispatch the SwapController still hides behind the
  prefill tail, and is installed by the SAME jitted install programs the
  colocated engine uses (``insert_prefill_kv`` / ``page_write_program``).

* ``run_prefill_chunk``: chunks compute on the pool via the compute-only
  ``prefill_chunk_kv_program`` and SHIP EAGERLY — each chunk's transfer
  dispatches as it completes, overlapping the remaining chunks' compute —
  while the decode-side installs (``page_write_program`` /
  ``chunk_write_program``, the fused programs' exact scatters) are DEFERRED
  on the channel until the final chunk, so decode rounds in between never
  acquire a data dependency on the in-flight prefill.  Non-final chunks
  also skip the host sync the colocated runner pays for timing: blocking
  would serialize the engine's single step loop against prefill-pool work
  and forfeit the overlap (so disagg ``t_prefill`` records dispatch time
  plus the final chunk's sync, and the true prefill wall time runs
  concurrently on the other pool).

Because every install runs the colocated engine's own quantize-on-write
programs on the same fp values, and installs land before the request's
first token is sampled, greedy outputs are bit-identical to the
single-engine ``EngineCore`` across layouts x kv dtypes, chunked included.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kv_cache import insert_prefill_kv
from repro.core.swap import SwapController
from repro.obs.trace import TRACER
from repro.serving.core import EngineStats, ModelRunner, Request, timed_wait
from repro.serving.disagg.handoff import KVHandoffChannel
from repro.serving.disagg.prefill_pool import PrefillPool
from repro.serving.paging import PrefixMatch

# Cross-object lock discipline (checked by repro.analysis): accesses
# through a local named `pool` are held to PrefillPool's annotations — in
# particular chunk_prefix, which only the pool's dispatch thread may touch.
# analysis: bind(pool=PrefillPool)


class DisaggRunner(ModelRunner):
    """ModelRunner with prefill outsourced to an attached PrefillPool."""

    prefill_pool: Optional[PrefillPool] = None
    handoff: Optional[KVHandoffChannel] = None

    def attach(self, prefill_pool: PrefillPool, handoff: KVHandoffChannel) -> None:
        """Wire the pools together (DisaggEngine calls this right after
        construction, before any request can prefill)."""
        assert prefill_pool.mode == self.mode
        assert prefill_pool.cache_layout == self.cache_layout
        assert prefill_pool.kv_dtype == self.kv_dtype
        assert prefill_pool.prefill_chunk == self.prefill_chunk
        self.prefill_pool = prefill_pool
        self.handoff = handoff
        # the fp chunk-prefix mirror lives on the prefill pool; drop the
        # decode-side buffer the base constructor allocated (prefix_width
        # reads chunk_cap, not the buffer)
        self.chunk_prefix = None

    # ------------------------------------------------------------- prefill --

    def _prefill(self, req: Request, slot: int, resuming: bool, stats: EngineStats):
        """Monolithic prefill on the prefill pool + handoff + decode-side
        install — the two-pool mirror of ``ModelRunner._prefill`` (same
        allocation order, same install programs, same stats accounting)."""
        pool, handoff = self.prefill_pool, self.handoff
        tokens_np = np.asarray(req.prompt, np.int32)
        n = len(tokens_np)
        bucket = self.bucket(n)
        pprogs = pool.progs(bucket)

        match = None
        if self.cache_layout == "paged":
            match = self.paged.allocate_prompt(slot, tokens_np)  # may raise
            if not resuming:
                n_full = n // self.block_size
                stats.prefix_hits += match.cached_pages
                stats.prefix_misses += n_full - match.cached_pages
                stats.prefix_hit_tokens += match.cached_pages * self.block_size

        padded = np.zeros((bucket,), np.int32)
        padded[:n] = tokens_np
        tokens = jnp.asarray(padded[None])
        last_pos = jnp.int32(n - 1)

        def swap_write(kv):
            """The swap payload crosses the pool boundary here.  Dispatched
            before the prefill tail (SwapController), so transfer + install
            hide behind the tail's compute exactly like the colocated
            relayout does."""
            if self.cache_layout == "paged":
                kv = handoff.ship(kv)  # fp prefill-layout pages; the decode-
                # side page_write quantizes on write, as colocated
                ids = self.paged.page_ids_for_write(match, bucket // self.block_size)
                self.paged.kv = self.engine.page_write_program(
                    bucket, self.block_size).fn(self.paged.kv, kv, ids)
                return self.paged.kv
            if self.mode == "pdswap":
                relayed = pprogs["relayout"].fn(kv)
            else:
                relayed = pool.relay_static(kv)
            # decode-layout (quantized payload+scales when kv_dtype != fp)
            relayed = handoff.ship(relayed)
            self.cache = insert_prefill_kv(self.cache, relayed, slot, n)
            return self.cache

        t0 = time.perf_counter()
        if self.mode == "pdswap":
            ctl = SwapController(
                pprogs["body"].fn,
                lambda p, x: pprogs["tail"].fn(p, x, last_pos),
                swap_write,
                wait=lambda x: timed_wait(x, stats, "prefill.wait"),
            )
            logits, _, timing = ctl.prefill_and_swap(
                pool.params, tokens, overlap=self.overlap
            )
            if not resuming:
                stats.record_swap(timing)
        else:
            with TRACER.span("prefill.dispatch"):
                logits, kv = pprogs["full"].fn(pool.params, tokens, last_pos)
                swap_write(kv)
        # first-token logits cross to the decode pool too: the sampler (and
        # any program mixing them with decode-resident operands) must never
        # see prefill-mesh arrays
        logits = handoff.ship_aux(logits)
        t1 = time.perf_counter()
        if resuming:
            stats.t_replay += t1 - t0
        else:
            stats.t_prefill += t1 - t0
            stats.prefill_tokens += n

        if self.cache_layout == "paged":
            self.paged.register_prompt_pages(match)
        return logits

    # ------------------------------------------------------ chunked prefill --

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
        """One chunk computed on the prefill pool, shipped eagerly, install
        deferred (see the module docstring for why deferral is what
        actually eliminates cross-phase interference).  Its span is the
        ENGINE-side window (dispatch + final-chunk drain/sync), distinct
        from the pool thread's ``prefill.chunk.compute``."""
        with TRACER.span("prefill.chunk.dispatch", request_id=req.request_id,
                         start=start, size=size,
                         final=start + size == len(req.prompt)):
            return self._run_prefill_chunk(req, slot, start, size, match,
                                           restarted, stats)

    def _run_prefill_chunk(self, req, slot, start, size, match, restarted, stats):
        pool, handoff = self.prefill_pool, self.handoff
        padded = self.chunk_bucket(size, start)
        prog = pool.chunk_kv_prog(padded, self.prefix_width(start))
        buf = np.zeros((padded,), np.int32)
        buf[:size] = np.asarray(req.prompt[start : start + size], np.int32)
        final = start + size == len(req.prompt)
        t0 = time.perf_counter()

        def compute(buf=buf, prog=prog, start=start, size=size,
                    rid=req.request_id):  # thread: prefill-pool
            """Runs on the pool's dispatch thread (see PrefillPool.submit):
            the engine thread never dispatches chunk work itself — not even
            the token upload — so its next decode dispatch is not queued
            behind any piece of the chunk."""
            # recorded from the pool thread: this is the lane whose overlap
            # with decode quanta the trace is meant to show
            with TRACER.span("prefill.chunk.compute", request_id=rid,
                             start=start, size=size):
                tokens = jnp.asarray(buf[None])
                logits, chunk_kv, pool.chunk_prefix = prog.fn(
                    pool.params, tokens, pool.chunk_prefix, start, size - 1)
                shipped = handoff.ship(chunk_kv, eager=not final)
            return logits, shipped

        fut = pool.submit(compute)
        if self.cache_layout == "paged":
            bs = self.block_size
            ids = self.paged.page_ids_for_write(
                match, padded // bs, first_page=start // bs)
            wprog = self.engine.page_write_program(padded, bs)

            def install(fut=fut, ids=ids, wprog=wprog):
                self.paged.kv = wprog.fn(self.paged.kv, fut.result()[1], ids)
        else:
            wprog = self.engine.chunk_write_program(padded)

            def install(fut=fut, slot=slot, start=start, wprog=wprog):
                self.cache = wprog.fn(self.cache, fut.result()[1], slot, start)

        handoff.defer_install(slot, install)
        logits = None
        if final:
            # the request is about to join the decode set: land every
            # queued segment (ship order), then sync the logits the first
            # token is sampled from
            handoff.drain(slot)
            logits = handoff.ship_aux(fut.result()[0])
            timed_wait(logits, stats, "prefill.wait")
        t1 = time.perf_counter()
        if restarted:  # restart re-prefill is recompute overhead, not load
            stats.t_replay += t1 - t0
        else:
            stats.t_prefill += t1 - t0
        stats.prefill_chunks += 1
        return logits

    # ------------------------------------------------------------- release --

    def release(self, slot: int) -> None:
        """Slot release (finish / preempt / abort): discard the slot's
        queued installs FIRST — its pages are about to return to the pool,
        and a late install would scribble on their next owner."""
        if self.handoff is not None:
            self.handoff.discard(slot)
        super().release(slot)
