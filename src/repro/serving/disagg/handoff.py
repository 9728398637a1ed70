"""The KV handoff channel: prefill-pool KV into the decode pool's sharding.

This is the disaggregated analogue of the paper's bitstream load: where the
temporal engine pays a relayout "swap" to flip one fabric between phases, the
two-pool engine pays a cross-pool KV transfer.  ``ship()`` moves one finished
KV segment — a monolithic prompt's relayed (possibly quantized
payload+scales) pytree, or one chunk's fp KV — onto the decode mesh via
``core.disagg.kv_transfer_program`` (a ``device_put`` resharding; on real
hardware XLA emits the DCN collective, on forced host meshes a host copy).
Dispatch is asynchronous, so chunks shipped EAGERLY as prefill progresses
overlap their transfer with the remaining prefill compute — the same
"reconfiguration latency hidden by computation" trick as the temporal swap.

The channel also owns the decode-side install queue.  Installing a segment
means scattering it into the decode pool's cache, and because an XLA cache
buffer is one value, any install makes the NEXT decode round's execution
depend on that segment's whole producer chain (prefill compute + transfer).
Deferring installs until the request actually joins the decode set keeps
in-between decode rounds free of cross-pool dependencies — the interference
elimination the disagg benchmark measures — while leaving the installed
bytes (and therefore the emitted tokens) exactly what the colocated engine's
fused install order produces: a request's pages/rows are exclusively its own
until its first token is sampled, so its installs commute with other slots'
decode writes.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.disagg import kv_transfer_program
from repro.obs.trace import TRACER

# Trace lane for cross-pool transfers: ship dispatches happen on whichever
# thread runs the prefill (the pool's dispatch thread for chunks, the
# engine thread for monolithic swaps), but they are one logical resource —
# pinning the lane renders every transfer on a single track, visually
# interleaved with the engine-step and prefill-pool thread lanes.
TRACE_LANE = "kv-handoff"


class KVHandoffChannel:
    """Cross-pool KV transfer + deferred decode-side installs.

    Threading: ``ship()`` runs on BOTH the engine-step thread (monolithic
    swaps) and the prefill pool's dispatch thread (eager chunks), so its
    metering counters are lock-protected.  The install queue is engine-step
    state only — ``defer_install``/``drain``/``discard`` all run between
    quanta on the engine thread — and is annotated (and statically checked,
    see ``repro.analysis``) as such.
    """

    def __init__(self, decode_mesh: Optional[Mesh] = None,
                 spec: Optional[P] = None):
        self.decode_mesh = decode_mesh
        # default: replicate over the decode pool (rank-agnostic, correct
        # for every payload/scale rank); callers with a wide decode mesh
        # can pin a sharded spec instead
        self.spec = P() if spec is None else spec
        self._transfer = (kv_transfer_program(decode_mesh, self.spec)
                          if decode_mesh is not None else None)
        # (slot, install thunk) queue — install order is ship order, and a
        # preempted/aborted slot's segments are discarded before its pages
        # can be reused (DisaggRunner.release)
        self._pending: List[Tuple[int, Callable[[], None]]] = []  # owned-by: engine-step
        # ship() metering: incremented from the engine thread (monolithic
        # swaps) AND the prefill-pool dispatch thread (eager chunks) — the
        # unsynchronized += these started as dropped increments under load
        self._lock = threading.Lock()
        self.segments = 0  # guarded-by: self._lock
        self.eager_segments = 0  # guarded-by: self._lock
        self.bytes_shipped = 0  # guarded-by: self._lock
        self.installs = 0  # guarded-by: self._lock
        self.discarded = 0  # guarded-by: self._lock
        self.t_dispatch = 0.0  # guarded-by: self._lock

    # ------------------------------------------------------------ transfer --

    def ship(self, kv, *, eager: bool = False):
        """Move one KV segment onto the decode mesh (no mesh: same-device
        passthrough, still metered).  Returns the decode-resident pytree;
        the dispatch is async, so an ``eager`` mid-prefill chunk's transfer
        overlaps the chunks still computing on the prefill pool."""
        nbytes = sum(x.nbytes for x in jax.tree.leaves(kv))
        with TRACER.span("handoff.ship", lane=TRACE_LANE, bytes=nbytes, eager=eager):
            t0 = time.perf_counter()
            if self._transfer is not None:
                kv = self._transfer(kv)
            t1 = time.perf_counter()
        with self._lock:
            self.t_dispatch += t1 - t0
            self.segments += 1
            if eager:
                self.eager_segments += 1
            self.bytes_shipped += nbytes
        return kv

    def ship_aux(self, tree):
        """Move a small non-KV pytree (the prompt's first-token logits)
        across the boundary without counting it as a KV segment."""
        if self._transfer is not None:
            tree = self._transfer(tree)
        return tree

    # ------------------------------------------------------------ installs --

    def defer_install(self, slot: int, install: Callable[[], None]) -> None:  # thread: engine-step
        """Queue one shipped segment's decode-side install (a cache-scatter
        thunk reading the runner's CURRENT cache when run)."""
        self._pending.append((slot, install))

    def drain(self, slot: Optional[int] = None) -> int:  # thread: engine-step
        """Run queued installs (one slot's, or all) in ship order — called
        when a request's prefill completes, before its first token is
        sampled.  Returns the number installed."""
        if slot is None:
            run, self._pending = self._pending, []
        else:
            run = [(s, f) for s, f in self._pending if s == slot]
            self._pending = [(s, f) for s, f in self._pending if s != slot]
        # installs record on the CALLER's lane (the engine thread), not the
        # transfer lane: an install blocks on its segment's future, so it
        # can overlap a still-dispatching ship — same-lane events must nest
        with TRACER.span("handoff.install", slot=slot, segments=len(run)):
            for _, install in run:
                install()
        with self._lock:
            self.installs += len(run)
        return len(run)

    def discard(self, slot: int) -> int:  # thread: engine-step
        """Drop a slot's queued installs (preemption/abort: its pages are
        about to be released and may be reallocated — a late install would
        corrupt the new owner)."""
        keep = [(s, f) for s, f in self._pending if s != slot]
        n = len(self._pending) - len(keep)
        self._pending = keep
        with self._lock:
            self.discarded += n
        return n

    @property
    def pending(self) -> int:  # thread: engine-step
        return len(self._pending)

    # ------------------------------------------------------------- metrics --

    def snapshot(self) -> dict:  # thread: engine-step
        with self._lock:
            return {
                "segments": self.segments,
                "eager_segments": self.eager_segments,
                "bytes_shipped": self.bytes_shipped,
                "installs": self.installs,
                "discarded": self.discarded,
                "pending": self.pending,
                "t_dispatch_s": self.t_dispatch,
            }
