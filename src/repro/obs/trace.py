"""Process-wide tracer: bounded ring-buffer spans + Chrome trace export.

The serving stack is instrumented at every phase boundary the paper's
timeline argument cares about — request lifecycle events (submit → admit →
prefill chunk[i] → KV handoff → decode round → spec verify → preempt /
replay → shed / abort / finish) and engine spans (engine step, scheduling,
prefill and chunk dispatch and wait, swap, the decode round's prepare /
dispatch / wait / outputs phases).  Instrumentation sites call the module
singleton ``TRACER``; when tracing is disabled ``span()`` is one attribute
check that returns a shared no-op context (no annotation is built), and
``instant()`` / ``finish()`` return at the same check — CI-gated < 3 % on
the decode loop by ``benchmarks/tracing_overhead.py``.

While enabled, every live span (``span()``) also enters a
``jax.profiler.TraceAnnotation`` of the same name with the span's
arguments, so a profiler trace taken meanwhile holds the engine's spans on
its host plane, on the device trace's clock: operators capture one with
``TRACER.enable()`` inside ``jax.profiler.trace(...)``.  ``complete()``
records a span timed elsewhere; it reaches the ring buffer only.

Events land in a ``deque(maxlen=capacity)`` — a long serving run can trace
forever and keep only the most recent window; ``dropped`` counts evictions.
``export_chrome_trace()`` emits the Chrome trace-event JSON format
(chrome://tracing / Perfetto): complete events (``ph: "X"``) and instants
(``ph: "i"``), one lane (``tid``) per *origin* — by default the emitting
thread's name, so the engine step loop, the ``prefill-pool`` dispatch
thread, and explicit lanes like ``kv-handoff`` render as separate tracks
whose overlap is the paper's Fig. 5 as a real trace.

Exactly-once finish: ``finish()`` is the single funnel for terminal
lifecycle events.  While tracing is enabled it asserts no request finishes
twice — the double-stamp class of bug (``done_t`` restamped on a second
finish path) becomes a hard error instead of silently skewed latency.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation


class _NullSpan:
    """No-op context manager returned by ``span()`` when tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """A live span: the ring-buffer record and a profiler annotation of the
    same name and arguments, entered and left together."""

    __slots__ = ("_tr", "_name", "_lane", "_args", "_t0", "_ann")

    def __init__(self, tr: "Tracer", name: str, lane: Optional[str], args):
        self._tr = tr
        self._name = name
        self._lane = lane
        self._args = args

    def __enter__(self):
        self._ann = TraceAnnotation(self._name, **self._args)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self._tr.complete(self._name, self._t0, t1, lane=self._lane, **self._args)
        return False


class Tracer:
    """Bounded-ring-buffer event recorder with Chrome trace export.

    Storage is a tuple per event — ``("X", name, t0, dur, lane, args)`` for
    spans, ``("i", name, t, lane, args)`` for instants — appended to a
    ``deque(maxlen=...)``; ``deque.append`` is atomic under the GIL, so the
    engine thread, the prefill-pool thread, and benchmark drivers record
    concurrently without a lock on the hot path.
    """

    DEFAULT_CAPACITY = 65536

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.enabled = False
        self._lock = threading.Lock()
        self._configure(capacity)

    # analysis: allow(lock:unguarded) — callers hold self._lock (enable/clear);
    # __init__ calls it on a not-yet-shared object
    def _configure(self, capacity: int) -> None:
        if capacity < 1:
            raise ValueError("tracer capacity must be >= 1")
        self.capacity = capacity  # guarded-by: self._lock
        self._events: deque = deque(maxlen=capacity)  # guarded-by: self._lock
        self._emitted = 0  # guarded-by: self._lock
        self._finished: set = set()  # guarded-by: self._lock
        self._t0 = time.perf_counter()  # guarded-by: self._lock

    # ------------------------------------------------------------ control --

    def enable(self, capacity: Optional[int] = None) -> None:
        """Start recording (fresh buffer).  ``capacity`` bounds the ring."""
        with self._lock:
            self._configure(capacity or self.capacity)
            self.enabled = True

    def disable(self) -> None:
        """Stop recording; the buffered events stay exportable."""
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._configure(self.capacity)

    @property
    # analysis: allow(lock:unguarded) — monitoring read; a torn
    # emitted/len pair can misreport dropped by one scrape, never corrupt
    def dropped(self) -> int:
        """Events evicted by the ring bound (emitted minus retained)."""
        return self._emitted - len(self._events)

    # analysis: allow(lock:unguarded) — list(deque) snapshots atomically
    # under the GIL; used by tests/benchmarks, not the export path
    def events(self) -> List[tuple]:
        return list(self._events)

    # ---------------------------------------------------------- recording --

    # analysis: allow(lock:unguarded) — lock-free hot path by design (class
    # docstring): deque.append and int += are GIL-atomic enough for metering,
    # and a lock here would serialize the engine and pool threads per event
    def complete(self, name: str, t0: float, t1: float,
                 lane: Optional[str] = None, **args) -> None:
        """Record a complete span from ``perf_counter`` stamps the caller
        already took.  It reaches the ring buffer only: a profiler
        annotation cannot be entered after the fact, so sites that belong
        on the profiler's clock use ``span()``."""
        if not self.enabled:
            return
        self._emitted += 1
        self._events.append(
            ("X", name, t0, max(t1 - t0, 0.0),
             lane or threading.current_thread().name, args or None))

    def span(self, name: str, lane: Optional[str] = None, **args):
        """Context-manager span, recorded in the ring buffer and entered as
        a ``jax.profiler.TraceAnnotation``.  Disabled: one attribute check
        and a shared no-op context."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, lane, args)

    # analysis: allow(lock:unguarded) — lock-free hot path, same contract
    # as complete()
    def instant(self, name: str, lane: Optional[str] = None, **args) -> None:
        if not self.enabled:
            return
        self._emitted += 1
        self._events.append(
            ("i", name, time.perf_counter(),
             lane or threading.current_thread().name, args or None))

    # analysis: allow(lock:unguarded) — _finished is only touched by finish
    # paths, which all run on the engine-step thread (the funnel property
    # this method asserts); set.add is GIL-atomic besides
    def finish(self, request_id: str, reason: Optional[str]) -> None:
        """Terminal lifecycle event — must fire exactly once per request.

        All finish paths (stop/length via ``process_tokens``, resume-at-
        budget, shed, abort) funnel through here; a second finish for the
        same id while tracing is a hard error, catching double-finalize
        bugs that would otherwise only skew ``done_t`` silently."""
        if not self.enabled:
            return
        if request_id in self._finished:
            raise RuntimeError(
                f"duplicate finish event for request {request_id!r} "
                f"(reason={reason!r}): a request must finish exactly once")
        self._finished.add(request_id)
        self.instant("req.finish", request_id=request_id, reason=reason)

    # ------------------------------------------------------------- export --

    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event JSON dict (``chrome://tracing`` / Perfetto).

        One ``tid`` per lane in first-seen order, named via ``"M"``
        thread_name metadata; timestamps are microseconds relative to the
        last ``enable()``/``clear()``.
        """
        with self._lock:
            events = list(self._events)
            t0 = self._t0
        lanes: Dict[str, int] = {}

        def tid(lane: str) -> int:
            if lane not in lanes:
                lanes[lane] = len(lanes) + 1
            return lanes[lane]

        out: List[Dict[str, Any]] = []
        for ev in events:
            if ev[0] == "X":
                _, name, ts, dur, lane, args = ev
                rec: Dict[str, Any] = {
                    "name": name, "ph": "X", "pid": 1, "tid": tid(lane),
                    "ts": (ts - t0) * 1e6, "dur": dur * 1e6,
                }
            else:
                _, name, ts, lane, args = ev
                rec = {
                    "name": name, "ph": "i", "s": "t", "pid": 1,
                    "tid": tid(lane), "ts": (ts - t0) * 1e6,
                }
            if args:
                rec["args"] = dict(args)
            out.append(rec)
        meta: List[Dict[str, Any]] = []
        for lane, lane_tid in lanes.items():
            meta.append({"name": "thread_name", "ph": "M", "pid": 1,
                         "tid": lane_tid, "args": {"name": lane}})
            meta.append({"name": "thread_sort_index", "ph": "M", "pid": 1,
                         "tid": lane_tid, "args": {"sort_index": lane_tid}})
        return {"traceEvents": meta + out, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> Dict[str, Any]:
        trace = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(trace, f)
        return trace


# The process-wide tracer every instrumentation site records into.  A
# single engine per process is the deployment shape (the disagg pools are
# threads of one engine); tests that run several engines call ``clear()``
# between them so the exactly-once finish set does not span runs.
# Rebinding it would silently split the singleton (sites hold direct
# references) — declared shared so repro.analysis flags any rebind.
# analysis: shared-global(TRACER)
TRACER = Tracer()
