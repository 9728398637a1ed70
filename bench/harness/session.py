"""Drives the serving engine the way its users do: ``EngineCore.submit``
and ``EngineCore.step()``, the entry ``repro.launch.serve`` drives.

Every step is wrapped in a ``jax.profiler.TraceAnnotation`` of the
harness's own (``bench.step``, ``bench.submit``, ``bench.wait_arrival``,
``bench.outputs``), so a traced run can say what the host was doing in each
idle gap of the device.  A delta is stamped when ``step()`` hands it back,
which is when a client would have it.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import resource
import time
from typing import Dict, List, Optional

import jax
from bench.harness import traffic as tr
from bench.harness.window import Stream
from repro.obs.trace import TRACER

ANN = jax.profiler.TraceAnnotation


def build_engine(cfg, params, engine: dict):
    from repro.serving import EngineCore

    kw = dict(engine)
    return EngineCore(cfg, params, swap_policy=kw.pop("swap_policy", None), **kw)


def counters(eng) -> dict:
    """Every counter of the engine's ``EngineStats`` (its int and float
    fields), which a window reads as deltas: a counter the program adds
    reaches the metrics and the family's work with no change here."""
    st = eng.stats
    return {f.name: getattr(st, f.name) for f in dataclasses.fields(st)
            if isinstance(getattr(st, f.name), (int, float))}


class CompileCount:
    """Backend compiles, from JAX's own monitoring events; every other
    event JAX records (tracing, cache lookups) is tallied in ``seen``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        self.seen: Dict[str, list] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(lambda event, **_: self._on(event, 0.0))

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration
        tally = self.seen.setdefault(event, [0, 0.0])
        tally[0] += 1
        tally[1] += duration


class HostWatch:
    """What the host did over the window, to tell a stall's cause: the
    garbage collector's pauses, the process's CPU time, context switches
    and page faults, and the load of the machine."""

    def __init__(self):
        self.pauses: List[tuple] = []  # (start, seconds, generation)
        self._t = None
        self.on = False
        gc.callbacks.append(self._gc)

    def _gc(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((self._t, time.perf_counter() - self._t, info["generation"]))
            self._t = None

    @staticmethod
    def _usage():
        r = resource.getrusage(resource.RUSAGE_SELF)
        return {"cpu_s": r.ru_utime + r.ru_stime, "involuntary_switches": r.ru_nivcsw,
                "voluntary_switches": r.ru_nvcsw, "major_faults": r.ru_majflt,
                "minor_faults": r.ru_minflt}

    def start(self):
        self.u0, self.load0, self.on = self._usage(), _load(), True

    def stop(self):
        self.on = False
        self.u1, self.load1 = self._usage(), _load()
        gc.callbacks.remove(self._gc)

    def summary(self, t0: float) -> str:
        longest = max(self.pauses, key=lambda p: p[1], default=None)
        gc_s = sum(p[1] for p in self.pauses)
        use = ", ".join(f"{k} {self.u1[k] - self.u0[k]:.6g}" for k in self.u0)
        out = (f"gc {len(self.pauses)} pauses, {gc_s * 1e3:.3f} ms in all; {use}; "
               f"load average {self.load0} -> {self.load1}")
        if longest:
            out += (f"; longest gc pause {longest[1] * 1e3:.3f} ms (generation "
                    f"{longest[2]}) at {longest[0] - t0:.3f}s")
        return out


def _load():
    return tuple(round(x, 2) for x in os.getloadavg())


class Session:
    """One engine under one seed's traffic."""

    def __init__(self, eng, seed: int, vocab: int):
        self.eng, self.seed, self.vocab = eng, seed, vocab
        self.streams: Dict[str, Stream] = {}
        self.requests: Dict[str, object] = {}
        self._count = 0
        # (start, end, main thread's CPU seconds) of every step() call: a long
        # step that took little CPU waited on the device or for the core
        self.steps: List[tuple] = []

    # ------------------------------------------------------------ requests --

    def submit(self, item: tr.Item, due: float, stream: int = 3) -> Stream:
        from repro.serving import Request

        i = self._count
        self._count += 1
        rid = f"r{i}"
        prompt = tr.prompt_tokens(self.seed, stream, i, item.prompt_len, self.vocab)
        req = Request(rid, prompt, max_new=item.max_new)
        req.arrival_time_s = due  # the engine's queue wait counts from when it was due
        s = Stream(rid, due, time.perf_counter(), item.prompt_len)
        with ANN("bench.submit"):
            try:
                self.eng.submit(req)
            except ValueError:
                s.failed = True
        self.streams[rid], self.requests[rid] = s, req
        return s

    def step(self) -> List[str]:
        """One engine step; returns the ids of the requests it finished."""
        t_in, cpu = time.perf_counter(), time.thread_time()
        with ANN("bench.step"):
            outs = self.eng.step()
        t = time.perf_counter()
        self.steps.append((t_in, t, time.thread_time() - cpu))
        done = []
        with ANN("bench.outputs"):
            for o in outs:
                s = self.streams[o.request_id]
                if o.new_token_ids:
                    s.emits.append((t, len(o.new_token_ids)))
                if o.finished:
                    s.done = t
                    s.failed = o.finish_reason not in ("stop", "length")
                    done.append(o.request_id)
        return done

    def busy(self) -> bool:
        return self.eng.has_unfinished()

    # ------------------------------------------------------------- warm-up --

    def warm_up(self, mix: dict) -> int:
        """Run every prefill shape the mix's prompt lengths can reach, with
        two tokens out so decode runs too, and at least one request in
        every slot; returns the number of warm-up requests."""
        r = self.eng.runner
        lo, hi = int(mix["prompt_len"]["lo"]), int(mix["prompt_len"]["hi"])

        def shapes(n):
            if r.prefill_chunk is None:
                return {("bucket", r.bucket(n))}
            out, start = set(), 0
            for size in r.chunk_sizes(n):
                out.add((r.chunk_bucket(size, start), r.prefix_width(start)))
                start += size
            return out

        seen, lengths = set(), []
        for n in range(hi, lo - 1, -1):
            new = shapes(n) - seen
            if new:
                seen |= new
                lengths.append(n)
        n_slots = r.slots.n_slots
        lengths = lengths + [lengths[0]] * max(0, n_slots - len(lengths))
        now = time.perf_counter()
        for n in lengths:
            self.submit(tr.Item(n, 2), now, stream=4)
        while self.busy():
            self.step()
        self.streams.clear()
        self.requests.clear()
        self.steps.clear()
        return len(lengths)


class Hooks:
    """What the traffic loop calls: ``open(t)`` when the window opens,
    ``close(t)`` when it ends; then, for ``after_s`` seconds more of the
    same traffic, ``after_open(t)`` and ``after_close()`` (the traced slice
    of a ``--trace 1`` run, kept out of the window)."""

    after_s = 0.0

    def open(self, t: float) -> None:
        pass

    def close(self, t: float) -> None:
        pass

    def after_open(self, t: float) -> None:
        pass

    def after_close(self) -> None:
        pass


def _phases(hooks: Hooks, t0: float, seconds: float):
    """(window end, slice end) for a window opening at t0."""
    return t0 + seconds, t0 + seconds + hooks.after_s


def run_closed(sess: Session, lanes: List[List[tr.Item]], seconds: float, hooks: Hooks):
    """Closed loop: every client sends its next request the moment the
    previous one finishes.  Pre-roll: until every client's first request
    has produced a token.  Returns (t0, t1) of the measured window."""
    owner, nxt = {}, [1] * len(lanes)
    now = time.perf_counter()
    firsts = []
    for c, lane in enumerate(lanes):
        s = sess.submit(lane[0], now)
        owner[s.rid] = c
        firsts.append(s)

    def refill(done):
        t = time.perf_counter()
        for rid in done:
            c = owner.pop(rid)
            lane = lanes[c]
            s = sess.submit(lane[nxt[c] % len(lane)], t)
            nxt[c] += 1
            owner[s.rid] = c

    while not all(s.emits or s.failed for s in firsts):
        refill(sess.step())
    t0 = t = time.perf_counter()
    hooks.open(t0)
    t_close, t_after = _phases(hooks, t0, seconds)
    t1 = None
    while t < t_after or t1 is None:
        refill(sess.step())
        t = time.perf_counter()
        if t1 is None and t >= t_close:
            t1 = t
            hooks.close(t1)
            if hooks.after_s:
                hooks.after_open(t1)
    if hooks.after_s:
        hooks.after_close()
    return t0, t1


def run_open(sess: Session, items: List[tr.Item], pre_roll_s: float, seconds: float,
             hooks: Hooks):
    """Open loop: each request is sent when it is due, whatever the engine
    is doing; the engine idles only when it has nothing to do.  The window
    opens at the first step boundary ``pre_roll_s`` after the traffic
    starts.  Returns (t0, t1) of the measured window."""
    base = time.perf_counter()
    dues = [base + it.due for it in items]
    i, t0, t1 = 0, None, None
    while True:
        now = time.perf_counter()
        if t0 is None and now >= base + pre_roll_s:
            t0 = now
            hooks.open(t0)
            t_close, t_after = _phases(hooks, t0, seconds)
        if t0 is not None:
            if t1 is None and now >= t_close:
                t1 = now
                hooks.close(t1)
                if hooks.after_s:
                    hooks.after_open(t1)
            if t1 is not None and now >= t_after:
                break
        while i < len(items) and dues[i] <= now:
            sess.submit(items[i], dues[i])
            i += 1
        if sess.busy():
            sess.step()
        elif i < len(items):
            with ANN("bench.wait_arrival"):
                time.sleep(max(0.0, dues[i] - time.perf_counter()))
        else:
            raise RuntimeError("the traffic ran out before the window closed")
    if hooks.after_s:
        hooks.after_close()
    return t0, t1


class TraceSlice:
    """The profiler over a slice of the traffic, marked by the host
    annotation ``bench.trace_window``, with the engine's spans
    (``repro.obs.trace.TRACER``) recorded inside the slice only."""

    def __init__(self, log_dir: str):
        self.dir = log_dir
        self._ann = None

    def start(self) -> None:
        jax.profiler.start_trace(self.dir)
        self._ann = ANN("bench.trace_window")
        self._ann.__enter__()
        TRACER.enable()

    def stop(self) -> None:
        if self._ann is not None:
            TRACER.disable()
            TRACER.clear()
            self._ann.__exit__(None, None, None)
            self._ann = None
            jax.profiler.stop_trace()


def free(eng) -> None:
    """Drop the engine's device state (caches, pools, prefix mirror)."""
    r = eng.runner
    for name in ("cache", "paged", "chunk_prefix", "last_tokens"):
        if hasattr(r, name):
            setattr(r, name, None)
    r.params = None
