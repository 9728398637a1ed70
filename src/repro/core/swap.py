"""Latency-overlapped logic swap (paper §3.4, Fig. 5 — contribution C5).

The paper's observation: prefill attention hardware is dead the moment the
*last layer's* attention finishes, while the remaining prefill work (last
O-projection + FFN + logits) still takes ~31 ms; starting the ~45 ms
reconfiguration at that point hides ~75 % of it.

TPU mapping: the swap cost is the ``kv_relayout`` program (reshard prefill
KV into the decode cache layout).  JAX dispatch is asynchronous — and
``kv_relayout`` depends only on ``prefill_body`` outputs, so dispatching it
*before* ``prefill_tail`` lets the runtime overlap the two (on TPU they run
back-to-back on independent buffers; the relayout's collectives overlap the
tail's compute).  Decode starts only after both complete — the paper's
conservative correctness rule.

``SwapTiming`` records the measured wall-clock on this host.  Only
``measure_both`` (one serialized and one overlapped run) gives
``hidden_fraction`` a meaning; an overlapped run alone never times the
relayout by itself.  In a serving engine the swap's device time is the
``swap_relayout`` program's in a profiler trace, under the ``swap`` span.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional, Tuple

import jax

from repro.obs.trace import TRACER


@dataclasses.dataclass
class SwapTiming:
    t_body: float = 0.0
    t_tail: float = 0.0
    t_relayout: float = 0.0
    t_total_overlapped: float = 0.0
    t_total_serialized: float = 0.0

    @property
    def hidden_fraction(self) -> float:
        """Fraction of the swap latency hidden by the tail (paper: ~75 %)."""
        exposed = max(self.t_total_overlapped - self.t_body - self.t_tail, 0.0)
        if self.t_relayout <= 0:
            return 0.0
        return max(0.0, 1.0 - exposed / self.t_relayout)


@dataclasses.dataclass
class SwapAggregates:
    """Running aggregates over every ``SwapTiming`` ever recorded.

    ``EngineStats`` keeps only a rolling window of raw timings (unbounded
    growth over a long serving run was a leak); these sums survive the
    window and are what the swap-cost-aware scheduling policy consults —
    the measured-history analogue of the paper's 45 ms PCAP bitstream-load
    budget (a modeled roofline figure can override them, see
    ``SwapCostAwarePolicy``).
    """

    count: int = 0
    sum_cost: float = 0.0  # exposed (decode-visible) swap latency

    @staticmethod
    def exposed_cost(t: SwapTiming) -> float:
        """Decode-visible latency of one swap: the part of the relayout the
        prefill tail failed to hide (overlapped runs), or the full measured
        relayout (serialized runs)."""
        if t.t_total_overlapped:
            return max(t.t_total_overlapped - t.t_body - t.t_tail, 0.0)
        return t.t_relayout

    def update(self, t: SwapTiming) -> None:
        self.count += 1
        self.sum_cost += self.exposed_cost(t)

    @property
    def mean_cost(self) -> float:
        return self.sum_cost / self.count if self.count else 0.0


class SwapController:
    """Temporal PD swap for one engine (the paper's single-RP mode).

    ``wait`` blocks on a result (``jax.block_until_ready`` by default; the
    serving engine passes one that also counts the time).  Traced, a run is
    ``prefill.dispatch`` (the body), a wait, and ``swap``: the relayout's
    dispatch and the waits that end it (with overlap, the tail's dispatch
    and wait too)."""

    def __init__(
        self,
        prefill_body: Callable,
        prefill_tail: Callable,
        kv_relayout: Callable,
        *,
        conservative: bool = True,
        wait: Callable = jax.block_until_ready,
    ):
        self.prefill_body = prefill_body
        self.prefill_tail = prefill_tail
        self.kv_relayout = kv_relayout
        self.conservative = conservative
        self.wait = wait

    def prefill_and_swap(
        self, params, tokens, *, overlap: bool = True
    ) -> Tuple[Any, Any, SwapTiming]:
        """Returns (last_logits, decode_cache, timing).

        overlap=False serializes relayout after the tail (the ablation the
        Fig. 5 benchmark measures against).
        """
        timing = SwapTiming()
        wait = self.wait
        t0 = time.perf_counter()
        with TRACER.span("prefill.dispatch"):
            x_mid, kv = self.prefill_body(params, tokens)
        wait(x_mid)
        timing.t_body = time.perf_counter() - t0

        if overlap:
            # Dispatch the swap FIRST: it depends only on `kv`, so it can run
            # concurrently with the tail (async dispatch; on TPU the relayout
            # collectives overlap the tail's FFN compute).
            with TRACER.span("swap"):
                t1 = time.perf_counter()
                cache = self.kv_relayout(kv)
                logits = self.prefill_tail(params, x_mid)
                wait(logits)
                timing.t_tail = time.perf_counter() - t1
                wait(cache)  # conservative: decode waits for swap
            timing.t_total_overlapped = time.perf_counter() - t0
        else:
            t1 = time.perf_counter()
            with TRACER.span("prefill.dispatch"):
                logits = self.prefill_tail(params, x_mid)
            wait(logits)
            timing.t_tail = time.perf_counter() - t1
            with TRACER.span("swap"):
                t2 = time.perf_counter()
                cache = self.kv_relayout(kv)
                wait(cache)
                timing.t_relayout = time.perf_counter() - t2
            timing.t_total_serialized = time.perf_counter() - t0
        return logits, cache, timing

    def measure_both(self, params, tokens) -> SwapTiming:
        """One serialized + one overlapped run, merged into a single record."""
        _, _, ser = self.prefill_and_swap(params, tokens, overlap=False)
        _, _, ovl = self.prefill_and_swap(params, tokens, overlap=True)
        ser.t_total_overlapped = ovl.t_total_overlapped
        ser.t_body, ser.t_tail = ovl.t_body, ovl.t_tail
        return ser
