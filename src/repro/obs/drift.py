"""Roofline drift attribution: measured per-phase time vs the analytic
bound, as a queryable metric instead of a one-off report.

The paper's efficiency claims are phrased against rooflines — decode is
KV-bandwidth-bound (Eq. 5), prefill is compute-bound, speculation amortizes
the KV stream across accepted tokens.  ``roofline_drift()`` compares what
the engine MEASURED (``EngineStats`` wall-time sums and the streamed-
context accumulator) against what ``core/roofline.py`` predicts for the
same workload on the target chip, per phase:

* ``prefill`` — measured s/prefill-token vs the 2N-flops compute bound
  (``prefill_compute_time``, N = parameter count of the loaded model);
* ``decode`` — measured s/decoded-token vs the Eq. (5) KV-stream bound at
  the MEAN streamed context (kv_dtype-aware), divided by the measured
  tokens-per-round amortization (1.0 without speculation — so the same
  formula covers plain and speculative rounds);
* ``spec_verify`` — present when verify rounds ran: the same measured
  number vs the ANALYTIC speculative bound
  (``decode_kv_stream_time_speculative`` at the measured acceptance rate)
  — the gap between this and ``decode`` is how much of the predicted
  amortization the draft stream actually delivered.

``residency_ratio = bound / measured`` — the fraction of the roofline the
engine achieves (1.0 = running at the bound; CI's CPU runs sit far below a
v5e bound, which is fine: the metric tracks DRIFT over time, regressions
show as the ratio falling).  All host arithmetic over already-maintained
counters: safe to compute on every snapshot/scrape.
"""
from __future__ import annotations

from typing import Any, Dict

PHASES = ("prefill", "decode", "spec_verify")


def _n_params(runner) -> int:
    """Total parameter count of the loaded model, cached on the runner
    (shapes only — no device transfer; a packed ternary byte counts its
    four weights)."""
    cached = getattr(runner, "_obs_n_params", None)
    if cached is not None:
        return cached
    from repro.common.tree import tree_param_count

    n = tree_param_count(runner.params)
    runner._obs_n_params = n
    return n


def _entry(measured: float, bound: float, **extra) -> Dict[str, Any]:
    from repro.core.roofline import roofline_residency

    out = {
        "measured_s_per_token": measured,
        "bound_s_per_token": bound,
        "residency_ratio": roofline_residency(bound, measured),
    }
    out.update(extra)
    return out


def roofline_drift(core) -> Dict[str, Dict[str, Any]]:
    """Per-phase ``{measured_s_per_token, bound_s_per_token,
    residency_ratio}`` for the engine's accumulated stats (empty phases —
    no tokens yet — are omitted).  Bounds come from the same
    ``core.roofline.predict_phase`` predictions the ``program`` analysis
    pass audits the traced programs against — one source for the numbers
    the gate enforces and the metric reports."""
    from repro.core.roofline import predict_phase

    stats = core.stats
    runner = core.runner
    cfg, kv_dtype = runner.cfg, runner.kv_dtype
    out: Dict[str, Dict[str, Any]] = {}

    if stats.prefill_tokens and stats.t_prefill > 0.0:
        out["prefill"] = _entry(
            stats.t_prefill / stats.prefill_tokens,
            predict_phase("prefill", n_params=_n_params(runner)).t_per_token,
            n_params=_n_params(runner),
        )

    if stats.decode_tokens and stats.t_decode > 0.0:
        # mean context STREAMED per decode pass (each round streams every
        # active slot's cache once; the accumulator sums slot lengths per
        # round, slot_rounds normalizes to one pass)
        ctx = (stats.decode_ctx_tokens / stats.slot_rounds
               if stats.slot_rounds else 0.0)
        measured = stats.t_decode / stats.decode_tokens
        tpr = max(stats.tokens_per_round(), 1.0)
        out["decode"] = _entry(
            measured,
            predict_phase("decode", cfg, context=ctx,
                          kv_dtype=kv_dtype).t_per_token / tpr,
            context_mean=ctx,
            kv_dtype=kv_dtype,
            tokens_per_round=tpr,
        )
        if stats.verify_rounds and runner.spec_decode:
            out["spec_verify"] = _entry(
                measured,
                predict_phase("spec_verify", cfg, context=ctx,
                              k=runner.spec_decode,
                              accept_rate=stats.acceptance_rate(),
                              kv_dtype=kv_dtype).t_per_token,
                context_mean=ctx,
                kv_dtype=kv_dtype,
                accept_rate=stats.acceptance_rate(),
                k=runner.spec_decode,
            )
    return out
