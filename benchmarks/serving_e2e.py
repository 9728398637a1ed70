"""End-to-end measured serving: PD-Swap vs static engine on this host.

Functional companion to fig6: drives the real step-driven serving core
(``EngineCore.step()`` + SwapController) with batched requests on a
reduced-config model, CPU backend.  Absolute tok/s is a CPU number; the *comparison* exercises the
identical code paths the TPU deployment uses (program swap, KV relayout,
decode masking, slot management).  Correctness cross-check: both modes must
emit identical tokens for identical prompts (greedy).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import reduced_config
from repro.models import get_model
from repro.serving import EngineCore, Request

from .common import save_result, stats_block


def _drive(mode: str, cfg, params, prompts, *, n_slots=4, max_len=96, prompt_len=24, max_new=16):
    eng = EngineCore(cfg, params, n_slots=n_slots, max_len=max_len,
                     prompt_len=prompt_len, mode=mode)
    for i, p in enumerate(prompts):
        eng.submit(Request(f"r{i}", p, max_new=max_new))
    streamed = {f"r{i}": [] for i in range(len(prompts))}
    while eng.has_unfinished():
        for out in eng.step():  # incremental RequestOutput deltas
            streamed[out.request_id].extend(out.new_token_ids)
    outs = {rid: r.out_tokens for rid, r in eng.finished.items()}
    assert streamed == outs, "streaming deltas must reassemble the outputs"
    return eng, outs


def run() -> dict:
    cfg = reduced_config("smollm-135m", num_layers=3, d_model=192, vocab_size=2048,
                         num_heads=6, num_kv_heads=2)
    api = get_model(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=24).astype(np.int32) for _ in range(6)]

    eng_pd, outs_pd = _drive("pdswap", cfg, params, prompts)
    eng_st, outs_st = _drive("static", cfg, params, prompts)
    stats_pd, stats_st = eng_pd.stats, eng_st.stats

    same = all(outs_pd[k] == outs_st[k] for k in outs_pd)

    def _row(engine, stats):
        return {"engine": engine, "decode_tokens": stats.decode_tokens,
                "decode_tok/s (CPU)": stats.decode_tput(), "swaps": stats.swaps,
                "prefill_s": stats.t_prefill,
                # client-visible latency aggregates (arrival-stamped)
                "queue_wait_p95_ms": 1e3 * stats.queue_wait.p95,
                "ttft_p95_ms": 1e3 * stats.ttft.p95,
                "itl_p95_ms": 1e3 * stats.itl.p95}

    rows = [_row("pdswap", stats_pd), _row("static", stats_st)]
    checks = {
        "identical greedy tokens across engines": same,
        "all requests finished (both engines)": len(outs_pd) == len(prompts) == len(outs_st),
        "queue wait + TTFT recorded for every admission": (
            stats_pd.queue_wait.count == len(prompts)
            and stats_pd.ttft.count == len(prompts)),
    }
    result = {
        "name": "serving_e2e",
        "rows": rows,
        "notes": (
            "Measured continuous-batching run on this host (reduced config; CPU "
            "numbers validate the mechanism, not TPU perf).  Claim checks: "
            + ", ".join(f"{k}={'PASS' if v else 'FAIL'}" for k, v in checks.items())
        ),
        "checks": checks,
        "stats": {"pdswap": stats_block(eng_pd), "static": stats_block(eng_st)},
    }
    save_result(result)
    return result
