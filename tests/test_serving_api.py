"""Step-driven serving API: EngineCore.step(), SamplingParams + the on-device
sampler, streaming outputs, SwapPolicy, and PR-1 run() compatibility."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core.swap import SwapAggregates, SwapTiming
from repro.models import get_model
from repro.serving import (
    DrainPolicy,
    EngineCore,
    EngineStats,
    Request,
    SamplingParams,
    SchedulerView,
    ServingEngine,
    SwapCostAwarePolicy,
    make_policy,
)
from repro.serving.outputs import OutputProcessor
from repro.serving.sampling import filter_logits, sample_tokens


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced_config("bitnet-730m", num_layers=3, d_model=128, vocab_size=512,
                         num_heads=4, num_kv_heads=2)
    api = get_model(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, api, params


# ---------------------------------------------------------------- sampler --


def _nucleus_support(logits, temp, top_k, top_p):
    """NumPy reference for the sampling support of one logit row."""
    scaled = np.asarray(logits, np.float64) / max(temp, 1e-6)
    order = np.argsort(-scaled, kind="stable")
    desc = scaled[order]
    v = len(desc)
    k_eff = min(top_k, v) if top_k > 0 else v
    probs = np.exp(desc - desc.max())
    probs /= probs.sum()
    mass_before = np.cumsum(probs) - probs
    n_keep = max(int((mass_before < top_p).sum()), 1)
    cut = max(desc[k_eff - 1], desc[n_keep - 1])
    return set(np.nonzero(scaled >= cut)[0].tolist())


def test_sampler_seeded_determinism():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4, 128)) * 3, jnp.float32)
    seeds = jnp.asarray([7, 7, 9, 9], jnp.int32)
    steps = jnp.asarray([0, 1, 0, 1], jnp.int32)
    temps = jnp.full((4,), 1.0, jnp.float32)
    ks = jnp.zeros((4,), jnp.int32)
    ps = jnp.ones((4,), jnp.float32)
    a = np.asarray(sample_tokens(logits, seeds, steps, temps, ks, ps))
    b = np.asarray(sample_tokens(logits, seeds, steps, temps, ks, ps))
    np.testing.assert_array_equal(a, b)  # same (seed, step) -> same token
    # a different seed (or a different step index) draws a different stream
    c = np.asarray(sample_tokens(logits, seeds + 1, steps, temps, ks, ps))
    assert not np.array_equal(a, c)


@pytest.mark.parametrize("temp,top_k,top_p", [
    (1.0, 8, 1.0),    # pure top-k
    (1.0, 0, 0.7),    # pure nucleus
    (0.7, 16, 0.85),  # composed, with temperature
    (2.5, 3, 0.5),    # aggressive truncation
])
def test_sampler_support_invariants(temp, top_k, top_p):
    """Mass outside the top-k ∩ nucleus support must be exactly zero, and
    every drawn token must come from the support."""
    rng = np.random.default_rng(1)
    logits = rng.normal(size=(2, 64)).astype(np.float32) * 2
    temps = jnp.full((2,), temp, jnp.float32)
    ks = jnp.full((2,), top_k, jnp.int32)
    ps = jnp.full((2,), top_p, jnp.float32)
    masked = np.asarray(filter_logits(jnp.asarray(logits), temps, ks, ps))
    for row in range(2):
        support = _nucleus_support(logits[row], temp, top_k, top_p)
        probs = jax.nn.softmax(jnp.asarray(masked[row]))
        outside = [i for i in range(64) if i not in support]
        assert float(jnp.asarray(probs)[jnp.asarray(outside)].sum()) == 0.0
        assert np.isfinite(masked[row][list(support)]).all()
        if top_k > 0:
            assert len(support) <= top_k
    # 64 draws across step indices: every token lands in the support
    for step in range(32):
        toks = np.asarray(sample_tokens(
            jnp.asarray(logits), jnp.asarray([3, 5], jnp.int32),
            jnp.full((2,), step, jnp.int32), temps, ks, ps))
        for row in range(2):
            assert toks[row] in _nucleus_support(logits[row], temp, top_k, top_p)


def test_sampler_temperature_greedy_limit():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(3, 256)), jnp.float32)
    ref = np.asarray(jnp.argmax(logits, axis=-1))
    seeds = jnp.asarray([1, 2, 3], jnp.int32)
    steps = jnp.zeros((3,), jnp.int32)
    ks = jnp.zeros((3,), jnp.int32)
    ps = jnp.ones((3,), jnp.float32)
    # temp == 0: the greedy path, exactly argmax
    zero = sample_tokens(logits, seeds, steps, jnp.zeros((3,), jnp.float32), ks, ps)
    np.testing.assert_array_equal(np.asarray(zero), ref)
    # temp -> 0+: the sampled path concentrates all mass on the argmax
    for step in range(16):
        cold = sample_tokens(logits, seeds, jnp.full((3,), step, jnp.int32),
                             jnp.full((3,), 1e-3, jnp.float32), ks, ps)
        np.testing.assert_array_equal(np.asarray(cold), ref)


def test_sampling_params_validation():
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError, match="max_tokens"):
        SamplingParams(max_tokens=0)
    assert SamplingParams().greedy and not SamplingParams(temperature=0.5).greedy
    assert SamplingParams(seed=-3).seed32 >= 0


# ------------------------------------------------------- step() vs run() --


def _submit_all(eng, prompts, max_new=6, params=None):
    for i, p in enumerate(prompts):
        eng.submit(Request(f"r{i}", p.copy(), max_new=max_new,
                           params=params or SamplingParams()))


# Golden greedy outputs for the workload below (tiny fixture, rng seed 11,
# n_slots=3, max_len=48, prompt_len=12, max_new=6), captured from the
# drain-scheduled greedy engine on CPU float32 / jax 0.9.0, where
# ``jax_threefry_partitionable`` is on.  With it off, the same code still
# reproduces the tokens pinned under jax 0.4.37: only the init PRNG stream
# moved them.  Pins run()/step() semantics against silent drift: a refactor
# that changes scheduling order, bucketing, or the greedy path must not
# alter these tokens.
_GOLDEN_GREEDY = {
    "r0": [359, 350, 350, 350, 359, 359],
    "r1": [96, 96, 109, 109, 109, 130],
    "r2": [26, 26, 26, 26, 49, 49],
    "r3": [425, 205, 380, 380, 380, 380],
}


@pytest.mark.parametrize("mode", ["pdswap", "static"])
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_step_drives_both_modes_and_layouts(tiny, mode, layout):
    """An explicit step() loop must finish every request in every
    mode x layout combination, matching the compat run() token-for-token
    (greedy + DrainPolicy == the PR-1 engine)."""
    cfg, api, params = tiny
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, 12).astype(np.int32) for _ in range(4)]

    ref_eng = ServingEngine(cfg, params, n_slots=3, max_len=48, prompt_len=12,
                            mode=mode, cache_layout=layout, block_size=8)
    _submit_all(ref_eng, prompts)
    ref_stats = ref_eng.run()
    ref = {k: v.out_tokens for k, v in ref_eng.finished.items()}
    # every mode x layout must reproduce the recorded PR-1 greedy tokens
    # (the PR-1 suite pinned cross-mode/layout equality; the literal values
    # anchor the whole equivalence class against drift)
    assert ref == _GOLDEN_GREEDY

    eng = EngineCore(cfg, params, n_slots=3, max_len=48, prompt_len=12,
                     mode=mode, cache_layout=layout, block_size=8,
                     swap_policy=DrainPolicy())
    _submit_all(eng, prompts)
    streamed = {f"r{i}": [] for i in range(4)}
    steps = 0
    while eng.has_unfinished():
        steps += 1
        assert steps < 100
        for out in eng.step():
            streamed[out.request_id].extend(out.new_token_ids)
    assert {k: v.out_tokens for k, v in eng.finished.items()} == ref
    assert streamed == ref  # the deltas reassemble the full outputs
    assert eng.stats.decode_tokens == ref_stats.decode_tokens
    assert eng.stats.swaps == ref_stats.swaps
    assert all(r.finish_reason == "length" for r in eng.finished.values())


def test_streaming_generate_deltas(tiny):
    cfg, api, params = tiny
    eng = EngineCore(cfg, params, n_slots=2, max_len=48, prompt_len=12)
    got = []
    for out in eng.generate(np.arange(10, dtype=np.int32), max_new=7):
        assert out.new_token_ids  # every yield carries a delta
        got.extend(out.new_token_ids)
        # token_ids is a live view: never behind the deltas, may run ahead
        # within one step() quantum (prefill token + decode token together)
        assert out.token_ids[: len(got)] == got
    req = eng.finished[out.request_id]
    assert out.finished and out.finish_reason == "length"
    assert got == req.out_tokens and len(got) == 7
    assert req.first_token_t > 0.0 and req.done_t >= req.first_token_t


def test_stop_token_finishes_early(tiny):
    cfg, api, params = tiny
    prompt = np.arange(8, dtype=np.int32)
    eng = EngineCore(cfg, params, n_slots=1, max_len=48, prompt_len=12)
    eng.submit(Request("g", prompt.copy(), max_new=8))
    eng.run()
    full = eng.finished["g"].out_tokens
    stop = full[3]
    first_hit = full.index(stop)

    eng2 = EngineCore(cfg, params, n_slots=1, max_len=48, prompt_len=12)
    eng2.submit(Request("g", prompt.copy(), max_new=8,
                        params=SamplingParams(stop_tokens=(stop,))))
    eng2.run()
    req = eng2.finished["g"]
    assert req.finish_reason == "stop"
    assert req.out_tokens == full[: first_hit + 1]  # stop token kept, then cut


def test_max_tokens_overrides_max_new(tiny):
    cfg, api, params = tiny
    eng = EngineCore(cfg, params, n_slots=1, max_len=48, prompt_len=12)
    eng.submit(Request("m", np.arange(8, dtype=np.int32), max_new=12,
                       params=SamplingParams(max_tokens=3)))
    eng.run()
    assert len(eng.finished["m"].out_tokens) == 3
    assert eng.finished["m"].finish_reason == "length"


def test_engine_sampling_seeded_determinism(tiny):
    cfg, api, params = tiny
    prompt = np.arange(10, dtype=np.int32)

    def gen(seed):
        eng = EngineCore(cfg, params, n_slots=2, max_len=48, prompt_len=12)
        eng.submit(Request("s", prompt.copy(), max_new=8,
                           params=SamplingParams(temperature=0.8, top_k=64,
                                                 top_p=0.95, seed=seed)))
        eng.run()
        return eng.finished["s"].out_tokens

    assert gen(123) == gen(123)  # bitwise-repeatable
    assert gen(123) != gen(124)  # and actually stochastic across seeds


def test_sampled_preemption_replay_token_parity(tiny):
    """THE sampling-correctness property: a preempted+replayed request under
    temperature/top-k/top-p sampling continues bit-identically to a run that
    was never preempted (stateless fold_in(seed, token_index) keys +
    teacher-forced replay)."""
    cfg, api, params = tiny
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, 14).astype(np.int32) for _ in range(4)]
    sps = [SamplingParams(temperature=0.8, top_k=64, top_p=0.95, seed=100 + i)
           for i in range(4)]

    def serve(layout, **kw):
        eng = EngineCore(cfg, params, n_slots=3, max_len=64, prompt_len=12,
                         mode="static", cache_layout=layout, block_size=8, **kw)
        for i, p in enumerate(prompts):
            eng.submit(Request(f"r{i}", p.copy(), max_new=10, priority=i,
                               params=sps[i]))
        stats = eng.run()
        return eng, stats, {k: v.out_tokens for k, v in eng.finished.items()}

    _, _, ref = serve("contiguous")  # ample capacity: never preempts
    eng, stats, got = serve("paged", num_blocks=7)  # starved pool: must evict
    assert stats.preemptions > 0 and stats.replayed_tokens > 0
    assert got == ref
    # satellite: resumed requests must report a real TTFT, not 0.0
    assert all(r.first_token_t > 0.0 for r in eng.finished.values())


def test_int8_kv_greedy_matches_fp_token_for_token(tiny):
    """Golden accuracy check for the quantized KV cache: at short contexts
    the int8 cache's greedy decode is token-identical to fp on this tiny
    model — the per-token absmax error (<0.5%) never flips an argmax.
    (Pinned workload: drift here means the quantization math changed.)"""
    cfg, api, params = tiny
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, 12).astype(np.int32) for _ in range(3)]

    def serve(kv_dtype):
        eng = EngineCore(cfg, params, n_slots=3, max_len=64, prompt_len=12,
                         mode="static", cache_layout="paged", block_size=8,
                         kv_dtype=kv_dtype)
        for i, p in enumerate(prompts):
            eng.submit(Request(f"r{i}", p.copy(), max_new=6))
        eng.run()
        assert len(eng.finished) == 3
        return {k: v.out_tokens for k, v in eng.finished.items()}

    assert serve("int8") == serve("fp")


# ----------------------------------------------------------- SwapPolicy --


def _view(queue_depth, active=2, free=1, swap_cost=0.04, decode_cost=0.01):
    return SchedulerView(queue_depth=queue_depth, free_slots=free,
                         active_slots=active, swap_cost=swap_cost,
                         decode_round_cost=decode_cost)


def test_swap_cost_aware_policy_defers_shallow_queues():
    pol = SwapCostAwarePolicy(max_defer_rounds=100)
    # one swap costs 4 decode rounds -> threshold 4
    assert pol.threshold(_view(1)) == 4
    assert not pol.should_prefill(_view(1))
    assert not pol.should_prefill(_view(3))
    assert pol.should_prefill(_view(4))  # deep enough to amortize the flip
    assert pol.should_prefill(_view(1, active=0))  # idle fabric: flip is free
    # no measured history yet -> drain-like warmup
    assert pol.should_prefill(_view(1, swap_cost=0.0, decode_cost=0.0))
    # roofline/modeled override stands in for measured host timings
    pol45 = SwapCostAwarePolicy(swap_cost_override=0.045, max_defer_rounds=100)
    assert pol45.threshold(_view(1, decode_cost=0.005)) == 9


def test_swap_cost_aware_policy_defer_cap_guarantees_admission():
    pol = SwapCostAwarePolicy(max_defer_rounds=3)
    assert [pol.should_prefill(_view(1)) for _ in range(4)] == [False] * 3 + [True]
    pol.reset()
    assert not pol.should_prefill(_view(1))  # counter restarts after reset


def test_make_policy_registry():
    assert isinstance(make_policy("drain"), DrainPolicy)
    p = make_policy("swap-aware", min_queue=5)
    assert isinstance(p, SwapCostAwarePolicy) and p.threshold(_view(1)) == 5
    with pytest.raises(ValueError, match="unknown swap policy"):
        make_policy("nope")


def test_swap_aware_engine_batches_bursts_same_tokens(tiny):
    """Step-driven arrivals: the cost-aware policy must enter fewer prefill
    phases than drain (it batches admissions) while leaving every request's
    tokens unchanged (slot trajectories are independent)."""
    cfg, api, params = tiny
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, cfg.vocab_size, 12).astype(np.int32) for _ in range(6)]

    def drive(policy):
        eng = EngineCore(cfg, params, n_slots=6, max_len=48, prompt_len=12,
                         swap_policy=policy)
        eng.submit(Request("r0", prompts[0].copy(), max_new=10))
        # single-request arrivals mid-decode: drain flips the fabric for each
        # one; the cost-aware policy waits for the queue to deepen
        arrivals = {2: [1], 3: [2], 5: [3], 6: [4], 8: [5]}
        step = 0
        while eng.has_unfinished() or arrivals:
            step += 1
            assert step < 200
            for i in arrivals.pop(step, []):
                eng.submit(Request(f"r{i}", prompts[i].copy(), max_new=10))
            eng.step()
        return eng

    drain = drive(DrainPolicy())
    aware = drive(SwapCostAwarePolicy(min_queue=2, max_defer_rounds=6))
    assert len(drain.finished) == len(aware.finished) == 6
    assert {k: v.out_tokens for k, v in drain.finished.items()} == \
           {k: v.out_tokens for k, v in aware.finished.items()}
    assert aware.stats.swaps == drain.stats.swaps == 6  # one swap per request
    assert aware.stats.prefill_bursts < drain.stats.prefill_bursts


# ------------------------------------------------- stats & output plumbing --


def test_swap_timings_window_bounded_with_running_aggregates():
    stats = EngineStats()
    for i in range(200):
        stats.record_swap(SwapTiming(t_body=0.010, t_tail=0.005,
                                     t_total_overlapped=0.017))
    assert stats.swaps == 200
    assert len(stats.swap_timings) == stats.swap_timings.maxlen == 64
    assert stats.swap_agg.count == 200  # aggregates survive the window
    # exposed cost = overlapped_total - body - tail = 2ms per swap
    assert stats.swap_agg.mean_cost == pytest.approx(0.002)
    ser = SwapTiming(t_relayout=0.03, t_total_serialized=0.05)
    assert SwapAggregates.exposed_cost(ser) == pytest.approx(0.03)


def test_output_processor_stamps_ttft_once():
    proc = OutputProcessor()
    req = Request("x", np.zeros(4, np.int32), max_new=3)
    out = proc.process_token(req, 5)
    assert req.first_token_t > 0.0 and not out.finished
    t = req.first_token_t
    proc.process_token(req, 6)
    assert req.first_token_t == t  # never overwritten
    out = proc.process_token(req, 7)
    assert out.finished and out.finish_reason == "length" and req.done_t > 0.0
    # the PR-1 TTFT bug shape: a restart arriving with tokens but no stamp
    restart = Request("y", np.zeros(4, np.int32), max_new=8, out_tokens=[1, 2],
                      preempted=True)
    proc.process_token(restart, 3)
    assert restart.first_token_t > 0.0
