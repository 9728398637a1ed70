"""Speculative decoding (prompt-lookup drafting + batched verify) and the
finish-semantics fixes that ride along: greedy bit-identity vs the
non-speculative engine across {contiguous, paged} x {fp, int8, int4},
preempt/replay mid-speculation, draft clamping at the cache headroom
(the parked-write-row invariant), multi-token stop/budget truncation, and
the resume-at-budget terminal output."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core.sampling import accept_length
from repro.models import get_model
from repro.models.transformer import convert_for_inference
from repro.serving import EngineCore, Request, SamplingParams
from repro.serving.core import ModelRunner
from repro.serving.outputs import OutputProcessor
from repro.serving.spec_decode import find_draft


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced_config("bitnet-730m", num_layers=3, d_model=128, vocab_size=512,
                         num_heads=4, num_kv_heads=2)
    api = get_model(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, api, params


@pytest.fixture(scope="module")
def markov(tiny):
    """The tiny model with every attention output projection zeroed, so its
    next token depends on the current token only: ``nxt[t]``.  A prompt
    that walks a cycle of ``nxt`` is repetitive by construction: the greedy
    stream keeps walking the cycle, and prompt-lookup drafts of it are right
    whatever the random weights.  Returns (cfg, params, longest cycle)."""
    cfg, api, params = tiny
    layers = dict(params["layers"])
    layers["attn"] = dict(layers["attn"], wo=jax.tree.map(jnp.zeros_like, layers["attn"]["wo"]))
    params = dict(params, layers=layers)
    tokens = jnp.arange(cfg.vocab_size, dtype=jnp.int32)[:, None]
    # the weights as the engine serves them: ternary linears packed once
    served = convert_for_inference(cfg, params)
    logits, _ = jax.jit(lambda p, t: api.forward_prefill(p, t, cfg))(served, tokens)
    nxt = np.asarray(jnp.argmax(logits[:, : cfg.vocab_size], -1))
    best = []
    for t in range(cfg.vocab_size):
        path = {}
        while t not in path:
            path[t] = len(path)
            t = int(nxt[t])
        cycle = list(path)[path[t]:]
        if len(cycle) > len(best):
            best = cycle
    return cfg, params, np.asarray(best, np.int32)


def _prompts(cfg, seed=3):
    """Mixed workload: one self-repetitive prompt (the drafter's regime)
    plus random ones (the adversarial pole)."""
    rng = np.random.default_rng(seed)
    pat = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    return [np.tile(pat, 4),
            rng.integers(0, cfg.vocab_size, 14).astype(np.int32),
            rng.integers(0, cfg.vocab_size, 9).astype(np.int32)]


def _serve(cfg, params, prompts, *, layout, spec=None, mode="static",
           max_new=12, max_len=64, sp=None, **kw):
    eng = EngineCore(cfg, params, n_slots=3, max_len=max_len, prompt_len=12,
                     mode=mode, cache_layout=layout, block_size=8,
                     spec_decode=spec, **kw)
    for i, p in enumerate(prompts):
        eng.submit(Request(f"r{i}", p.copy(), max_new=max_new,
                           params=sp or SamplingParams()))
    stats = eng.run()
    return eng, stats, {k: v.out_tokens for k, v in eng.finished.items()}


# ----------------------------------------------------------- the drafter --


def test_find_draft_matches_most_recent_ngram():
    ctx = np.array([5, 1, 2, 3, 9, 1, 2, 3], np.int32)
    # trailing 3-gram [1,2,3] matched at position 1; continuation follows it
    np.testing.assert_array_equal(find_draft(ctx, 1, 3), [9])
    np.testing.assert_array_equal(find_draft(ctx, 4, 3), [9, 1, 2, 3])
    # among full-continuation matches the most recent wins
    ctx2 = np.array([1, 2, 3, 7, 8, 1, 2, 3, 9, 1, 2, 3], np.int32)
    np.testing.assert_array_equal(find_draft(ctx2, 2, 3), [9, 1])
    # a match whose continuation would be empty is never selected — the
    # earlier occurrence (with real continuation tokens) is
    ctx3 = np.array([1, 2, 3, 7, 8, 1, 2, 3], np.int32)
    np.testing.assert_array_equal(find_draft(ctx3, 2, 3), [7, 8])


def test_find_draft_falls_back_to_shorter_ngrams_and_empty():
    ctx = np.array([4, 4, 4, 4], np.int32)  # period-1: only size-1+ matches
    got = find_draft(ctx, 3, 3)
    assert len(got) >= 1 and all(t == 4 for t in got)
    # no earlier occurrence of anything -> no draft
    assert len(find_draft(np.array([1, 2, 3, 4], np.int32), 3, 3)) == 0
    assert len(find_draft(np.array([7], np.int32), 3, 3)) == 0
    assert len(find_draft(np.array([1, 2, 1, 2], np.int32), 0, 3)) == 0


def test_accept_length_rule():
    assert accept_length([1, 2, 3], [1, 2, 3]) == 3
    assert accept_length([1, 2, 3], [1, 9, 3]) == 1
    assert accept_length([1, 2], [9, 2]) == 0
    assert accept_length([], []) == 0


# ------------------------------------------- multi-token finish semantics --


class _Req:
    def __init__(self, max_new, stop=(), out=None):
        self.request_id = "t"
        self.max_new = max_new
        self.params = SamplingParams(stop_tokens=stop)
        self.out_tokens = list(out or [])
        self.first_token_t = 0.0
        self.done_t = 0.0
        self.finish_reason = None


def test_process_tokens_truncates_at_first_stop():
    """Satellite: an accepted speculative block must never leak tokens past
    a stop token — everything after the FIRST stop is dropped."""
    req = _Req(max_new=10, stop=(7,))
    out = OutputProcessor().process_tokens(req, [3, 7, 5, 6])
    assert out.new_token_ids == [3, 7]
    assert req.out_tokens == [3, 7]
    assert out.finished and out.finish_reason == "stop"


def test_process_tokens_caps_at_budget_headroom():
    req = _Req(max_new=4, out=[1, 2])
    out = OutputProcessor().process_tokens(req, [3, 4, 5, 6])
    assert out.new_token_ids == [3, 4]  # headroom was 2
    assert out.finished and out.finish_reason == "length"
    assert len(req.out_tokens) == 4


def test_process_tokens_stop_wins_on_budget_boundary():
    """A stop token landing exactly on the budget edge reports "stop" —
    the same precedence the single-token path always had."""
    req = _Req(max_new=2, stop=(9,), out=[1])
    out = OutputProcessor().process_tokens(req, [9, 5])
    assert out.new_token_ids == [9]
    assert out.finish_reason == "stop"


def test_process_token_delegates_unchanged():
    req = _Req(max_new=2)
    out = OutputProcessor().process_token(req, 5)
    assert out.new_token_ids == [5] and not out.finished
    assert req.first_token_t > 0.0
    out = OutputProcessor().process_token(req, 6)
    assert out.finished and out.finish_reason == "length"


def test_engine_stop_mid_accepted_block_truncates(markov):
    """Satellite, engine-level: a stop token landing INSIDE an accepted
    speculative block ends the stream at the stop — no leaked tokens past
    it — and matches the non-speculative stream exactly."""
    cfg, params, cycle = markov
    assert len(cycle) >= 3, cycle
    prompt = np.tile(cycle, -(-24 // len(cycle)))[:24]
    # the stream continues the cycle, so its third token is new to the
    # stream and can only be reached inside a multi-token accepted block
    _, _, probe = _serve(cfg, params, [prompt], layout="contiguous", max_new=12)
    stream = probe["r0"]
    stop_tok = next(t for i, t in enumerate(stream) if i >= 2 and t not in stream[:i])
    sp = SamplingParams(stop_tokens=(int(stop_tok),))
    _, _, ref = _serve(cfg, params, [prompt], layout="contiguous",
                       max_new=12, sp=sp)
    _, stats, got = _serve(cfg, params, [prompt], layout="contiguous",
                           max_new=12, sp=sp, spec=4)
    assert got == ref
    assert got["r0"][-1] == stop_tok and stop_tok not in got["r0"][:-1]
    assert stats.accepted_tokens > 0  # the block path was really exercised


# ------------------------------------------ resume-at-budget terminal out --


def _resume_at_budget(tiny, out_tokens, stop=()):
    cfg, api, params = tiny
    eng = EngineCore(cfg, params, n_slots=2, max_len=64, prompt_len=12,
                     mode="static", cache_layout="paged", block_size=8)
    rng = np.random.default_rng(0)
    req = Request("resume", rng.integers(0, cfg.vocab_size, 10).astype(np.int32),
                  max_new=len(out_tokens),
                  params=SamplingParams(stop_tokens=stop))
    req.out_tokens = list(out_tokens)
    req.preempted = True  # external replay / checkpoint-restore path
    eng.submit(req)
    outs = []
    for _ in range(20):
        outs.extend(eng.step())
        if "resume" in eng.finished:
            break
    return eng, outs


def test_resume_exactly_at_budget_emits_terminal_output(tiny):
    """Satellite regression: a replayed request resuming EXACTLY at its
    max_new budget used to finish silently — finish_reason None, no
    terminal RequestOutput, the stream just went dark."""
    eng, outs = _resume_at_budget(tiny, [5, 6, 7])
    assert "resume" in eng.finished
    req = eng.finished["resume"]
    assert req.finish_reason == "length"
    term = [o for o in outs if o.request_id == "resume" and o.finished]
    assert len(term) == 1
    assert term[0].new_token_ids == []  # zero-delta: tokens streamed pre-eviction
    assert term[0].finish_reason == "length"
    assert not eng.runner.slots.active_slots()  # slot released


def test_resume_at_budget_stop_token_reports_stop(tiny):
    eng, outs = _resume_at_budget(tiny, [5, 6, 9], stop=(9,))
    assert eng.finished["resume"].finish_reason == "stop"
    term = [o for o in outs if o.request_id == "resume" and o.finished]
    assert term and term[0].finish_reason == "stop"


# ------------------------------------------------- greedy bit-identity ----


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("kv_dtype", ["fp", "int8", "int4"])
def test_spec_greedy_bit_identical_to_plain_decode(tiny, layout, kv_dtype):
    """THE speculative contract: with greedy sampling, spec-on streams are
    bit-identical to the non-speculative engine — every emitted token is
    the token sequential decode would have produced — for every layout x
    kv_dtype, while the repetitive prompt actually exercises acceptance."""
    cfg, api, params = tiny
    prompts = _prompts(cfg)
    _, _, ref = _serve(cfg, params, prompts, layout=layout, kv_dtype=kv_dtype)
    _, stats, got = _serve(cfg, params, prompts, layout=layout,
                           kv_dtype=kv_dtype, spec=4)
    assert got == ref
    assert stats.verify_rounds > 0 and stats.draft_tokens > 0
    assert stats.accepted_tokens > 0  # the repetitive prompt drafts land
    assert stats.decode_rounds < 3 * 12  # strictly fewer rounds than 1/token


def test_spec_pdswap_mode_bit_identical(tiny):
    cfg, api, params = tiny
    prompts = _prompts(cfg)
    _, _, ref = _serve(cfg, params, prompts, layout="contiguous", mode="pdswap")
    _, stats, got = _serve(cfg, params, prompts, layout="contiguous",
                           mode="pdswap", spec=4)
    assert got == ref and stats.accepted_tokens > 0


def test_spec_sampled_streams_match_sequential(tiny):
    """Sampled targets reuse the sequential fold_in(seed, index) key
    stream, so spec-on sampling reproduces spec-off sampling exactly."""
    cfg, api, params = tiny
    prompts = _prompts(cfg, seed=5)
    sp = SamplingParams(temperature=0.9, top_k=20, top_p=0.9, seed=11)
    _, _, ref = _serve(cfg, params, prompts, layout="contiguous", sp=sp)
    _, _, got = _serve(cfg, params, prompts, layout="contiguous", sp=sp, spec=4)
    assert got == ref


def test_spec_acceptance_exceeds_one_token_per_round(markov):
    """The headline claim (pinned as a count, not wall clock): on a
    repetitive-suffix workload the engine accepts MORE than one draft
    token per SLOT per decode round.  Normalized by slot_rounds — a
    concurrent batch already emits batch-many tokens per round without
    speculation, so per-round totals could masquerade as amortization;
    per-slot cannot (the non-speculative baseline is exactly 1.0)."""
    cfg, params, cycle = markov
    prompts = [np.tile(cycle, -(-26 // len(cycle)))[:26].copy() for _ in range(2)]
    _, stats, _ = _serve(cfg, params, prompts, layout="paged", spec=4,
                         max_new=16, max_len=96)
    assert stats.verify_rounds > 0 and stats.slot_rounds > 0
    assert stats.accepted_tokens / stats.slot_rounds > 1.0
    assert stats.tokens_per_round() > 2.0  # per slot: >2x plain decode
    # sanity of the normalizer itself: a non-speculative run sits at 1.0
    _, base, _ = _serve(cfg, params, prompts, layout="paged",
                        max_new=16, max_len=96)
    assert base.tokens_per_round() == 1.0


# ------------------------------------------------ preemption + rollback ----


@pytest.mark.parametrize("kv_dtype", ["fp", "int4"])
def test_spec_preemption_replay_mid_speculation(tiny, kv_dtype):
    """A pool too small for the offered load forces eviction mid-stream
    (mid-speculation included); the replayed restart re-derives the same
    drafts from the same history and continues bit-identically to the
    never-preempted non-speculative reference."""
    cfg, api, params = tiny
    rng = np.random.default_rng(4)
    pat = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
    prompts = [np.tile(pat, 2)] + [
        rng.integers(0, cfg.vocab_size, 14).astype(np.int32) for _ in range(3)]
    _, _, ref = _serve(cfg, params, prompts, layout="contiguous",
                       max_new=10, kv_dtype=kv_dtype)
    eng, stats, got = _serve(cfg, params, prompts, layout="paged",
                             max_new=10, kv_dtype=kv_dtype, spec=4,
                             num_blocks=7)
    assert stats.preemptions > 0 and stats.replayed_tokens > 0
    assert got == ref
    # rollback accounting: after the run every page is back home
    pool = eng.runner.paged.pool
    assert pool.num_live == 0
    assert len(pool.free_list) + len(pool.evictable) == pool.num_blocks


def test_truncate_slot_releases_overshoot_pages(tiny):
    """Unit: speculative rollback drops exactly the trailing pages past the
    accepted length and keeps the pool invariant intact."""
    cfg, api, params = tiny
    runner = ModelRunner(cfg, params, n_slots=2, max_len=64, prompt_len=12,
                         mode="static", cache_layout="paged", block_size=8)
    paged = runner.paged
    slot = runner.slots.assign("t", 10, 20)
    match = paged.allocate_prompt(slot, np.arange(10, dtype=np.int32))
    assert len(paged.tables[slot]) == 2  # 10 tokens @ bs=8
    for pos in range(10, 10 + 7):  # grow a verify span of 7 rows
        paged.ensure_append_page(slot, pos)
    assert len(paged.tables[slot]) == 3  # positions [0, 17) -> 3 pages
    released = paged.truncate_slot(slot, 12)  # accept 2 rows, reject 5
    assert released == 1 and len(paged.tables[slot]) == 2
    pool = paged.pool
    assert pool.num_live == 2
    assert len(pool.free_list) + len(pool.evictable) + pool.num_live == pool.num_blocks
    assert paged.truncate_slot(slot, 12) == 0  # idempotent


# ----------------------------------------------- headroom clamp (parking) --


def test_spec_draft_clamped_at_cache_headroom(tiny):
    """Satellite: the contiguous parked-write trick relies on live KV never
    occupying row max_len - 1.  With prompt + max_new == max_len the final
    rounds leave less headroom than the draft depth — the clamp must keep
    every live verify row <= max_len - 2 (the engine asserts it per round)
    while the stream stays bit-identical."""
    cfg, api, params = tiny
    rng = np.random.default_rng(3)
    pat = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
    prompts = [np.tile(pat, 4)]  # 20 tokens; 20 + 12 == max_len == 32
    _, _, ref = _serve(cfg, params, prompts, layout="contiguous",
                       max_new=12, max_len=32)
    _, stats, got = _serve(cfg, params, prompts, layout="contiguous",
                           max_new=12, max_len=32, spec=8)
    assert got == ref and stats.accepted_tokens > 0


def test_spec_unclamped_draft_trips_the_parking_assertion(tiny):
    """Regression guard for the clamp itself: an (artificially) unclamped
    draft that would write live KV at row max_len - 1 must be caught by
    the verify round's assertion, not silently corrupt the parked row."""
    cfg, api, params = tiny
    eng = EngineCore(cfg, params, n_slots=1, max_len=32, prompt_len=12,
                     mode="static", cache_layout="contiguous", spec_decode=16)
    rng = np.random.default_rng(0)
    eng.submit(Request("r0", rng.integers(0, cfg.vocab_size, 20).astype(np.int32),
                       max_new=12))
    k = 11  # slot length starts at 20: rows reach 20 + 11 = 31 == max_len - 1
    eng.runner.draft_for = lambda req, slot: np.zeros((k,), np.int32)
    with pytest.raises(AssertionError):
        eng.run(max_rounds=4)


# -------------------------------------------------------- streaming API ----


def test_generate_streams_multi_token_deltas(tiny):
    cfg, api, params = tiny
    eng = EngineCore(cfg, params, n_slots=2, max_len=64, prompt_len=12,
                     mode="static", cache_layout="paged", block_size=8,
                     spec_decode=4)
    rng = np.random.default_rng(3)
    pat = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    deltas = []
    for out in eng.generate(np.tile(pat, 4), max_new=12, request_id="g"):
        deltas.append(list(out.new_token_ids))
        last = out
    toks = [t for d in deltas for t in d]
    assert last.finished and len(toks) == 12
    assert toks == eng.finished["g"].out_tokens
    assert max(len(d) for d in deltas) > 1  # speculation produced real blocks
