"""Serving from resident packed ternary weights: the engine ternarizes and
2-bit packs bitnet's linears once when it is built, every phase program
reads the packed weights, and the engine reports what it holds."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core.phase_engine import PhaseEngine
from repro.models import get_model
from repro.models import transformer as T
from repro.obs.engine import engine_registry
from repro.quant.kv_quant import quantize_kv_tree
from repro.quant.ternary import TernaryWeight
from repro.serving import EngineCore, Request

PROMPT = 16  # = the engine's prompt quantum, so prefill runs unpadded
MAX_LEN = 40


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced_config("bitnet-730m", num_layers=2, d_model=64, vocab_size=256,
                         num_heads=4, num_kv_heads=2)
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def _linears(params):
    return {f"{blk}/{name}": lin["w"] for blk in ("attn", "mlp")
            for name, lin in params["layers"][blk].items()}


def test_runner_holds_packed_weights_and_reports_them(tiny):
    cfg, params = tiny
    eng = EngineCore(cfg, params, n_slots=2, max_len=MAX_LEN, prompt_len=PROMPT)
    held = _linears(eng.runner.params)
    assert len(held) == 7
    assert all(isinstance(w, TernaryWeight) for w in held.values())
    assert all(w.packed.shape[0] == cfg.num_layers for w in held.values())
    assert not any(isinstance(w, TernaryWeight) for w in _linears(params).values())
    n = 7 * cfg.num_layers
    for stats in (eng.stats.snapshot(), eng.snapshot()):
        assert (stats["packed_linears"], stats["latent_linears"]) == (n, 0)
        assert stats["kernel_linears"] == 0
        assert stats["weight_bytes"] > 0
    eng.reset_stats()
    assert (eng.stats.packed_linears, eng.stats.latent_linears) == (n, 0)
    text = engine_registry(eng).prometheus_text()
    assert re.search(rf"^repro_engine_packed_linears {n}(\.0)?$", text, re.M)
    # the CPU serves them through the reference, not the compiled kernel
    assert re.search(r"^repro_engine_kernel_linears 0(\.0)?$", text, re.M)
    assert re.search(r"^repro_engine_latent_linears 0(\.0)?$", text, re.M)
    wb = float(re.search(r"^repro_engine_weight_bytes (\S+)$", text, re.M).group(1))
    assert wb == eng.stats.weight_bytes
    # packed: a quarter byte per linear weight, not the latent float32's four
    latent_bytes = sum(w.size * 4 for w in _linears(params).values())
    packed_bytes = sum(w.packed.size + w.scale.size * 4 for w in held.values())
    assert packed_bytes * 15 < latent_bytes


def test_bf16_engine_holds_no_ternary_weights():
    cfg = reduced_config("qwen2.5-14b", num_layers=2, d_model=64, vocab_size=256)
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    eng = EngineCore(cfg, params, n_slots=2, max_len=MAX_LEN, prompt_len=PROMPT)
    assert eng.runner.params is params
    snap = eng.stats.snapshot()
    assert (snap["packed_linears"], snap["latent_linears"], snap["kernel_linears"]) == (0, 0, 0)


def _weight_quant_f32_shapes(cfg, params_abstract):
    """float32 shapes of the decode program's instructions under the
    ``weight_quant`` scope that a weight matrix (or its layer stack) has."""
    prog = PhaseEngine(cfg, max_len=MAX_LEN).decode_program(params_abstract, 2, MAX_LEN)
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 2, MAX_LEN))
    i32 = jax.ShapeDtypeStruct((2,), jnp.int32)
    text = prog.fn.lower(params_abstract, i32, cache, i32).compile().as_text()
    weights = {tuple(w.shape[1:]) for w in _linears(jax.eval_shape(
        lambda: get_model(cfg).init(cfg, jax.random.PRNGKey(0), dtype=jnp.float32))).values()}
    found = set()
    for line in text.splitlines():
        if "/weight_quant/" not in line:
            continue
        for dims in re.findall(r"f32\[([\d,]+)\]", line.split(" = ", 1)[-1].split("metadata=")[0]):
            shape = tuple(int(d) for d in dims.split(","))
            if shape[-2:] in weights:
                found.add(shape)
    return found


def test_decode_program_reads_no_float_weight_under_weight_quant(tiny):
    cfg, params = tiny
    eng = EngineCore(cfg, params, n_slots=2, max_len=MAX_LEN, prompt_len=PROMPT)
    assert _weight_quant_f32_shapes(cfg, eng.runner._pa) == set()
    # the latent tree, as the engine received it, would re-quantize the
    # float32 weights in the program: the check sees that
    assert _weight_quant_f32_shapes(cfg, jax.eval_shape(lambda: params))


def _direct_greedy(cfg, params, prompts, max_new, kv_dtype):
    """The model called directly, batch of all prompts, as the static
    engine runs it: prefill each prompt, pad its KV to MAX_LEN, decode."""
    pre = jax.jit(lambda p, t: T.forward_prefill(p, t, cfg))
    dec = jax.jit(lambda p, t, c, n: T.decode_step(p, t, c, n, cfg))
    firsts, kvs = zip(*(pre(params, jnp.asarray(p)[None]) for p in prompts))

    def relay(*xs):  # (L, 1, H, S, D) each -> (B, L, H, MAX_LEN, D)
        x = jnp.concatenate(xs, axis=1)
        pad = [(0, 0)] * x.ndim
        pad[-2] = (0, MAX_LEN - x.shape[-2])
        return jnp.moveaxis(jnp.pad(x, pad), 0, 1)

    cache = quantize_kv_tree(jax.tree.map(relay, *kvs), kv_dtype)
    if kv_dtype == "fp":  # stored in the engine's cache dtype
        cache = jax.tree.map(lambda x, c: x.astype(c.dtype), cache,
                             T.init_cache(cfg, len(prompts), MAX_LEN))
    tok = jnp.asarray([int(jnp.argmax(lg[0])) for lg in firsts], jnp.int32)
    lengths = jnp.full((len(prompts),), PROMPT, jnp.int32)
    out = [tok]
    for _ in range(max_new - 1):
        logits, cache = dec(params, tok, cache, lengths)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lengths = lengths + 1
        out.append(tok)
    return np.stack([np.asarray(t) for t in out], axis=1)


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_engine_greedy_equals_model_on_converted_weights(tiny, layout, kv_dtype):
    cfg, params = tiny
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, PROMPT).astype(np.int32) for _ in range(2)]
    max_new = 6
    eng = EngineCore(cfg, params, n_slots=2, max_len=MAX_LEN, prompt_len=PROMPT,
                     mode="static", cache_layout=layout, block_size=8, kv_dtype=kv_dtype)
    for i, p in enumerate(prompts):
        eng.submit(Request(f"r{i}", p.copy(), max_new=max_new))
    eng.run()
    served = np.stack([eng.finished[f"r{i}"].out_tokens for i in range(2)])
    want = _direct_greedy(cfg, T.convert_for_inference(cfg, params), prompts, max_new, kv_dtype)
    np.testing.assert_array_equal(served, want)


def test_kernel_linears_counts_what_the_kernel_serves(tiny, monkeypatch):
    """0 on the CPU; every packed matrix where Mosaic compiles the kernel
    (the backend steered as a TPU's here); none in a program that GSPMD
    partitions over several devices."""
    import repro.kernels as kernels

    cfg, params = tiny
    conv = T.convert_for_inference(cfg, params)
    n = 7 * cfg.num_layers
    assert T.kernel_linears(cfg, conv) == 0
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    assert T.kernel_linears(cfg, conv) == n
    assert T.kernel_linears(cfg, params) == 0  # latent weights: no packed matrix
    with kernels.partitioned():
        assert T.kernel_linears(cfg, conv) == 0
    bf16 = reduced_config("qwen2.5-14b", num_layers=2, d_model=64, vocab_size=256)
    assert T.kernel_linears(bf16, get_model(bf16).init(bf16, jax.random.PRNGKey(0))) == 0


def test_linear_apply_takes_no_kernel_switch():
    import inspect

    from repro.layers.linear import linear_apply

    assert "use_pallas" not in inspect.signature(linear_apply).parameters
    w = {"w": jnp.ones((8, 4), jnp.float32)}
    with pytest.raises(TypeError):
        linear_apply(w, jnp.ones((2, 8), jnp.float32), reduced_config("bitnet-730m").quant,
                     use_pallas=True)
