"""End-to-end serving driver: PD-Swap vs static engine on batched requests.

The paper's headline experiment (Fig. 6) as a runnable program: the same
model and request stream served by (a) the PD-Swap engine — phase-
specialized prefill/decode programs, latency-overlapped logic swap — and
(b) the static TeLLMe-style engine.  Greedy outputs must match exactly;
timings on this host validate the mechanism (performance claims for the
TPU target come from the roofline benchmarks).

    PYTHONPATH=src python examples/serve_pdswap.py [--requests 8]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import reduced_config
from repro.models import get_model
from repro.serving.engine import Request, ServingEngine


def drive(mode, cfg, params, prompts, args):
    eng = ServingEngine(cfg, params, n_slots=args.slots, max_len=args.max_len,
                        prompt_len=args.prompt_len, mode=mode)
    for i, prompt in enumerate(prompts):
        eng.submit(Request(f"req-{i}", prompt, max_new=args.max_new))
    stats = eng.run()
    return eng, stats


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--requests", type=int, default=6)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=24)
    p.add_argument("--max-new", type=int, default=12)
    p.add_argument("--max-len", type=int, default=64)
    args = p.parse_args()

    cfg = reduced_config("bitnet-730m", num_layers=3, d_model=192, vocab_size=2048,
                         num_heads=6, num_kv_heads=2)
    api = get_model(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=args.prompt_len).astype(np.int32)
               for _ in range(args.requests)]

    eng_pd, st_pd = drive("pdswap", cfg, params, prompts, args)
    eng_st, st_st = drive("static", cfg, params, prompts, args)

    same = all(eng_pd.finished[k].out_tokens == eng_st.finished[k].out_tokens
               for k in eng_pd.finished)
    print(f"{'engine':8s} {'decode tok':>10s} {'decode tok/s':>12s} {'swaps':>6s} {'prefill s':>10s}")
    for name, st in (("pdswap", st_pd), ("static", st_st)):
        print(f"{name:8s} {st.decode_tokens:10d} {st.decode_tput():12.1f} "
              f"{st.swaps:6d} {st.t_prefill:10.2f}")
    print(f"greedy outputs identical across engines: {same}")
    assert same, "PD-Swap must be bit-identical to the static engine"


if __name__ == "__main__":
    main()
