"""Prefill's share of the chip's peak, in percent: the operations the
prompts whose first token came in the window need (every token through the
model, causal attention at each position, as the configuration's family
counts them) at the published peaks, over the engine's t_prefill in the
window.  Requests whose prefill straddles an edge of the window put the two
counts a little out of step."""
from bench.harness import costs, spec


def read(ctx):
    s = ctx.stats
    if ctx.peak is None or not s["t_prefill"]:
        return None
    done = [st.prompt_len for st in ctx.streams
            if st.emits and ctx.t0 < st.emits[0][0] <= ctx.t1]
    if not done:
        return None
    fam = spec.family(ctx.config)
    work = costs.Work()
    for n in done:
        work = work + fam.prefill(ctx.config, n)
    compute = costs.Work(work.int8_ops, work.bf16_flops, 0.0)
    return 100.0 * compute.seconds(ctx.peak) / s["t_prefill"]
