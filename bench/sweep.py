"""The knee of an open-loop cell: the highest arrival rate its engine
sustains, found once by a sweep on the chip.

    python3 bench/sweep.py --workload <cell> --rates 1,2,3 --seconds 30

In one process and over one compiled engine, for each rate: the cell's
traffic at that rate (its pre-roll, then ``--seconds`` measured), then the
time to first token, the gap between tokens, the tokens per second and the
backlog at the window's end.  One JSON line per rate.  Above the knee the
backlog grows through the window and the time to first token with it.
The benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from bench.harness import cell_run, session, spec, traffic
    from bench.harness import window as win
    from bench.harness.weights import make_weights

    cell = spec.load_cell(args.workload)
    dev = cell_run.devices_for(cell.chips, True)[0]
    cell_run.use_compile_cache()
    c, mix = cell.config, cell.traffic
    cfg = spec.model_config(c)
    params = make_weights(c, args.seed, dev)
    eng = session.build_engine(cfg, params, cell.engine)
    session.Session(eng, args.seed, cfg.vocab_size).warm_up(mix)
    for rate in [float(r) for r in args.rates.split(",")]:
        sess = session.Session(eng, args.seed, cfg.vocab_size)
        items = traffic.open_loop(mix, args.seconds, rate=rate)
        t = time.perf_counter()
        t0, t1 = session.run_open(sess, items, float(mix["pre_roll_s"]), args.seconds,
                                  session.Hooks())
        streams = list(sess.streams.values())
        due = win.due_in(streams, t0, t1)
        ttft, gaps = win.ttft(streams, t0, t1), win.itl(streams, t0, t1)
        line = {
            "rate": rate, "due": len(due),
            "first_token_by_end": sum(1 for s in due if s.emits and s.emits[0][0] <= t1),
            "waiting_at_end": len(eng.scheduler.queue),
            "ttft_p50_ms": 1e3 * win.percentile(ttft, 50),
            "ttft_p90_ms": 1e3 * win.percentile(ttft, 90),
            "itl_p50_ms": 1e3 * win.percentile(gaps, 50),
            "itl_p95_ms": 1e3 * win.percentile(gaps, 95),
            "output_tokens_per_s": win.tokens(streams, t0, t1) / (t1 - t0),
            "lateness_max_ms": 1e3 * max(win.lateness(streams, t0, t1)),
            "seconds": time.perf_counter() - t,
        }
        print(json.dumps(line), flush=True)
        for s in streams:
            if s.done is None:
                eng.abort(s.rid)
    return 0


if __name__ == "__main__":
    sys.exit(main())
