"""Traced runs of one cell that keep more of the trace's reading, on the
chip.

    python3 bench/trace_layers.py --workload <cell> --runs SEED:SPANS[,...] \\
        --seconds <s> [--record PATH] [--out PATH]

Each run is ``bench/run.py --trace 1`` (``cell_run.execute``), whose traced
slice already holds the engine's spans and is read by
``bench.harness.layers``.  SPANS 1 is that run as it stands; SPANS 2 also
records ``repro.obs.trace.TRACER`` from set-up on (the window too, which
prices the spans).  The JSON line adds ``weight_quant_ms`` to the metrics
and ``scopes``, ``runs``, ``spans`` and ``clock_shift_ms`` to the
breakdown.  ``--record`` writes the first 0.3 s of the first run's slice as
gzipped events, the form ``bench/tests/data`` keeps.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

RECORD_S = 0.3
METRICS = ({"name": "weight_quant_ms", "unit": "ms"},)


def clip(ev: dict, seconds: float) -> dict:
    """The events of the first ``seconds`` of the traced slice."""
    from bench.harness import trace

    lo, _ = next((s, e) for n, s, e in ev["host"] if n == trace.WINDOW)
    hi = lo + seconds * 1e9

    def inside(items):
        return [list(x) for x in items if x[1] < hi and x[2] > lo]

    host = [[n, s, min(e, hi) if n == trace.WINDOW else e] for n, s, e in inside(ev["host"])]
    return {"ops": {c: inside(v) for c, v in ev["ops"].items()},
            "modules": {c: inside(v) for c, v in ev["modules"].items()},
            "host": host}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", required=True, help="SEED:SPANS pairs, comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", default=None, help="write the first run's slice here")
    ap.add_argument("--out", default=None, help="append the JSON lines here too")
    args = ap.parse_args(argv)
    runs = [tuple(int(x) for x in item.split(":")) for item in args.runs.split(",")]
    if any(spans not in (1, 2) for _, spans in runs):
        ap.error("SPANS is 1 (the engine's spans in the traced slice) or 2 (from set-up on)")

    import dataclasses

    from bench.harness import cell_run, layers, spec
    from repro.obs.trace import TRACER

    state = {"reduced": None, "record": args.record}

    class Reader:
        events_from_xplane = staticmethod(layers.events_from_xplane)

        @staticmethod
        def reduce_events(ev):
            if state["record"]:
                with gzip.open(state["record"], "wt") as f:
                    json.dump(clip(ev, RECORD_S), f)
                state["record"] = None
            state["reduced"] = layers.reduce_events(ev)
            return state["reduced"]

    cell_run.layers = Reader

    cell = spec.load_cell(args.workload)
    cell = dataclasses.replace(cell, per_layer=cell.per_layer + METRICS)
    for i, (seed, spans) in enumerate(runs):
        def patch(eng, spans=spans):
            if spans == 2:
                TRACER.enable()

        t_start = T_START if i == 0 else time.perf_counter()
        try:
            result = cell_run.execute(cell, seed, args.seconds, True, t_start=t_start,
                                      patch=patch)
        finally:
            TRACER.disable()
            TRACER.clear()
        r = state["reduced"]
        keys = ("scopes", "runs", "spans", "clock_shift_ms")
        result["breakdown"].update({k: r[k] for k in keys})
        result.update(workload=args.workload, seed=seed, spans=spans)
        line = json.dumps(result)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
