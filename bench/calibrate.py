"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 50 \\
        [--control [PRECISION]] [--out calib.jsonl]

One process runs ``bench.harness.cell_run.execute``, the whole timed path
of a run, once for each seed, and prints one JSON line per seed: the
served tokens' widest logit gap against the float32 reference (the number
a run compares) and, with ``--control``, the same reading for the tokens
a lower-precision reference ranks first at the same positions, with the
run's ``correct`` decided on it: the configuration's ``"control"``, or the
precision named, one of its family's ``CONTROLS``.
The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", nargs="?", const="", default=None,
                    help="decide correct on a control precision (default: the configuration's)")
    ap.add_argument("--out", default=None, help="append the JSON lines here too")
    args = ap.parse_args(argv)

    from bench.harness import cell_run, spec

    cell = spec.load_cell(args.workload)
    control = args.control
    if control == "":
        control = cell.config["control"]
    controls = spec.family(cell.config).CONTROLS
    if control is not None and control not in controls:
        ap.error(f"control {control!r} is not one of {controls}")  # before a window is spent
    t_start = T_START
    for seed in [int(s) for s in args.seeds.split(",")]:
        r = cell_run.execute(cell, seed, args.seconds, False, t_start=t_start,
                             control=control)
        line = {"seed": seed, "control": control, "correct": r["correct"],
                **{k: r[k] for k in ("readings", "check", "metrics")},
                "seconds": time.perf_counter() - t_start}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
