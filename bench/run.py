"""One run of one benchmark cell on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout that holds ``BENCHMARK.json``, ``bench/``
and the program (``src/``).  The last line of standard output is the result
as one JSON object; the numbers the correctness check compared are the last
lines of standard error.  Exits non-zero, with no result line, when JAX
finds no TPU or fewer chips than the cell asks for.
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
    ap.add_argument("--workload", required=True, help="a cell named in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True, help="seeds weights and traffic")
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace a slice of the window and report per-layer metrics")
    args = ap.parse_args(argv)

    from bench.harness import cell_run, spec

    cell = spec.load_cell(args.workload)
    try:
        result = cell_run.execute(cell, args.seed, args.seconds, bool(args.trace),
                                  t_start=T_START)
    except cell_run.NoChip as e:
        cell_run.log(f"no chip: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
