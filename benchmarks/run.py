"""Benchmark harness entry: ``PYTHONPATH=src python -m benchmarks.run``.

One module per paper table/figure (DESIGN.md §6).  Each prints a markdown
table and persists raw rows under results/bench/.  Modules that need the
dry-run artifacts degrade gracefully when results/dryrun is incomplete.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from . import (
    beyond_paper,
    chunked_prefill_interleave,
    disagg_interference,
    dse_sweep,
    fig5_overlap,
    fig6_decode_throughput,
    fig6_ttft,
    kv_quant_sweep,
    paged_vs_contiguous,
    policy_compare,
    roofline_report,
    serving_e2e,
    spec_decode,
    table1_comparison,
    table2_resources,
    tracing_overhead,
    traffic_storm,
)
from .common import render
from repro.common.compile_cache import enable_compile_cache

BENCHES = {
    "roofline_report": roofline_report,
    "dse_sweep": dse_sweep,
    "fig6a_decode_throughput": fig6_decode_throughput,
    "fig6b_ttft": fig6_ttft,
    "table1_comparison": table1_comparison,
    "table2_resources": table2_resources,
    "fig5_overlap": fig5_overlap,
    "serving_e2e": serving_e2e,
    "paged_vs_contiguous": paged_vs_contiguous,
    "kv_quant_sweep": kv_quant_sweep,
    "chunked_prefill_interleave": chunked_prefill_interleave,
    "spec_decode": spec_decode,
    "policy_compare": policy_compare,
    "traffic_storm": traffic_storm,
    "tracing_overhead": tracing_overhead,
    "disagg_interference": disagg_interference,
    "beyond_paper": beyond_paper,
}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--only", nargs="*", choices=list(BENCHES), default=None)
    args = p.parse_args(argv)
    names = args.only or list(BENCHES)
    # before JAX starts its backends: disagg_interference runs in this
    # process and needs two devices (the flag only affects the CPU backend)
    disagg_interference.ensure_host_devices(2)
    enable_compile_cache()

    failures, all_checks = [], []
    for name in names:
        t0 = time.time()
        try:
            result = BENCHES[name].run()
            print(render(result))
            print(f"\n[{name}: {time.time()-t0:.1f}s]")
            for k, v in result.get("checks", {}).items():
                all_checks.append((name, k, v))
        except Exception as e:
            failures.append((name, repr(e)))
            print(f"\n## {name}\nFAILED: {e}")
            traceback.print_exc()

    print("\n# Claim-check summary")
    for name, k, v in all_checks:
        print(f"  [{'PASS' if v else 'FAIL'}] {name}: {k}")
    if failures:
        print(f"\n{len(failures)} benchmark(s) failed: {[f[0] for f in failures]}")
        return 1
    n_fail = sum(1 for _, _, v in all_checks if not v)
    print(f"\n{len(all_checks) - n_fail}/{len(all_checks)} claim checks pass.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
