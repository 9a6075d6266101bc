"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig6 table5
    PYTHONPATH=src python -m benchmarks.run --quick    # CI perf-gate set

Each benchmark prints ``name,us_per_call,derived`` CSV rows; the full set
is also written to results/bench.csv (override with ``--out``).

``--quick`` runs the reduced scheduler matrix (fewer tenants/reps via
``BENCH_QUICK=1``) that ``benchmarks.check_regression`` compares against
the committed results/bench.csv in the CI ``perf-gate`` job — the row
names intersect the full run's, the timings are just cheaper.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import List

SUITES = {
    "fig6": ("benchmarks.sharing_workloads",
             "multi-tenant sharing modes (Fig 6 / Table 4)"),
    "fig7": ("benchmarks.standalone_overhead",
             "standalone fencing overhead (Fig 7/8)"),
    "fig9": ("benchmarks.instruction_delta",
             "instrumentation footprint (Fig 9)"),
    "fig10": ("benchmarks.fence_vs_intensity",
              "fence overhead vs intensity (Fig 10)"),
    "table5": ("benchmarks.interception_cost",
               "interception cost (Table 5)"),
    "table6": ("benchmarks.implicit_calls",
               "implicit library calls (Table 6)"),
    "mem": ("benchmarks.manager_memory",
            "context-memory footprint (§2.2)"),
    "sched": ("benchmarks.scheduler_throughput",
              "batched launch scheduler vs round-robin drain (§4.2.4)"),
    "fault": ("benchmarks.fault_containment",
              "fault containment: detection latency + co-tenant throughput"),
    "elastic": ("benchmarks.elastic_sharing",
                "elastic vs static partition packing over a churn trace"),
    "slo": ("benchmarks.slo_isolation",
            "SLO isolation: tenant classes vs adversarial best-effort"),
    "compress": ("benchmarks.compression",
                 "cross-pod int8 gradient compression (beyond-paper)"),
    "serve_smoke": ("benchmarks.serve_smoke",
                    "serve-path smoke timings (the four CI configs)"),
    "serve_cont": ("benchmarks.serve_continuous",
                   "continuous batching vs lockstep/independent serving"),
    "production": ("benchmarks.production_trace",
                   "trace-driven production macro-bench (mixed fleet, "
                   "SLO ledger report)"),
    "roofline": ("benchmarks.roofline", "dry-run roofline table"),
}

#: the suites a --quick run times (must emit rows whose names intersect
#: the committed baseline so check_regression has something to compare).
#: mem rows gate=abs (deterministic byte counts), elastic rows gate=skip
#: (the packing ratio is asserted inside the suite itself), slo gates
#: its deterministic 1+p99 row (gate=abs) and asserts its bars in-suite,
#: production gates its quick/full-invariant 1+LC-violations row
#: (gate=abs) with throughput rows gate=skip self-asserted
QUICK_SUITES = ["sched", "fault", "mem", "elastic", "slo", "serve_cont",
                "production"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("suites", nargs="*",
                    help=f"suites to run (default: all); known: "
                         f"{list(SUITES)}")
    ap.add_argument("--quick", action="store_true",
                    help="reduced CI matrix (BENCH_QUICK=1, sched+fault)")
    ap.add_argument("--out", default="results/bench.csv",
                    help="CSV output path")
    args = ap.parse_args()
    from repro.launch.compile_cache import place_compile_cache
    place_compile_cache()

    if args.quick:
        os.environ["BENCH_QUICK"] = "1"
    want = args.suites or (QUICK_SUITES if args.quick else list(SUITES))
    rows: List[str] = []
    failed: List[str] = []
    for key in want:
        if key not in SUITES:
            print(f"unknown suite {key!r}; known: {list(SUITES)}")
            continue
        mod_name, desc = SUITES[key]
        print(f"\n=== {key}: {desc} ===")
        t0 = time.time()
        mod = __import__(mod_name, fromlist=["main"])
        try:
            mod.main(rows)
        except Exception as e:  # run the rest, then exit non-zero
            rows.append(f"{key}.ERROR,0,{type(e).__name__}:{e}")
            print(rows[-1])
            failed.append(key)
        print(f"--- {key} done in {time.time() - t0:.1f}s")
    out_dir = os.path.dirname(args.out)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    with open(args.out, "w") as f:
        f.write("name,us_per_call,derived\n")
        f.write("\n".join(rows) + "\n")
    print(f"\n{len(rows)} rows -> {args.out}")
    if failed:
        sys.exit(f"suites that raised: {failed}")


if __name__ == "__main__":
    main()
