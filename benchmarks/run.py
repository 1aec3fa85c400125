"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (paper-figure analogues measured
on CPU + TPU roofline models; see each module's docstring for the mapping).

  bench_column_solve — paper Fig. 15 axis: ref vs Pallas cell-layout column
                     solvers (block-Thomas + matrix-free r/w) over nl/columns
  bench_horizontal_rhs — the fused horizontal-RHS pipeline vs the seed
                     per-call path vs the Pallas lateral-flux kernel over
                     nl in {4,8,16}; also writes BENCH_horizontal.json
                     (machine-readable perf trajectory of the hottest loop)
  fig13_resolution — paper Fig. 13 (perf vs horizontal resolution)
  fig15_layers     — paper Fig. 15 (layer-count scaling / occupancy)
  fig16_scaling    — paper Figs. 16-18 (multi-device scaling, Amdahl)
  kernel_util      — paper Fig. 14 / §4.1 (per-kernel utilisation) + the
                     §3.3 dispatch-latency experiment
  roofline_table   — the 40-cell dry-run roofline table (EXPERIMENTS.md)
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmarks")
    ap.add_argument("--skip-slow", action="store_true",
                    help="skip the multi-process scaling benchmark")
    args = ap.parse_args()

    from repro.launch import compile_cache
    compile_cache.configure()
    from . import (bench_column_solve, bench_horizontal_rhs, fig13_resolution,
                   fig15_layers, fig16_scaling, kernel_util, roofline_table)
    benches = {
        "kernel_util": kernel_util.run,
        "bench_column_solve": bench_column_solve.run,
        "bench_horizontal_rhs": bench_horizontal_rhs.run,
        "fig13_resolution": fig13_resolution.run,
        "fig15_layers": fig15_layers.run,
        "fig16_scaling": fig16_scaling.run,
        "roofline_table": roofline_table.run,
    }
    if args.only:
        names = args.only.split(",")
    else:
        names = list(benches)
        if args.skip_slow:
            names.remove("fig16_scaling")
    print("name,us_per_call,derived")
    ok = True
    for n in names:
        try:
            benches[n]()
        except Exception:
            traceback.print_exc()
            print(f"{n},0,FAILED")
            ok = False
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
