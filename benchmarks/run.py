"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Run:
    PYTHONPATH=src python -m benchmarks.run [--only fig3,table2,...]

``--smoke`` runs every suite end-to-end at tiny sizes (one cheap
workload, 1-2 iterations, CPU-friendly).  The numbers are meaningless;
the point is that CI executes the real benchmark code paths on every
push so they cannot bit-rot silently.
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import List

from repro.api.persistent_cache import enable_persistent_cache

from . import (bench_buffers, bench_compile_overhead, bench_control_flow,
               bench_dist, bench_fig3_frameworks, bench_fig4_static_gap,
               bench_obs, bench_roofline, bench_serve, bench_table2_nimble,
               bench_table3_kernels)

SUITES = {
    "fig3": bench_fig3_frameworks.main,
    "table2": bench_table2_nimble.main,
    "table3": bench_table3_kernels.main,
    "fig4": bench_fig4_static_gap.main,
    "compile": bench_compile_overhead.main,
    "buffers": bench_buffers.main,
    "roofline": bench_roofline.main,
    "serve": bench_serve.main,
    "dist": bench_dist.main,
    "ctrl": bench_control_flow.main,
    "obs": bench_obs.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated suite names")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, 1-2 iters, no GPU assumptions (CI)")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(SUITES)
    enable_persistent_cache()

    print("name,us_per_call,derived")
    csv: List[str] = []
    failed = False
    for name in names:
        t0 = time.time()
        try:
            SUITES[name](csv, smoke=args.smoke)
        except Exception as e:  # pragma: no cover
            import traceback
            traceback.print_exc()
            csv.append(f"{name}_ERROR,,{e!r}")
            failed = True
        csv.append(f"{name}_suite_seconds,,{time.time() - t0:.1f}")
    print("\n".join(csv))
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
