#!/usr/bin/env python3
"""Readings that set a cell's logit-gap limit: the program and its control.

    python3 bench/control.py --workload <name> --seconds <s> --seeds <n> ...

Builds the cell once, then for each seed: new weights, one window of the
cell's own traffic (``bench/run.py``'s window), the same sample of
finished requests that a run compares, and the reference over it, with
the control beside it. The control is the reference computed with every
matrix rounded to float8 (``bench/lib/reference.py``). Prints per seed
the widest gap of the served tokens (the program's reading) and of the
control's first choices; the limit lies between the largest of the
first and the smallest of the second. Each side's checks go through
``harness.is_correct`` as a run's do: ``correct`` is the program's
verdict, ``control_correct`` the control's, put in the program's place
(it has to be false). Each line also gives the window's end-to-end
readings but ``setup_s``. Run it on the chip; the benchmark's own runs never
run the control.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    harness.use_compile_cache(ROOT)
    try:
        cell = harness.Cell(ROOT, args.workload)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    cell.build(args.seeds[0])
    e2e = [m for m in cell.bench["end_to_end"] if m["name"] != "setup_s"]
    for k, seed in enumerate(args.seeds):
        if k:
            cell.set_params(seed)
        run = cell.run(seed, args.seconds)
        picked = cell.sample(run, seed)
        cell.free(cache=False)
        t0 = time.perf_counter()
        checks = cell.check(run, picked, seed, control=True)
        ctrl = harness.control_checks(checks, cell.gaps)
        readings = harness.read_metrics(ROOT, e2e, run)
        print(json.dumps({
            "seed": seed, "requests": len(run["requests"]),
            "compared": len(picked),
            "served_tokens": checks["served_tokens"]["value"],
            "served_gap": max(cell.gaps["served"]),
            "control_gap": max(cell.gaps["control"]),
            "served_gaps": cell.gaps["served"],
            "control_gaps": cell.gaps["control"],
            "failed": checks["failed_requests"]["value"],
            "short": checks["short_answers"]["value"],
            "reference_s": time.perf_counter() - t0,
            "correct": harness.is_correct(checks),
            "control_correct": harness.is_correct(ctrl),
            "limit": checks["logit_gap"]["limit"],
            **{k: v["value"] for k, v in readings.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
