#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate at which
the waiting queue does not grow across the window.

    python3 bench/sweep.py --workload <name> --seconds <s> \
        --blocks 24 28 32 --seeds 1 2 3

Builds the cell once (``bench/run.py``'s set-up) and runs one window per
block size and seed. A block of ``b`` requests is offered at
``traffic.window_rate(b, seconds)``, the rate at which the window holds
exactly that block, so each seed offers the same work. For each window
it prints the rate, the mean waiting queue in the first and the last
third of the window, the TTFT p50 of the requests that arrived in each
of those thirds, and the end-to-end readings of ``BENCHMARK.json`` other
than ``setup_s``. Run it on the chip. The traffic file's rate and block
are those of the highest knee-free block times about 0.8.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness  # noqa: E402
from bench.lib.readings import percentile  # noqa: E402
from bench.lib.traffic import window_rate  # noqa: E402


def thirds(run, pairs):
    s = run["seconds"]
    return [[x for t, x in pairs if k * s / 3 <= t < (k + 1) * s / 3]
            for k in (0, 2)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--blocks", type=int, nargs="+", required=True)
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
    for block in args.blocks:
        rate = window_rate(block, args.seconds)
        spec = dict(cell.traffic_spec, rate_per_s=rate, block=block)
        for seed in args.seeds:
            run = cell.run(seed, args.seconds, traffic_spec=spec)
            q = thirds(run, run["queue"])
            ttft = thirds(run, [(r["arrival"], (r["tokens"][0] if r["tokens"]
                                                else run["end"])
                                 - r["arrival"]) for r in run["requests"]])
            readings = harness.read_metrics(ROOT, e2e, run)
            print(json.dumps({
                "block": block, "rate_per_s": rate, "seed": seed,
                "requests": sum(1 for r in run["requests"]
                                if r["arrival"] < args.seconds),
                "queue_first_third": sum(q[0]) / max(len(q[0]), 1),
                "queue_last_third": sum(q[1]) / max(len(q[1]), 1),
                "ttft_p50_first": percentile(ttft[0], 50),
                "ttft_p50_last": percentile(ttft[1], 50),
                "drain_s": run["end"] - run["seconds"],
                "window_compiles": run["window_compiles"],
                **{k: v["value"] for k, v in readings.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
