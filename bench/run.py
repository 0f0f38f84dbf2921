#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``). The run makes the weights and the
requests from ``--seed``, builds the serving engine and every program the
traffic can reach (set-up, reported as ``setup_s``), measures for
``--seconds`` seconds, then compares a sample of the served tokens with
the float32 reference in ``bench/lib/reference.py``.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler and the metrics are
its per-layer metrics, each read by ``bench/metrics/<name>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``) and, last, ``checks``: each number compared, with
its limit. The same numbers are the last lines of standard error.
Exits 3, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness  # noqa: E402
from bench.lib import trace as trace_lib  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    harness.use_compile_cache(ROOT)
    traced = bool(args.trace)
    try:
        cell = harness.Cell(ROOT, args.workload, traced=traced)
    except harness.NoChip as e:
        log(f"no result: {e}")
        return 3
    log(f"device: {cell.device}")
    cell.build(args.seed)
    setup_s = time.perf_counter() - T_START
    log(f"set-up: {setup_s:.3f} s; warm-up: {json.dumps(cell.warm_info)}")
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    try:
        run = cell.run(args.seed, args.seconds, trace_dir=tdir)
        run["setup_s"] = setup_s
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in cell.devices)
        run["trace"] = (trace_lib.reduce_events(trace_lib.load_events(tdir))
                        if traced else None)
    finally:
        if tdir is not None:
            shutil.rmtree(tdir, ignore_errors=True)
    reqs = run["requests"]
    log(f"window: {len(reqs)} requests, "
        f"{sum(1 for r in reqs if r['failed'])} failed; "
        f"compiles inside the window: {run['window_compiles']}; "
        f"generator lag max "
        f"{max((r['submit'] - r['arrival'] for r in reqs), default=0):.6f} s"
        f"; prefill launches {len(run['prefills'])}, decode steps "
        f"{len(run['decodes'])}, drain ended {run['end']:.3f} s")
    picked = cell.sample(run, args.seed)
    cell.free()
    t0 = time.perf_counter()
    checks = cell.check(run, picked, args.seed)
    log(f"reference: {len(picked)} requests in "
        f"{time.perf_counter() - t0:.3f} s")
    if not traced:
        # every end-to-end reading, those this cell is not judged by too
        log("readings: " + json.dumps(
            harness.read_metrics(ROOT, cell.bench["end_to_end"], run)))
    metrics = harness.read_metrics(
        ROOT, harness.cell_metrics(cell.bench, args.workload, traced), run)
    device = dict(cell.device, memory_peak_bytes=int(peak))
    result = {"correct": harness.is_correct(checks),
              "attempted": len(reqs),
              "failed": sum(1 for r in reqs if r["failed"]),
              "metrics": metrics, "device": device}
    if traced:
        tr = run["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        log(f"trace: program seconds {json.dumps(tr['program_s'])}; "
            f"modules {json.dumps(dict(list(tr['modules_s'].items())[:8]))}")
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
