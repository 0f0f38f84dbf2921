#!/usr/bin/env python3
"""A traced window read with the program's own spans.

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s> [--keep DIR]

Runs one window of the cell as ``bench/run.py --trace 1`` does, but with
``repro.obs.trace.Tracer(profiler=True)`` installed before the cell is
built, so that the engine's, the dispatch's and the compile cache's
spans are in the profile and in the tracer's buffer from set-up on. Then
reads, beside the cell's own metrics (``bench/metrics/``):

* ``admit_wait_p90_ms``: p90 over the window's requests of scheduled
  arrival to admission (the close of ``request.queued``);
* ``prefill_cache_write_share``: device self time of the operations
  under the model scope ``attn/cache_write`` in the prefill programs,
  over the prefill programs' device time;
* ``device_idle_with_work_share``: the device's idle time in the window
  while a ``serve.step`` span was open, over the window;
* ``setup_compile_s``: seconds of ``compile.bucket`` and
  ``compile.exact`` spans before the first timed request;

and the device seconds of every scope, the host's accounting of the
window, and the end-to-end readings. No reference check runs:
``bench/run.py`` decides ``correct``. The last line of standard output is
one JSON object. ``--keep`` writes the profile, the tracer's buffer
(Chrome trace JSON), the run record and each prefill and decode program's
optimised HLO text there. Exits 3 without a TPU.
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

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench.lib import harness, spans  # noqa: E402
from bench.lib import trace as trace_lib  # noqa: E402
from bench.lib.readings import percentile, window_requests  # noqa: E402
from repro.obs import metrics  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402


def programs(eng):
    """The optimised HLO text of each prefill bucket's and the decode
    step's program, lowered again at the shapes the dispatch launched
    (the persistent compilation cache supplies the executables)."""
    out = {}
    for (b, s), entry in eng._prefill_fn.bucket_programs().items():
        rows = jax.tree.map(lambda c: c[:, :b], eng.cache)
        z = np.zeros((b,), np.int32)
        out[f"prefill_{b}x{s}"] = entry.lower(
            eng.params, rows, np.zeros((b, s), np.int32), z, z).compile()
    for _, entry in eng._decode_fn.bucket_programs().items():
        n = eng.n_slots
        out["decode"] = entry.lower(
            eng.params, eng.cache, jnp.zeros((n, 1), np.int32),
            jnp.zeros((n,), np.int32), jnp.zeros((n,), bool)).compile()
    return {k: c.as_text() for k, c in out.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    harness.use_compile_cache(ROOT)
    tracer = obs_trace.install(obs_trace.Tracer(profiler=True))
    try:
        cell = harness.Cell(ROOT, args.workload, traced=True)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    cell.build(args.seed)
    setup_s = time.perf_counter() - T_START
    tdir = tempfile.mkdtemp(prefix="bench_spans_")
    keep = pathlib.Path(args.keep) if args.keep else None
    try:
        run = cell.run(args.seed, args.seconds, trace_dir=tdir)
        obs_trace.clear()
        run["setup_s"] = setup_s
        run["trace"] = trace_lib.reduce_events(trace_lib.load_events(tdir))
        if keep:
            keep.mkdir(parents=True, exist_ok=True)
            shutil.copytree(tdir, keep / "profile", dirs_exist_ok=True)
            tracer.export_chrome_trace(keep / "tracer.json")
            (keep / "run.json").write_text(json.dumps(run, default=str))
        hlo = programs(cell.engine)
        if keep:
            for k, text in hlo.items():
                (keep / f"{k}.hlo.txt").write_text(text)
        ops = spans.load(tdir, spans.op_names(hlo.values()))
        red = spans.reduce(ops)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    reqs = window_requests(run)
    waits = spans.admit_waits(tracer.events, reqs)
    prefill_s = run["trace"]["program_s"]["prefill"]
    scoped = sum(1 for o in ops if o.scope)
    program = {
        "admit_wait_p90_ms": 1000.0 * percentile(
            [waits.get(r["rid"], run["end"] - r["arrival"]) for r in reqs],
            90),
        "prefill_cache_write_share": 100.0 * red["scope_s"]["prefill"].get(
            "attn/cache_write", 0.0) / prefill_s if prefill_s else None,
        "device_idle_with_work_share":
            100.0 * red["idle_with_work_s"] / red["window_s"],
        "setup_compile_s": spans.setup_compile_s(tracer.events),
    }
    e2e = harness.read_metrics(ROOT, cell.bench["end_to_end"], run)
    layer = harness.read_metrics(
        ROOT, harness.cell_metrics(cell.bench, args.workload, True), run)
    result = {
        "workload": args.workload, "seed": args.seed,
        "device": cell.device, "program": program,
        "end_to_end": {k: v["value"] for k, v in e2e.items()},
        "per_layer": {k: v["value"] for k, v in layer.items()},
        "scope_s": red["scope_s"],
        "scoped_ops": scoped,
        "window_s": red["window_s"], "idle_s": red["idle_s"],
        "idle_with_work_s": red["idle_with_work_s"],
        "accounted_share": red["accounted_s"] / red["window_s"],
        "serve_steps_in_window": red["serve_steps"],
        "busy_s": run["trace"]["busy_s"],
        "program_s": run["trace"]["program_s"],
        "idle_gaps": run["trace"]["idle_gaps"],
        "compile_seconds": {fp: st["compile_seconds"] for fp, st in
                            metrics.snapshot()["compile"].items()},
        "window_compiles": run["window_compiles"],
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
