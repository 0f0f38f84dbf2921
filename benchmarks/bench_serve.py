"""Serve-path throughput benchmark: the continuous-batching trajectory.

Drives the serve engine with a synthetic bursty arrival trace
(``VarLenRequestStream.sample_trace``) and measures, per configuration:
tokens/sec, p50/p99 request latency, prefill compile counts, and decode
stall (longest gap between decode launches).  Three comparisons:

* **replay vs single-pass batched prefill** (same FIFO admission): the
  headline win — one 2-D-bucketed launch per admission group instead of
  O(prompt_len) sequential decode-width launches per request;
* **FIFO vs admission policies** (shortest-prompt-first, priority) on the
  batched engine;
* **chunked vs unchunked prefill** on a long-prompt trace: decode stall
  shrinks when prompts are split into chunks interleaved with decode;
* **paged vs fixed-row KV at equal cache memory**: the fixed engine's
  ``max_batch`` rows of ``max_seq`` vs a block pool holding the same
  number of KV positions shared by 4x the slots — short requests stop
  paying for worst-case rows, so concurrency multiplies;
* **speculative (n-gram) vs plain decode** on a repetition-heavy
  long-tail trace: accepted drafts ride one widened verify launch, so
  tokens/sec rises as decode launches fall;
* **fault-hook overhead**: interleaved best-of passes over the same
  trace with fault hooks disabled (``faults.ACTIVE is None``, the
  production state) vs a no-op injector installed.  The installed
  injector is a strict *upper bound* on the disabled-hook cost — every
  site pays the full dispatch — so holding it within 2% of disabled
  throughput (full mode) proves the hooks this PR threaded through the
  hot paths are free when off.

``--chaos`` additionally runs a seeded random-fault pass
(``FaultInjector.chaos``) over a paged-pool engine and asserts graceful
degradation: the engine never raises, every request is retired DONE or
FAILED-with-reason, and the block allocator stays consistent.

Writes ``BENCH_serve.json`` at the repo root.  Throughput is measured on
a second pass over the same trace after a warmup pass, so compile time
never pollutes the steady-state numbers (compile cost is reported
separately).  Asserts (non-zero exit under ``benchmarks.run``): batched
and replay generations are identical, batched tokens/sec beats replay
(≥2x full, ≥1.1x smoke — CI boxes are noisy), chunked prefill reduces
max decode stall on the long-prompt trace (full mode only), the
equal-memory paged engine sustains ≥2x the fixed engine's peak
concurrent slots while completing every request, an unconstrained pool
reproduces the fixed engine's generations bit-exactly, and speculative
decoding matches plain-decode outputs exactly with a tokens/sec win on
the long-tail trace (full mode only).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import time
from typing import Dict, List

import jax
import numpy as np

from disc import FaultInjector, ServeConfig, ServeEngine, faults
from repro.configs import get_config
from repro.data.pipeline import Request, VarLenRequestStream
from repro.models.registry import get_model
from repro.obs import trace as obs_trace

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _trace(vocab, *, n, lo, hi, max_new, seed=0, burst=4):
    stream = VarLenRequestStream(vocab=vocab, min_len=lo, max_len=hi,
                                 seed=seed, distribution="uniform")
    reqs = stream.sample_trace(n, burst=burst, mean_gap=0.02)
    for r in reqs:
        r.max_new_tokens = max_new
    return reqs


def _motif_trace(vocab, *, n, lo, hi, max_new, seed=5, motif=4):
    """Short repeated-motif prompts: the repetition-heavy long tail where
    prompt-lookup drafting earns its keep (the model's greedy
    continuations cycle, so n-gram drafts hit)."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        ln = int(rng.randint(lo, hi + 1))
        pat = rng.randint(0, min(8, vocab), size=motif)
        toks = np.tile(pat, -(-ln // motif))[:ln].astype(np.int32)
        out.append(Request(rid=i, tokens=toks, max_new_tokens=max_new))
    return out


def _run_trace(eng, reqs, max_steps=50_000) -> Dict[int, float]:
    """Feed arrivals as simulated time passes; returns per-request
    latency (idle waits are fast-forwarded, not slept through)."""
    pending = sorted(reqs, key=lambda r: r.arrival)
    arrive: Dict[int, float] = {}
    lat: Dict[int, float] = {}
    skipped = 0.0  # fast-forwarded idle time
    t0 = time.monotonic()
    for _ in range(max_steps):
        if not (pending or eng.queue
                or any(s is not None for s in eng.slots)):
            break
        now = time.monotonic() - t0 - skipped
        if pending and not eng.queue \
                and all(s is None for s in eng.slots) \
                and pending[0].arrival > now:
            skipped -= pending[0].arrival - now  # jump to next arrival
            now = pending[0].arrival
        while pending and pending[0].arrival <= now:
            r = pending.pop(0)
            arrive[r.rid] = max(now, r.arrival)
            eng.submit([r])
        before = len(eng.done)
        eng.step()
        if len(eng.done) > before:
            done_t = time.monotonic() - t0 - skipped
            for rid in eng.done:
                if rid not in lat:
                    lat[rid] = done_t - arrive[rid]
    return lat


def _max_decode_gap(events) -> float:
    """Decode stall: the longest gap between the starts of consecutive
    ``serve.decode_step`` spans that share a request (one waiting on its
    next token across the gap)."""
    steps = [e for e in events if e["name"] == "serve.decode_step"]
    return max((b["ts"] - a["ts"] for a, b in zip(steps, steps[1:])
                if set(a["args"]["rids"]) & set(b["args"]["rids"])),
               default=0.0)


def _measure(model, params, scfg, reqs_fn) -> Dict:
    """Warmup pass (compiles), then a measured pass over the same trace."""
    eng = ServeEngine(model, params, scfg)
    # admission grouping is timing-sensitive (arrival-gated), so one pass
    # may not visit every (B, S) pair the measured pass will: warm until
    # a whole pass adds no compiles (bounded)
    warm_compiles = -1
    for _ in range(4):
        if eng.stats["prefill_compiles"] == warm_compiles:
            break
        warm_compiles = eng.stats["prefill_compiles"]
        _run_trace(eng, reqs_fn())
        eng.done.clear()  # every pass reuses the same trace rids
    warm_compiles = eng.stats["prefill_compiles"]
    eng.reset_stats()
    # the decode stall is read off the measured pass's decode spans (into
    # the run-wide tracer under --trace)
    with obs_trace.tracing(obs_trace.ACTIVE) as tr:
        n0 = len(tr.events)
        lat = _run_trace(eng, reqs_fn())
    st = eng.stats
    vals = sorted(lat.values())
    return {
        "tokens_per_sec": round(st["tokens_per_sec"], 1),
        "p50_latency_s": round(float(np.percentile(vals, 50)), 4),
        "p99_latency_s": round(float(np.percentile(vals, 99)), 4),
        "max_decode_gap_s": round(_max_decode_gap(tr.events[n0:]), 4),
        "prefill_calls": st["prefill_calls"],
        "batched_prefills": st["batched_prefills"],
        "prefill_chunks": st["prefill_chunks"],
        "prefill_compiles": st["prefill_compiles"],
        "prefill_bucket_pairs": st["prefill_bucket_pairs"],
        "warmup_compiles": warm_compiles,
        "steady_state_new_compiles": st["prefill_compiles"] - warm_compiles,
        "peak_active_slots": st["peak_active_slots"],
        "mem_launch_bytes": st["mem_launch_bytes"],
        "mem_peak_launch_bytes": st["mem_peak_launch_bytes"],
        "mem_launch_saved_bytes": st["mem_launch_saved_bytes"],
        "kv_preemptions": st["kv_preemptions"],
        "kv_peak_occupancy": round(st["kv_peak_occupancy"], 3),
        "spec_drafted_tokens": st["spec_drafted_tokens"],
        "spec_accepted_tokens": st["spec_accepted_tokens"],
        "done": dict(eng.done),
    }


def _fault_overhead(model, params, scfg, reqs_fn, smoke: bool) -> Dict:
    """Interleaved best-of passes: hooks disabled vs a no-op injector
    installed.  One warmed engine serves both arms so compile state and
    allocator layout are identical; interleaving cancels thermal /
    scheduler drift.  The no-op injector (zero specs) still pays the
    full per-site dispatch, so its throughput lower-bounds the disabled
    state the production path runs in."""
    assert faults.ACTIVE is None, "fault injector leaked into the benchmark"
    eng = ServeEngine(model, params, scfg)
    warm = -1
    for _ in range(4):                      # warm: compiles out of the way
        if eng.stats["prefill_compiles"] == warm:
            break
        warm = eng.stats["prefill_compiles"]
        _run_trace(eng, reqs_fn())
        eng.done.clear()

    def one_pass() -> float:
        eng.reset_stats()
        _run_trace(eng, reqs_fn())
        eng.done.clear()
        return eng.stats["tokens_per_sec"]

    best = {"disabled": 0.0, "noop_injector": 0.0}
    for _ in range(2 if smoke else 3):
        best["disabled"] = max(best["disabled"], one_pass())
        faults.install(FaultInjector([], seed=0))
        try:
            best["noop_injector"] = max(best["noop_injector"], one_pass())
        finally:
            faults.clear()
    ratio = best["noop_injector"] / max(best["disabled"], 1e-9)
    return {"disabled_tokens_per_sec": round(best["disabled"], 1),
            "noop_injector_tokens_per_sec": round(best["noop_injector"], 1),
            "overhead_ratio": round(ratio, 4)}


def _chaos_pass(model, params, cfg, smoke: bool,
                *, seed: int = 12, rate: float = 0.04) -> Dict:
    """Seeded random-fault pass over a paged-pool engine: transient
    launch faults (retried), permanent pool-allocation denials (bounded
    recompute → ``PoolExhausted``).  Asserts graceful degradation, not
    throughput — every request retires DONE or FAILED-with-reason and
    the allocator stays consistent."""
    reqs = _trace(cfg.vocab, n=8 if smoke else 24, lo=16, hi=48,
                  max_new=4, seed=7, burst=8)
    scfg = ServeConfig(max_batch=4, max_seq=128, kv_block_size=16,
                       kv_pool_blocks=28, max_recomputes=8)
    eng = ServeEngine(model, params, scfg)
    inj = FaultInjector.chaos(seed=seed, rate=rate,
                              sites=("serve.launch", "pool.alloc"))
    with faults.inject(injector=inj):
        eng.submit(reqs)
        done = eng.run_until_done(max_steps=5000)   # must not raise
    retired = set(done) | set(eng.failed)
    missing = {r.rid for r in reqs} - retired
    assert not missing, f"chaos pass lost requests: {sorted(missing)}"
    eng.alloc.assert_consistent()
    return {"seed": seed, "rate": rate,
            "sites": ["serve.launch", "pool.alloc"],
            "requests": len(reqs),
            "completed": len(done), "failed": len(eng.failed),
            "faults_fired": dict(inj.fired),
            "retries": eng.stats["retries"],
            "failed_reasons": sorted(
                v.split("(")[0] for v in eng.failed.values())}


def main(csv: List[str], smoke: bool = False, chaos: bool = False) -> None:
    cfg = dataclasses.replace(get_config("tinyllama_11b").reduced(),
                              n_layers=2, vocab=512)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    max_batch = 4
    max_seq = 128 if smoke else 256
    kv_bs = 16
    if smoke:
        tput = dict(n=8, lo=24, hi=80, max_new=4)
        longp = dict(n=6, lo=8, hi=24, max_new=8)
        long_seq, long_len = 128, 96
        pgd = dict(n=10, lo=16, hi=40, max_new=4, burst=10)
        tail = dict(n=4, lo=12, hi=24, max_new=24)
        paged_seq, spec_seq = 128, 128
    else:
        tput = dict(n=24, lo=48, hi=160, max_new=4)
        longp = dict(n=12, lo=8, hi=32, max_new=16)
        long_seq, long_len = 512, 448
        pgd = dict(n=24, lo=32, hi=96, max_new=8, burst=24)
        tail = dict(n=6, lo=12, hi=24, max_new=160)
        paged_seq, spec_seq = 256, 256

    # ---- replay vs batched, FIFO vs policies (same throughput trace) ----
    runs: Dict[str, Dict] = {}
    grid = [("replay_fifo", dict(prefill_mode="replay", admission="fifo")),
            ("batched_fifo", dict(admission="fifo")),
            ("batched_sjf", dict(admission="shortest-prompt-first")),
            ("batched_priority", dict(admission="priority"))]
    for name, kw in grid:
        scfg = ServeConfig(max_batch=max_batch, max_seq=max_seq, **kw)
        runs[name] = _measure(model, params, scfg,
                              lambda: _trace(cfg.vocab, **tput))
        csv.append(f"serve_{name},,"
                   f"tps={runs[name]['tokens_per_sec']}"
                   f";p50={runs[name]['p50_latency_s']}"
                   f";p99={runs[name]['p99_latency_s']}"
                   f";compiles={runs[name]['prefill_compiles']}")

    assert runs["batched_fifo"]["done"] == runs["replay_fifo"]["done"], \
        "batched single-pass prefill diverged from the replay baseline"
    speedup = (runs["batched_fifo"]["tokens_per_sec"]
               / max(runs["replay_fifo"]["tokens_per_sec"], 1e-9))
    floor = 1.1 if smoke else 2.0
    assert speedup >= floor, \
        f"batched prefill speedup {speedup:.2f}x below the {floor}x floor"
    csv.append(f"serve_speedup_batched_vs_replay,,{speedup:.2f}x")
    bf = runs["batched_fifo"]
    csv.append(f"serve_mem_prefill_launch,,"
               f"peak={bf['mem_peak_launch_bytes']}"
               f";saved_vs_caps={bf['mem_launch_saved_bytes']}")

    # ---- chunked vs unchunked on a long-prompt trace -------------------
    def long_trace():
        reqs = _trace(cfg.vocab, **longp, seed=3)
        for r in reqs[:2]:  # two prompts long enough to stall decode
            rng = np.random.RandomState(100 + r.rid)
            r.tokens = rng.randint(0, cfg.vocab,
                                   size=long_len).astype(np.int32)
        return reqs

    chunk = 16 if smoke else 32
    chunked: Dict[str, Dict] = {}
    for name, pc in (("unchunked", None), ("chunked", chunk)):
        scfg = ServeConfig(max_batch=max_batch, max_seq=long_seq,
                           prefill_chunk=pc, prefill_interleave=1)
        chunked[name] = _measure(model, params, scfg, long_trace)
        csv.append(f"serve_{name}_max_decode_gap,,"
                   f"{chunked[name]['max_decode_gap_s']}s")
    assert chunked["chunked"]["done"] == chunked["unchunked"]["done"], \
        "chunked prefill diverged from unchunked"
    if not smoke:
        assert (chunked["chunked"]["max_decode_gap_s"]
                < chunked["unchunked"]["max_decode_gap_s"]), \
            "chunked prefill did not reduce max decode stall"

    # ---- paged vs fixed rows at equal KV-cache memory -------------------
    # the fixed engine's memory budget is max_batch rows of max_seq
    # positions; the paged pool holds exactly that many positions
    # (max_batch * max_seq / block_size blocks, + the never-allocated
    # null block) but shares them across 4x the slots
    fb = 2 if smoke else max_batch
    pool_blocks = fb * paged_seq // kv_bs
    paged_runs: Dict[str, Dict] = {}
    grid = [("fixed_rows", dict(max_batch=fb, max_seq=paged_seq)),
            ("paged_equal_mem", dict(max_batch=4 * fb, max_seq=paged_seq,
                                     kv_block_size=kv_bs,
                                     kv_pool_blocks=pool_blocks)),
            ("paged_unconstrained", dict(max_batch=fb, max_seq=paged_seq,
                                         kv_block_size=kv_bs))]
    for name, kw in grid:
        paged_runs[name] = _measure(model, params, ServeConfig(**kw),
                                    lambda: _trace(cfg.vocab, **pgd))
        csv.append(f"serve_{name},,"
                   f"tps={paged_runs[name]['tokens_per_sec']}"
                   f";peak_slots={paged_runs[name]['peak_active_slots']}"
                   f";p50={paged_runs[name]['p50_latency_s']}")
    assert paged_runs["paged_unconstrained"]["done"] \
        == paged_runs["fixed_rows"]["done"], \
        "unconstrained paged decode diverged from fixed rows"
    n_req = len(paged_runs["fixed_rows"]["done"])
    assert len(paged_runs["paged_equal_mem"]["done"]) == n_req, \
        "equal-memory paged engine dropped requests"
    slot_ratio = (paged_runs["paged_equal_mem"]["peak_active_slots"]
                  / max(paged_runs["fixed_rows"]["peak_active_slots"], 1))
    assert slot_ratio >= 2.0, \
        f"equal-memory paged slots only {slot_ratio:.1f}x fixed (need 2x)"
    csv.append(f"serve_paged_equal_mem_slot_ratio,,{slot_ratio:.1f}x")

    # ---- speculative (n-gram) vs plain decode on the long tail ----------
    spec_runs: Dict[str, Dict] = {}
    for name, kw in (("plain_decode", {}),
                     ("speculative_ngram", dict(speculative="ngram",
                                                speculative_k=4))):
        scfg = ServeConfig(max_batch=max_batch, max_seq=spec_seq, **kw)
        spec_runs[name] = _measure(model, params, scfg,
                                   lambda: _motif_trace(cfg.vocab, **tail))
        csv.append(f"serve_{name},,"
                   f"tps={spec_runs[name]['tokens_per_sec']}")
    assert spec_runs["speculative_ngram"]["done"] \
        == spec_runs["plain_decode"]["done"], \
        "speculative greedy accept-or-fix diverged from plain decode"
    drafted = spec_runs["speculative_ngram"]["spec_drafted_tokens"]
    accepted = spec_runs["speculative_ngram"]["spec_accepted_tokens"]
    spec_speedup = (spec_runs["speculative_ngram"]["tokens_per_sec"]
                    / max(spec_runs["plain_decode"]["tokens_per_sec"],
                          1e-9))
    if not smoke:
        assert spec_speedup >= 1.05, \
            f"speculative tokens/sec {spec_speedup:.2f}x below 1.05x"
    csv.append(f"serve_speculative_speedup,,{spec_speedup:.2f}x"
               f";accept_rate={accepted / max(drafted, 1):.2f}")

    # ---- fault-hook overhead: disabled vs no-op injector ---------------
    scfg = ServeConfig(max_batch=max_batch, max_seq=max_seq)
    overhead = _fault_overhead(model, params, scfg,
                               lambda: _trace(cfg.vocab, **tput), smoke)
    csv.append(f"serve_fault_hook_overhead,,"
               f"ratio={overhead['overhead_ratio']}"
               f";disabled_tps={overhead['disabled_tokens_per_sec']}")
    if not smoke:
        assert overhead["overhead_ratio"] >= 0.98, \
            (f"fault hooks cost {(1 - overhead['overhead_ratio']):.1%} "
             f"throughput even as a no-op (2% budget)")

    # ---- seeded chaos pass (opt-in: --chaos) ---------------------------
    chaos_out = None
    if chaos:
        chaos_out = _chaos_pass(model, params, cfg, smoke)
        csv.append(f"serve_chaos,,seed={chaos_out['seed']}"
                   f";fired={sum(chaos_out['faults_fired'].values())}"
                   f";completed={chaos_out['completed']}"
                   f";failed={chaos_out['failed']}")

    out = {
        "model": "tinyllama_11b.reduced(n_layers=2, vocab=512)",
        "smoke": smoke,
        "config": {"max_batch": max_batch, "max_seq": max_seq,
                   "throughput_trace": tput,
                   "long_prompt_trace": {**longp, "long_len": long_len,
                                         "max_seq": long_seq},
                   "prefill_chunk": chunk},
        "runs": {k: {kk: vv for kk, vv in v.items() if kk != "done"}
                 for k, v in runs.items()},
        "speedup_batched_vs_replay": round(speedup, 2),
        "chunked_prefill": {
            k: {kk: vv for kk, vv in v.items() if kk != "done"}
            for k, v in chunked.items()},
        "paged_kv": {
            "config": {**pgd, "max_seq": paged_seq, "kv_block_size": kv_bs,
                       "kv_pool_blocks": pool_blocks,
                       "fixed_max_batch": fb, "paged_max_batch": 4 * fb},
            "equal_memory_slot_ratio": round(slot_ratio, 1),
            "runs": {k: {kk: vv for kk, vv in v.items() if kk != "done"}
                     for k, v in paged_runs.items()},
        },
        "speculative": {
            "config": {**tail, "max_seq": spec_seq, "speculative_k": 4,
                       "proposer": "ngram"},
            "speedup_vs_plain": round(spec_speedup, 2),
            "accept_rate": round(accepted / max(drafted, 1), 2),
            "runs": {k: {kk: vv for kk, vv in v.items() if kk != "done"}
                     for k, v in spec_runs.items()},
        },
        "fault_overhead": overhead,
    }
    if chaos_out is not None:
        out["chaos"] = chaos_out
    (ROOT / "BENCH_serve.json").write_text(json.dumps(out, indent=2) + "\n")
    csv.append(f"serve_bench_json,,{(ROOT / 'BENCH_serve.json').name}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--chaos", action="store_true",
                    help="run the seeded random-fault pass as well")
    ap.add_argument("--trace", type=str, default=None, metavar="PATH",
                    help="record the whole run under the obs tracer and "
                         "export a Chrome trace_event JSON to PATH "
                         "(load it at ui.perfetto.dev)")
    args = ap.parse_args()
    rows: List[str] = []
    if args.trace:
        from disc import observe
        observe.start_trace()
        try:
            main(rows, smoke=args.smoke, chaos=args.chaos)
            observe.export_chrome_trace(args.trace)
        finally:
            observe.stop_trace()
        rows.append(f"serve_chrome_trace,,{args.trace}")
    else:
        main(rows, smoke=args.smoke, chaos=args.chaos)
    print("\n".join(rows))
