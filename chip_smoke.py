#!/usr/bin/env python3
"""Bring-up smoke for a TPU: the serving path and the Pallas compiler path.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips: mesh serving parity only

One chip runs two phases, each through the entry points a user calls:

* **serve** — ``minitron_4b`` at its published widths and full depth
  (``get_config`` -> ``get_model`` -> ``ServeEngine``, as
  ``repro.launch.serve`` does), random weights from ``--seed``: 8 requests
  over two prefill buckets, 16 greedy tokens each.  Checks that every
  request completes with no failure and no kernel demotion, that prefill
  compiles stay within the buckets hit, and that the engine's prefill
  logits match ``model.forward`` on the same params.
* **dhlo** — ``disc.compile(..., pipeline="dhlo", backend="pallas")`` of
  an rmsnorm -> 3072x9216 dot with a SiLU epilogue -> row sum, over a
  dynamic token dim, at three lengths.  Checks parity with plain
  ``jax.jit``, that every Pallas cluster kernel ran with no fallback, and
  that the compiled bucket programs hold Mosaic kernels
  (``tpu_custom_call``).

``--chips 4`` runs only the mesh phase: the same requests served on one
device, then on a 4-device ``data`` mesh with the config's sharding
profile, comparing logits and tokens and printing each device's memory.

Exits non-zero, printing no result, unless JAX finds a TPU.  The last line
of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import disc  # noqa: E402
from repro.api import ServeConfig, ServeEngine  # noqa: E402
from repro.api.persistent_cache import enable_persistent_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.codegen import KERNEL_DEMOTIONS  # noqa: E402
from repro.data.pipeline import Request  # noqa: E402
from repro.models.registry import get_model  # noqa: E402

ARCH = "minitron_4b"
# 4 prompts in prefill bucket S=64, then 4 in S=128 (POW2, granule 16):
# admission is FIFO over 4 slots, so each bucket is one batched launch
PROMPT_LENS = (33, 40, 52, 61, 70, 90, 113, 127)
MAX_NEW = 16   # generated tokens per request, the prefill's first included
MAX_BATCH, MAX_SEQ = 4, 1024
# bf16 carries 8 significant bits (relative spacing 2^-8 = 3.9e-3).  The
# engine's prefill (padded bucket, attention over the KV cache) and
# model.forward (exact length, no cache) round differently through 32
# residual layers, so they agree to a few spacings of the largest logit;
# a wrong mask, cache write or position gives errors of order 1.
LOGIT_TOL = 5e-2
DHLO_LENGTHS = (100, 300, 700)   # buckets 128, 512, 1024
DHLO_TOL = 2e-2                  # a few bf16 spacings of the largest value


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def memory_line(devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"dev{d.id} in_use={st.get('bytes_in_use')} "
                     f"peak={st.get('peak_bytes_in_use')}")
    return "; ".join(parts)


def rel_err(a: np.ndarray, ref: np.ndarray) -> float:
    """Largest absolute difference over the largest reference magnitude."""
    a = np.asarray(a, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(a - ref)) / max(np.max(np.abs(ref)), 1e-30))


class RecordingEngine(ServeEngine):
    """A ServeEngine that keeps, per request, the logits row behind each
    token it emits (row j produced token j); the engine itself keeps only
    the tokens."""

    def __init__(self, *args, **kw) -> None:
        super().__init__(*args, **kw)
        self.logits = {}

    def _launch(self, kind, fn, *args):
        out = super()._launch(kind, fn, *args)
        logits = np.asarray(out[0], np.float32)
        if kind == "prefill":
            # (params, rows, tokens, lens, offsets); whole prompts only
            tokens, lens = np.asarray(args[2]), np.asarray(args[3])
            for r, n in enumerate(lens):
                for s in self.slots:
                    if (s is not None and s.state == "prefill"
                            and s.plen == n
                            and np.array_equal(s.tokens, tokens[r, :n])):
                        self.logits[s.rid] = [logits[r]]
        elif kind == "decode":
            for i, s in enumerate(self.slots):
                if s is not None and s.state == "decode":
                    self.logits[s.rid].append(logits[i, 0])
        return out


def make_prompts(cfg, seed: int, lens=PROMPT_LENS):
    rng = np.random.default_rng(seed)
    # ids 0/1 stay out of prompts (eos_id is 1)
    return [rng.integers(2, cfg.vocab, size=n, dtype=np.int32) for n in lens]


def serve(engine: RecordingEngine, prompts, label: str):
    """Serve ``prompts`` through ``engine`` and check the run; returns
    (tokens by request index, logits by request index)."""
    reqs = [Request(rid=i, tokens=p, max_new_tokens=MAX_NEW - 1)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    engine.submit(reqs)
    done = engine.run_until_done()
    dt = time.perf_counter() - t0
    st = engine.stats
    log(f"[{label}] {len(done)}/{len(reqs)} requests completed in "
        f"{dt:.3f} s host wall (compiles included); "
        f"tokens={st['tokens_generated']} prefill_calls={st['prefill_calls']} "
        f"decode_steps={st['decode_steps']} "
        f"prefill_compiles={st['prefill_compiles']} "
        f"prefill_bucket_pairs={st['prefill_bucket_pairs']} "
        f"decode_compiles={engine.compile_counts()['decode']['total']} "
        f"failed={len(engine.failed)} rejected={len(engine.rejected)} "
        f"kernel_demotions={st['kernel_demotions']}")
    check(not engine.failed, f"[{label}] failed requests: {engine.failed}")
    check(not engine.rejected, f"[{label}] rejected: {engine.rejected}")
    check(sorted(done) == list(range(len(reqs))),
          f"[{label}] completed {sorted(done)} of {len(reqs)}")
    for rid, toks in done.items():
        check(len(toks) == MAX_NEW or toks[-1] == engine.scfg.eos_id,
              f"[{label}] request {rid} stopped after {len(toks)} tokens")
        check(len(engine.logits[rid]) >= len(toks),
              f"[{label}] request {rid}: logits not recorded")
    check(st["kernel_demotions"] == 0, f"[{label}] kernel demotions")
    check(st["prefill_compiles"] <= st["prefill_bucket_pairs"],
          f"[{label}] {st['prefill_compiles']} prefill compiles for "
          f"{st['prefill_bucket_pairs']} buckets")
    return {rid: list(t) for rid, t in done.items()}, dict(engine.logits)


def reference_last_logits(model, params, prompts, idx):
    """``model.forward`` logits at each chosen prompt's last position;
    prompts are right-padded into one batch (causal attention keeps the
    padding out of every earlier position)."""
    width = max(len(prompts[i]) for i in idx)
    toks = np.zeros((len(idx), width), np.int32)
    for r, i in enumerate(idx):
        toks[r, :len(prompts[i])] = prompts[i]
    last = jnp.asarray([len(prompts[i]) - 1 for i in idx])

    @jax.jit
    def fwd(params, toks, last):
        logits = model.forward(params, {"tokens": toks})
        return logits[jnp.arange(toks.shape[0]), last]

    return np.asarray(fwd(params, jnp.asarray(toks), last), np.float32)


def init_params(model, seed: int, label: str):
    t0 = time.perf_counter()
    params = model.init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"[{label}] params: {n} ({nbytes} bytes) initialised in "
        f"{time.perf_counter() - t0:.3f} s (compile included)")
    return params


def describe(cfg, label: str) -> None:
    log(f"[{label}] {cfg.name}: d_model={cfg.d_model} n_heads={cfg.n_heads} "
        f"n_kv_heads={cfg.n_kv_heads} head_dim={cfg.hd} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab} dtype={cfg.dtype} depth={cfg.n_layers}")


# ------------------------------------------------------------------ phases --

def serve_phase(cfg, seed: int, scfg: ServeConfig) -> None:
    """Serve at full width on the default device; prefill logits against
    ``model.forward``."""
    describe(cfg, "serve")
    model = get_model(cfg)
    params = init_params(model, seed, "serve")
    prompts = make_prompts(cfg, seed)
    _, logits = serve(RecordingEngine(model, params, scfg), prompts, "serve")
    log(f"[serve] memory: {memory_line(jax.devices()[:1])}")
    # one prompt from each prefill bucket
    idx = [0, len(prompts) - 1]
    t0 = time.perf_counter()
    ref = reference_last_logits(model, params, prompts, idx)
    for r, i in enumerate(idx):
        err = rel_err(logits[i][0], ref[r])
        log(f"[serve] prefill logits vs model.forward, prompt len "
            f"{len(prompts[i])}: rel_err={err:.6g} (tol {LOGIT_TOL}) "
            f"argmax {int(np.argmax(logits[i][0]))} vs "
            f"{int(np.argmax(ref[r]))}")
        check(err < LOGIT_TOL, f"prefill logits of prompt {i} off by {err}")
    log(f"[serve] reference forward in {time.perf_counter() - t0:.3f} s "
        f"(compile included)")


def mlp_block(x, g, w):
    """rmsnorm -> dot with a SiLU epilogue -> row sum."""
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    h = x * jax.lax.rsqrt(ms + 1e-6).astype(x.dtype) * g
    y = jax.nn.silu(h @ w)
    return y, jnp.sum(y.astype(jnp.float32), axis=-1)


def dhlo_phase(d_model: int, d_ff: int, lengths, seed: int) -> None:
    """The DISC compiler path with compiled Pallas cluster kernels."""
    dt = jnp.bfloat16
    specs = [disc.ArgSpec((disc.Dim("T", max=max(lengths) * 2), d_model), dt),
             disc.ArgSpec((d_model,), dt),
             disc.ArgSpec((d_model, d_ff), dt)]
    f = disc.compile(mlp_block, specs, options=disc.CompileOptions(
        pipeline="dhlo", backend="pallas"))
    ref = jax.jit(mlp_block)
    rng = np.random.default_rng(seed)
    g = jnp.asarray(1.0 + 0.1 * rng.standard_normal(d_model), dt)
    w = jnp.asarray(rng.standard_normal((d_model, d_ff)) / np.sqrt(d_model),
                    dt)
    for t in lengths:
        x = jnp.asarray(rng.standard_normal((t, d_model)), dt)
        t0 = time.perf_counter()
        y, s = jax.block_until_ready(f(x, g, w))
        dt_call = time.perf_counter() - t0
        ry, rs = ref(x, g, w)
        ey, es = rel_err(y, ry), rel_err(s, rs)
        log(f"[dhlo] T={t}: first call {dt_call:.3f} s (compile included) "
            f"rel_err y={ey:.6g} rowsum={es:.6g} (tol {DHLO_TOL})")
        check(y.shape == (t, d_ff) and s.shape == (t,), f"shapes at T={t}")
        check(ey < DHLO_TOL and es < DHLO_TOL, f"dhlo parity at T={t}")
    counts = f.compile_counts()
    kernels = disc.get_backend("pallas").cluster_kernels
    log(f"[dhlo] compiles {counts}; cluster templates "
        f"{f.report()['cluster_templates']}; kernels "
        + ", ".join(f"{t}: runs={k.runs} fallbacks={k.fallbacks}"
                    for t, k in kernels.items())
        + f"; demotions={list(KERNEL_DEMOTIONS)}")
    check(counts["bucket"] >= 2, "fewer than two buckets compiled")
    for t, k in kernels.items():
        check(k.runs > 0 and k.fallbacks == 0, f"kernel {t} ran {k.runs}, "
              f"fell back {k.fallbacks}")
    check(not KERNEL_DEMOTIONS, f"demotions: {KERNEL_DEMOTIONS}")
    programs = f.bucket_programs()
    n_custom = {key: prog.as_text().count("tpu_custom_call")
                for key, prog in programs.items()}
    log(f"[dhlo] tpu_custom_call per bucket program: {n_custom}")
    check(programs and all(n_custom.values()),
          "a bucket program holds no Mosaic kernel")


def mesh_phase(cfg, seed: int, n: int) -> None:
    """The same requests on one device, then on an n-device data mesh."""
    describe(cfg, "mesh")
    devices = jax.devices()[:n]
    model = get_model(cfg)
    params = init_params(model, seed, "mesh")
    prompts = make_prompts(cfg, seed)
    scfg = ServeConfig(max_batch=MAX_BATCH, max_seq=MAX_SEQ)
    tok1, log1 = serve(RecordingEngine(model, params, scfg), prompts,
                       "one-device")
    gc.collect()
    log(f"[one-device] memory: {memory_line(devices)}")
    mesh = disc.make_mesh((n,), ("data",), devices=devices)
    engine_cfg = dataclasses.replace(scfg, mesh=mesh,
                                     sharding_profile=cfg.sharding_profile)
    # the engine shards the params onto the mesh; drop the one-device copy
    engine = RecordingEngine(model, params, engine_cfg)
    del params
    gc.collect()
    log(f"[mesh] {n}-device data mesh, profile {cfg.sharding_profile}; "
        f"memory after sharding: {memory_line(devices)}")
    tok4, log4 = serve(engine, prompts, "mesh")
    log(f"[mesh] memory: {memory_line(devices)}")
    same = 0
    worst = 0.0
    for rid in range(len(prompts)):
        a, b = tok1[rid], tok4[rid]
        la, lb = log1[rid], log4[rid]
        j = 0
        while j < min(len(a), len(b)):
            err = rel_err(lb[j], la[j])
            worst = max(worst, err)
            check(err < LOGIT_TOL, f"request {rid} step {j}: mesh logits "
                  f"off by {err}")
            if a[j] != b[j]:
                # greedy paths may part only at a near tie of the
                # one-device logits, within the logit tolerance
                gap = float(la[j][a[j]] - la[j][b[j]])
                tie = LOGIT_TOL * float(np.max(np.abs(la[j])))
                log(f"[mesh] request {rid} parts at token {j}: "
                    f"{a[j]} vs {b[j]}, logit gap {gap:.6g} (tie bound "
                    f"{tie:.6g})")
                check(gap <= tie, f"request {rid} parts at token {j} "
                      f"without a near tie")
                break
            j += 1
        same += j == len(a) == len(b)
    log(f"[mesh] parity: {same}/{len(prompts)} token sequences identical; "
        f"worst logits rel_err={worst:.6g} (tol {LOGIT_TOL})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}")
    log(f"compile cache: {enable_persistent_cache()}")
    cfg = get_config(ARCH)
    # the compile rehearsal for a described v5e fits the full-depth
    # prefill and decode programs beside the weights: no depth cut
    log(f"depth: {cfg.n_layers} of {cfg.n_layers} layers (no cut)")
    t0 = time.perf_counter()
    if args.chips == 1:
        serve_phase(cfg, args.seed,
                    ServeConfig(max_batch=MAX_BATCH, max_seq=MAX_SEQ))
        log(f"serve phase: {time.perf_counter() - t0:.3f} s")
        t1 = time.perf_counter()
        dhlo_phase(cfg.d_model, cfg.d_ff, DHLO_LENGTHS, args.seed)
        log(f"dhlo phase: {time.perf_counter() - t1:.3f} s")
    else:
        mesh_phase(cfg, args.seed, args.chips)
        log(f"mesh phase: {time.perf_counter() - t0:.3f} s")
    log(f"memory: {memory_line(devices[:args.chips])}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
