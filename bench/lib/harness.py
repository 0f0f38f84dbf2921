"""One cell of the benchmark: set-up, the measured window, the check.

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in ``bench/configs/<config>.json``, the module that
describes the configuration's model in ``bench/models/<model>.py`` (the
configuration's ``"model"`` key; ``bench/lib/models.py``), its traffic
in ``bench/traffic/<traffic>.json`` and each metric's reader in
``bench/metrics/<metric>.py``. Adding a configuration, a model of
another family, a traffic mix of either loop or a metric adds files and
entries; no file here changes. A program family that the engine cannot
serve, or a loop other than open and closed, would.

The window drives ``ServeEngine.submit`` and ``step`` from one thread,
as ``repro.launch.serve`` does. An open loop submits each request at its
scheduled arrival; a closed loop keeps one request of each client in
flight. Every time is a host ``perf_counter`` reading, relative to the
window's opening.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.api import ServeConfig
from repro.data.pipeline import Request
from repro.models.common import ArchConfig
from repro.models.registry import get_model

from . import models, reference, weights
from .engine import BenchEngine, no_span, trace_span
from .peaks import peaks_for
from .traffic import Traffic

__all__ = ["NoChip", "Cell", "use_compile_cache", "load_benchmark",
           "cell_metrics", "read_metrics", "is_correct", "control_checks"]

# compile events: every executable obtained (built by XLA or loaded from
# the persistent compilation cache), and the cache's hits among them
_OBTAINED = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def use_compile_cache(root: pathlib.Path) -> None:
    """Keep JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program however short its compile, so that only
    a checkout's first run compiles."""
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def load_benchmark(root: pathlib.Path) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _find(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(bench: Dict, workload: str, trace: bool) -> List[Dict]:
    """The metrics a run of ``workload`` reports: the end-to-end ones, or
    with ``trace`` the per-layer ones."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    moves = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"] in
                                 moves else [])]


def read_metrics(root: pathlib.Path, metrics: List[Dict],
                 run: Dict) -> Dict[str, Dict]:
    """Each metric's value from its reader, ``bench/metrics/<name>.py``;
    a reader that finds nothing to read returns ``None`` and the metric
    is left out."""
    out = {}
    for m in metrics:
        path = root / "bench" / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{m['name'].replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        v = mod.read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


class _CompileCounter:
    """Counts executables obtained, and those loaded from the cache."""

    def __init__(self):
        self.total = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, duration: float, **kw) -> None:
        if event == _OBTAINED:
            self.total += 1

    def _event(self, event: str, **kw) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1


@dataclass
class ReqRec:
    rid: int
    arrival: float
    plen: int
    out_len: int
    submit: Optional[float] = None
    admit: Optional[float] = None
    tokens: List[float] = field(default_factory=list)
    failed: bool = False


class Cell:
    """A cell's program, built once; windows and checks run against it."""

    def __init__(self, root: pathlib.Path, workload: str, *,
                 require_tpu: bool = True, traced: bool = False,
                 engine_cls=BenchEngine, bench: Optional[Dict] = None):
        self.root = pathlib.Path(root)
        self.bench = bench if bench is not None else load_benchmark(self.root)
        self.cell = _find(self.bench["workloads"], workload, "workload")
        self.name = workload
        self.config = json.loads((self.root / "bench" / "configs" /
                                  f"{self.cell['config']}.json").read_text())
        self.traffic_spec = json.loads((self.root / "bench" / "traffic" /
                                        f"{self.cell['traffic']}.json")
                                       .read_text())
        self.arch = dict(self.config["arch"])
        self.serve = dict(self.config["serve"])
        self.model_file = models.of_config(self.root, self.config,
                                           self.cell["config"])
        self.module = models.load(self.model_file)
        self.span = trace_span if traced else no_span
        self.engine_cls = engine_cls
        devices = jax.devices()
        self.devices = devices[:self.cell["chips"]]
        kind = devices[0].device_kind
        if require_tpu:
            if devices[0].platform != "tpu":
                raise NoChip(f"no TPU: JAX finds {devices[0].platform}")
            if len(devices) < self.cell["chips"]:
                raise NoChip(f"{self.name} needs {self.cell['chips']} "
                             f"chips; JAX finds {len(devices)}")
            self.peaks = peaks_for(kind)
        else:
            self.peaks = None
        self.device = {"platform": devices[0].platform, "kind": kind,
                       "count": len(self.devices)}
        self.compiles = _CompileCounter()
        self.engine: Optional[BenchEngine] = None

    # ----------------------------------------------------------- set-up --
    def build(self, seed: int) -> None:
        """Weights from ``seed``, the engine (every key of the
        configuration's ``serve`` block goes to ``ServeConfig``), and
        every program the cell's traffic can reach."""
        cfg = ArchConfig(**self.arch)
        self.model = get_model(cfg)
        params = weights.program_params(self.module, self.arch, seed)
        want = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                           params)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError("benchmark weights do not match the model's "
                             "parameter tree")
        jax.block_until_ready(params)
        self.engine = self.engine_cls(
            self.model, params,
            ServeConfig(eos_id=-1, **self.serve),
            span=self.span)
        self.warm(seed)

    def set_params(self, seed: int) -> None:
        """New weights from ``seed`` for the engine already built."""
        self.engine.params = weights.program_params(self.module, self.arch,
                                                    seed)
        jax.block_until_ready(self.engine.params)

    def warm(self, seed: int) -> None:
        """Launch every (B, S) prefill bucket the traffic's clip can
        reach once (with the engine's prompt chunks, where it splits
        prompts), at its largest batch, then every admission group size
        at the smallest prompt bucket (the engine gathers and scatters
        cache rows per group size), each with one decode step."""
        eng = self.engine
        scfg = eng.scfg
        tr = Traffic(self.traffic_spec, seed, self.arch["vocab"])

        def s_bucket(n):
            return min(scfg.prefill_policy.bucket("S", max(n, 1)),
                       scfg.max_seq)

        def b_bucket(n):
            return min(scfg.batch_policy.bucket("B", n), eng.n_slots)

        s_pairs = tr.clip_buckets(s_bucket, scfg.prefill_chunk)
        top_nb: Dict[int, int] = {}
        for nb in range(1, eng.n_slots + 1):
            top_nb[b_bucket(nb)] = nb
        groups = [(nb, n) for _, n in s_pairs for nb in top_nb.values()]
        groups += [(nb, s_pairs[0][1]) for nb in range(1, eng.n_slots + 1)
                   if nb not in top_nb.values()]
        rng = np.random.default_rng([int(seed), 7])
        c0, h0 = self.compiles.total, self.compiles.cache_hits
        for g, (nb, n) in enumerate(groups):
            eng.submit([Request(rid=-(g * eng.n_slots + j + 1),
                                tokens=rng.integers(2, self.arch["vocab"],
                                                    size=n, dtype=np.int32),
                                max_new_tokens=1) for j in range(nb)])
            eng.run_until_done()
        if eng.failed:
            raise RuntimeError(f"warm-up requests failed: {eng.failed}")
        eng.done.clear()
        self.warm_info = {
            "prefill_buckets": len(s_pairs) * len(top_nb),
            "groups": len(groups),
            "programs": self.compiles.total - c0,
            "from_cache": self.compiles.cache_hits - h0,
            "programs_before_warm_up": c0,
            "engine_compiles": eng.compile_counts(),
        }

    # ----------------------------------------------------------- window --
    def run(self, seed: int, seconds: float, *,
            traffic_spec: Optional[Dict] = None,
            trace_dir: Optional[str] = None) -> Dict:
        """One measured window; returns the run record.

        An open loop sends request ``i`` at its scheduled arrival. A
        closed loop starts with every client sending, and a client sends
        again ``think_s`` after the step that finished its last request;
        a request's ``arrival`` is then the time it was sent. Its window
        opens after ``lead_in_steps`` engine steps, and every time taken
        before is moved to the window's clock (negative). Nothing is
        sent once the window has closed; the engine then serves what was
        sent until each request has its first token."""
        spec = traffic_spec or self.traffic_spec
        tr = Traffic(spec, seed, self.arch["vocab"])
        closed_loop = tr.loop == "closed"
        eng = self.engine
        if eng.queue or any(s is not None for s in eng.slots):
            eng.run_until_done()    # an earlier window's requests
        eng.done.clear()
        eng.failed.clear()
        eng.rejected.clear()
        eng.prefills.clear()
        eng.decodes.clear()
        eng.reset_stats()
        clock = time.perf_counter
        recs: Dict[int, ReqRec] = {}
        done_seen = [0]
        mst = eng._prefill_fn._mstats
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)

        def snap() -> Dict:
            return {"buckets": {k: list(v) for k, v in
                                mst.per_bucket.items()},
                    "calls": mst.calls, "host_s": mst.host_seconds,
                    "compiles": self.compiles.total}

        lead_steps = tr.lead_in_steps if closed_loop else 0
        t0 = clock()
        steps = 0

        def now() -> float:
            return clock() - t0

        # closed loop: the client of each request in flight, and when
        # each client sends next (None while its request is in flight)
        client_of: Dict[int, int] = {}
        ready = [0.0] * (tr.clients if closed_loop else 0)

        def new_request(i: int, at: float) -> Request:
            rec = ReqRec(rid=i, arrival=at, plen=tr.prompt_len(i),
                         out_len=tr.output_len(i))
            recs[i] = rec
            return Request(rid=i, tokens=tr.tokens(i),
                           max_new_tokens=rec.out_len - 1)

        def finish(rid: int, t: float) -> None:
            if rid in client_of:
                ready[client_of.pop(rid)] = t + tr.think_s

        def observe(t: float) -> None:
            """Admissions, token times and finished requests after a
            step."""
            for s in eng.slots:
                if s is not None and s.rid >= 0:
                    r = recs[s.rid]
                    if r.admit is None:
                        r.admit = t
                    r.tokens += [t] * (len(s.generated) - len(r.tokens))
            items = list(eng.done.items())
            for rid, toks in items[done_seen[0]:]:
                r = recs[rid]
                if r.admit is None:
                    r.admit = t
                r.tokens += [t] * (len(toks) - len(r.tokens))
                finish(rid, t)
            done_seen[0] = len(items)
            for rid in list(eng.failed) + eng.rejected:
                if rid in recs:
                    recs[rid].failed = True
                    finish(rid, t)

        def open_window() -> None:
            """Move every time taken so far to the window's clock."""
            nonlocal t0
            shift = now()
            t0 += shift
            for r in recs.values():
                r.arrival -= shift
                r.submit = None if r.submit is None else r.submit - shift
                r.admit = None if r.admit is None else r.admit - shift
                r.tokens = [x - shift for x in r.tokens]
            ready[:] = [None if at is None else at - shift for at in ready]
            queue[:] = [(x - shift, n) for x, n in queue]

        win = snap0 = snap1 = None
        closed_at = None
        lead_in_s = 0.0
        nxt = 0
        queue: List = []
        while True:
            if snap0 is None and steps >= lead_steps:
                lead_in_s = now()
                open_window()
                snap0 = snap()
                # the profiler's span starts when it is made
                win = self.span("bench.window")
                win.__enter__()
            t = now()
            if closed_at is None:
                due = []
                if closed_loop:
                    for c, at in enumerate(ready):
                        if at is not None and at <= t and (
                                snap0 is None or t < seconds):
                            due.append(new_request(nxt, t))
                            client_of[nxt] = c
                            ready[c] = None
                            nxt += 1
                else:
                    # every arrival scheduled before the close is sent,
                    # late if a step ran past it
                    while tr.arrival(nxt) <= t and tr.arrival(nxt) < seconds:
                        due.append(new_request(nxt, tr.arrival(nxt)))
                        nxt += 1
                if due:
                    eng.submit(due)
                    ts = now()
                    for q in due:
                        recs[q.rid].submit = ts
            if snap0 is not None and closed_at is None and t >= seconds:
                closed_at = t
                win.__exit__(None, None, None)
                snap1 = snap()
            if eng.queue or any(s is not None for s in eng.slots):
                eng.step()
                steps += 1
                observe(now())
                queue.append((now(), len(eng.queue)))
            elif closed_at is not None:
                break
            else:
                if closed_loop:
                    wake = min([at for at in ready if at is not None]
                               + ([seconds] if snap0 is not None else []))
                else:
                    wake = min(tr.arrival(nxt), seconds)
                with self.span("bench.idle"):
                    time.sleep(max(0.0, wake - now()))
            if closed_at is not None and all(r.tokens or r.failed
                                             for r in recs.values()):
                break
        end = now()
        if trace_dir is not None:
            jax.profiler.stop_trace()
        for r in recs.values():
            r.failed = r.failed or r.rid in eng.failed or \
                r.rid in eng.rejected
        buckets = {}
        for k, v in snap1["buckets"].items():
            c0 = snap0["buckets"].get(k, [0] * len(v))[0]
            if v[0] > c0:
                buckets[",".join(map(str, k))] = v[0] - c0

        def rel(x):
            return [(a - t0, b - t0, n) for a, b, n in x]

        return {
            "workload": self.name,
            "model": str(self.model_file),
            "loop": tr.loop,
            "lead_in_steps": lead_steps,
            "lead_in_s": lead_in_s,
            "seed": int(seed),
            "seconds": float(seconds),
            "closed_at": closed_at,
            "end": end,
            "arch": self.arch,
            "peaks": None if self.peaks is None else
            dataclasses.asdict(self.peaks),
            "requests": [dataclasses.asdict(r) for r in recs.values()],
            "prefills": rel(eng.prefills),
            "decodes": rel(eng.decodes),
            "prefill_buckets": buckets,
            "prefill_dispatch": {
                "calls": snap1["calls"] - snap0["calls"],
                "host_s": snap1["host_s"] - snap0["host_s"]},
            "window_compiles": snap1["compiles"] - snap0["compiles"],
            "queue": queue,
        }

    # ------------------------------------------------------------ check --
    def sample(self, run: Dict, seed: int) -> List[Dict]:
        """Requests of the window that finished, drawn from ``seed``:
        the one with the most served tokens and ``check_requests - 1``
        others."""
        eng = self.engine
        fin = [r for r in run["requests"] if r["rid"] in eng.done
               and not r["failed"]]
        if not fin:
            return []
        fin.sort(key=lambda r: (-len(eng.done[r["rid"]]), r["rid"]))
        n = int(self.config["check"]["requests"])
        rest = fin[1:]
        rng = np.random.default_rng([int(seed), 11])
        pick = [fin[0]] + [rest[i] for i in sorted(
            rng.choice(len(rest), size=min(n - 1, len(rest)),
                       replace=False))]
        tr = Traffic(self.traffic_spec, seed, self.arch["vocab"])
        return [{"rid": r["rid"], "prompt": tr.tokens(r["rid"]),
                 "plen": r["plen"], "served": list(eng.done[r["rid"]])}
                for r in pick]

    def free(self, *, cache: bool = True) -> None:
        """Drop the program's weights (and its cache) from the device."""
        eng = self.engine
        for x in jax.tree.leaves(eng.params) + (
                jax.tree.leaves(eng.cache) if cache else []):
            x.delete()
        eng.params = None
        gc.collect()

    def check(self, run: Dict, picked: List[Dict], seed: int,
              *, control: bool = False) -> Dict[str, Dict]:
        """The numbers that decide ``correct``, each beside its limit."""
        short = sum(1 for r in run["requests"]
                    if r["rid"] in self.engine.done and not r["failed"]
                    and len(self.engine.done[r["rid"]]) != r["out_len"])
        failed = sum(1 for r in run["requests"] if r["failed"])
        checks = {
            "failed_requests": {"value": failed, "limit": 0},
            "short_answers": {"value": short, "limit": 0},
        }
        if picked:
            gaps = reference.compare(
                self.module, self.arch, seed,
                [(p["prompt"], p["plen"], np.asarray(p["served"]))
                 for p in picked], control=control)
            self.gaps = gaps
            checks["served_tokens"] = {
                "value": sum(len(p["served"]) for p in picked),
                "limit": int(self.config["check"]["min_tokens"])}
            checks["logit_gap"] = {"value": max(gaps["served"]),
                                   "limit": float(
                                       self.config["check"]["logit_gap"])}
        else:
            checks["served_tokens"] = {
                "value": 0, "limit": int(self.config["check"]["min_tokens"])}
        return checks


def control_checks(checks: Dict[str, Dict], gaps: Dict) -> Dict[str, Dict]:
    """The checks of the control put in the program's place: the same
    numbers, but the widest gap is that of the control's first choices
    (``Cell.check(..., control=True)`` leaves them in ``gaps``)."""
    out = dict(checks)
    out["logit_gap"] = dict(checks["logit_gap"],
                            value=max(gaps["control"]))
    return out


def is_correct(checks: Dict[str, Dict]) -> bool:
    """Every number within its limit (``served_tokens`` is a floor)."""
    ok = True
    for name, c in checks.items():
        if name == "served_tokens":
            ok &= c["value"] >= c["limit"]
        else:
            ok &= c["value"] <= c["limit"]
    return bool(ok) and "logit_gap" in checks
