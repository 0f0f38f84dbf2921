"""Differential suite for the unified observability plane (repro.obs).

Contracts under test:

* **spans** nest with correct parenting across BOTH pipelines — a
  generated-dispatch ``dispatch`` span parents the cache's
  ``compile.bucket`` span on a miss and has no compile child on a hit;
* **one registry** — ``disc.observe()`` agrees exactly with the legacy
  accessors it absorbed (``ServeEngine.stats`` / ``report()["health"]``,
  ``Compiled.cache_stats()`` / ``cost_report()``, ``VMStats``);
* **Chrome export** — every event validates against the ``trace_event``
  schema (internal parent/depth fields stripped);
* **zero-overhead discipline** — with no tracer installed the generated
  dispatch source is byte-identical, no events are recorded, and the
  hot serve path never grows the lifecycle timeline;
* **typed reset** — ``ServeEngine.reset_stats()`` restores every stats
  key to its documented type (the old uniform ``= 0`` clobbered
  ``per_replica``'s list-of-dicts to an int);
* **one clock** — heartbeats and the obs clock are injectable and
  deterministic under a fixed source;
* **the profiler's clock** — ``Tracer(profiler=True)`` puts the
  engine's spans on the host plane of a JAX profile, nested as in the
  buffer;
* **compile spans time the compile** — on the ``jit`` pipeline the
  ``compile.bucket`` / ``compile.exact`` span is the entry's first call;
* **named model scopes** change HLO metadata only.
"""
import contextlib
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import disc
from repro.api import ArgSpec
from repro.configs import get_config
from repro.core.vm import NimbleVM
from repro.data.pipeline import Request
from repro.ft.supervisor import HeartbeatMonitor
from repro.models.registry import get_model
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.clock import CLOCK, Clock
from repro.serve.engine import STATS_KEYS, ServeConfig, ServeEngine


@pytest.fixture(autouse=True)
def fresh_obs():
    """Every test gets its own metrics registry (collectors registered
    by artifacts/engines built inside the test land there, isolated from
    whatever earlier tests left alive) and must not leak a tracer."""
    prev = obs_metrics.REGISTRY
    obs_metrics.REGISTRY = obs_metrics.MetricsRegistry()
    yield
    leaked = obs_trace.ACTIVE is not None
    obs_trace.clear()
    obs_metrics.REGISTRY = prev
    assert not leaked, "test left a tracer installed"


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama_11b").reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(vocab, lens, max_new=3):
    rng = np.random.RandomState(7)
    return [Request(rid=i,
                    tokens=rng.randint(0, vocab, size=ln).astype(np.int32),
                    max_new_tokens=max_new)
            for i, ln in enumerate(lens)]


def _engine(model, params, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq", 64)
    return ServeEngine(model, params, ServeConfig(**kw))


PIPELINES = ("dhlo", "jit")


def _artifact(pipeline, name="obs_fn"):
    specs = [ArgSpec(("S", 4), jnp.float32)]
    return disc.compile(lambda x: jnp.tanh(x) * 2.0, specs,
                        options=disc.CompileOptions(pipeline=pipeline,
                                                    name=name))


# ------------------------------------------------------------- tracer ----

class TestTracer:
    def test_manual_nesting_parent_and_depth(self):
        tr = obs_trace.Tracer()
        a = tr.begin("outer")
        b = tr.begin("inner")
        tr.instant("tick")
        b.end()
        a.end(extra=1)
        outer, inner, tick = tr.events
        assert (outer["parent"], outer["depth"]) == (-1, 0)
        assert (inner["parent"], inner["depth"]) == (0, 1)
        assert (tick["parent"], tick["depth"]) == (1, 2)
        assert outer["args"] == {"extra": 1}
        assert outer["dur"] >= inner["dur"] >= 0.0

    @pytest.mark.parametrize("pipeline", PIPELINES)
    def test_dispatch_parents_compile_span(self, pipeline):
        f = _artifact(pipeline)
        with obs_trace.tracing() as tr:
            f(np.ones((3, 4), np.float32))   # miss: compile inside dispatch
            f(np.ones((3, 4), np.float32))   # hit: no compile child
        disp = tr.spans("dispatch")
        assert len(disp) == 2
        miss, hit = disp
        assert miss["args"]["cache_hit"] is False
        assert hit["args"]["cache_hit"] is True
        assert miss["args"]["bucket"] == (16,)  # pow2 floor bucket
        # pad 3 -> 16 rows of 4 f32 (16 bytes/row): 13 padded rows
        assert miss["args"]["pad_bytes"] == 13 * 16
        assert miss["args"]["entry_seconds"] > 0.0
        comp = tr.spans("compile.bucket")
        assert len(comp) == 1
        assert comp[0]["parent"] == tr.events.index(miss)
        assert comp[0]["depth"] == miss["depth"] + 1
        # the hit span parents no compile event
        hit_idx = tr.events.index(hit)
        assert not [e for e in tr.events if e.get("parent") == hit_idx
                    and e["name"].startswith("compile")]

    def test_lower_span_dhlo(self):
        with obs_trace.tracing() as tr:
            _artifact("dhlo", name="lower_me")
        low = tr.spans("lower")
        assert len(low) == 1
        assert low[0]["args"]["artifact"] == "lower_me"
        assert low[0]["cat"] == "compile"

    def test_kernel_cluster_spans_nest_in_dispatch(self):
        # cluster spans need a backend with registered cluster kernels
        f = disc.compile(lambda x, y: jnp.tanh(x) * y + 1.0,
                         [ArgSpec(("B", 8), jnp.float32),
                          ArgSpec(("B", 8), jnp.float32)],
                         options=disc.CompileOptions(backend="pallas"))
        with obs_trace.tracing() as tr:
            f(np.ones((3, 8), np.float32), np.ones((3, 8), np.float32))
        clusters = tr.spans("kernel.cluster")
        assert clusters, "dhlo entry ran no cluster spans"
        disp_idx = tr.events.index(tr.spans("dispatch")[0])
        for c in clusters:
            assert c["cat"] == "backend"
            assert c["depth"] > 0
            # every cluster span sits somewhere under the dispatch span
            p = c
            while p["parent"] != -1 and p["parent"] != disp_idx:
                p = tr.events[p["parent"]]
            assert p["parent"] == disp_idx

    def test_vm_interp_span(self):
        f = _artifact("dhlo")
        vm = NimbleVM(f.graph)
        with obs_trace.tracing() as tr:
            vm(np.ones((4, 4), np.float32))
        sp = tr.spans("vm.interp")
        assert len(sp) == 1
        assert sp[0]["args"]["op_dispatches"] == vm.stats.op_dispatches > 0

    def test_metrics_event_mirrors_to_instant(self):
        with obs_trace.tracing() as tr:
            obs_metrics.record_event("replica.drain", replica=1)
        inst = tr.find("replica.drain")
        assert len(inst) == 1 and inst[0]["ph"] == "i"
        tl = obs_metrics.REGISTRY.snapshot()["timeline"]
        assert tl[-1]["event"] == "replica.drain"
        assert tl[-1]["replica"] == 1

    def test_overflow_drops_not_grows(self):
        tr = obs_trace.Tracer(max_events=2)
        for _ in range(5):
            tr.instant("x")
        assert len(tr.events) == 2 and tr.dropped == 3
        sp = tr.begin("late")      # over budget: recorded nowhere
        sp.end()
        assert len(tr.events) == 2
        assert tr.chrome_trace()["otherData"]["dropped"] == 4


class TestServeLifecycle:
    def test_request_async_events_and_launch_spans(self, tiny):
        cfg, model, params = tiny
        eng = _engine(model, params)
        with obs_trace.tracing() as tr:
            eng.submit(_requests(cfg.vocab, [5, 9, 12]))
            eng.run_until_done(max_steps=200)
        reqs = tr.find("request")
        begins = {e["id"] for e in reqs if e["ph"] == "b"}
        ends = {e["id"] for e in reqs if e["ph"] == "e"}
        assert begins == ends == {"0", "1", "2"}
        b0 = next(e for e in reqs if e["ph"] == "b" and e["id"] == "0")
        assert b0["args"]["prompt_len"] == 5
        e0 = next(e for e in reqs if e["ph"] == "e" and e["id"] == "0")
        assert e0["args"]["tokens"] == len(eng.done[0])
        pre = tr.spans("serve.prefill")
        dec = tr.spans("serve.decode")
        assert len(pre) == eng.stats["prefill_calls"] > 0
        assert len(dec) == eng.stats["decode_steps"] > 0
        assert all(s["args"] == {"attempts": 1, "error": False}
                   for s in pre + dec)
        # artifact dispatch spans nest inside the serve launch spans
        disp = tr.spans("dispatch")
        assert disp and all(d["parent"] != -1 for d in disp)

    def test_failed_request_closes_async_span(self, tiny):
        cfg, model, params = tiny
        eng = _engine(model, params, max_batch=1)
        eng._clock = lambda: 100.0           # frozen: deadline pre-expired
        with obs_trace.tracing() as tr:
            eng.submit([Request(rid=5, tokens=np.arange(4, dtype=np.int32),
                                max_new_tokens=2, deadline_s=-200.0)])
            eng.step()
        ends = [e for e in tr.find("request") if e["ph"] == "e"]
        assert len(ends) == 1 and ends[0]["args"]["failed"] is True
        assert "DeadlineExceeded" in ends[0]["args"]["reason"]
        tl = obs_metrics.REGISTRY.snapshot()["timeline"]
        assert any(ev["event"] == "deadline.expire" and ev["rid"] == 5
                   for ev in tl)


# ----------------------------------------------------------- registry ----

class TestMetricsParity:
    def test_observe_snapshot_covers_every_domain(self, tiny):
        cfg, model, params = tiny
        eng = _engine(model, params)
        eng.submit(_requests(cfg.vocab, [5, 9]))
        eng.run_until_done(max_steps=200)
        snap = disc.observe()
        for dom in obs_metrics.DOMAINS:
            assert dom in snap, f"missing domain {dom!r}"
        assert "serve" in snap and "engine" in snap["serve"]
        assert "health" in snap and "engine" in snap["health"]
        assert "prefill" in snap["dispatch"]
        assert "prefill" in snap["memory"]
        assert any(fp.startswith("serve") for fp in snap["compile"])

    def test_engine_stats_and_health_parity(self, tiny):
        cfg, model, params = tiny
        eng = _engine(model, params)
        eng.submit(_requests(cfg.vocab, [5, 9, 12]))
        eng.run_until_done(max_steps=200)
        snap = disc.observe()
        view = snap["serve"]["engine"]
        assert set(view) == set(STATS_KEYS)
        for k, v in eng.stats.items():
            assert view[k] == v, f"stats[{k!r}] diverged"
        assert snap["health"]["engine"] == eng.report()["health"]

    def test_compiled_accessor_parity(self):
        f = _artifact("jit")
        f(np.ones((3, 4), np.float32))
        f(np.ones((5, 4), np.float32))
        snap = disc.observe()
        assert snap["dispatch"]["obs_fn"] == f.cost_report()
        fp = f.cache.fingerprint
        assert snap["compile"][fp] == dict(f.cache_stats(),
                                           entries=len(f.cache._entries))
        mem = dict(snap["memory"]["obs_fn"])
        planning = mem.pop("planning")
        assert planning is False            # jit pipeline: no buffer plan
        assert mem == f._mstats.as_dict()
        assert f.report()["dispatch_cost"] == f.cost_report()

    def test_vm_collector_parity(self):
        f = _artifact("dhlo")
        vm = NimbleVM(f.graph)
        vm(np.ones((4, 4), np.float32))
        view = disc.observe()["vm"]
        assert view["calls"] == vm.stats.calls == 1
        assert view["op_dispatches"] == vm.stats.op_dispatches
        assert view["interp_seconds"] > 0.0

    def test_latest_collector_wins_per_name(self, tiny):
        cfg, model, params = tiny
        eng1 = _engine(model, params)
        eng2 = _engine(model, params)
        eng2.submit(_requests(cfg.vocab, [5]))
        eng2.run_until_done(max_steps=100)
        view = disc.observe()["serve"]["engine"]
        assert view["requests_completed"] == 1      # eng2, not eng1
        assert eng1.stats["requests_completed"] == 0

    def test_labeled_series_and_reset(self):
        reg = obs_metrics.REGISTRY
        reg.counter("launches", kind="prefill").inc(3)
        reg.counter("launches", kind="decode").inc()
        reg.gauge("occupancy", pool="kv").set(0.5)
        h = reg.histogram("pad_waste")
        h.observe(0.25)
        h.observe(0.75)
        snap = reg.snapshot()
        assert snap["counters"]["launches{kind=prefill}"] == 3
        assert snap["counters"]["launches{kind=decode}"] == 1
        assert snap["gauges"]["occupancy{pool=kv}"] == 0.5
        assert snap["histograms"]["pad_waste"]["mean"] == 0.5
        reg.reset()
        snap = reg.snapshot()
        assert not snap["counters"] and not snap["timeline"]


# ---------------------------------------------------- cost accounting ----

class TestCostAccounting:
    @pytest.mark.parametrize("pipeline", PIPELINES)
    def test_padding_waste_and_bucket_hits(self, pipeline):
        f = _artifact(pipeline)
        f(np.ones((3, 4), np.float32))     # bucket 16, true 3
        f(np.ones((20, 4), np.float32))    # bucket 32, true 20
        f(np.ones((20, 4), np.float32))
        cost = f.cost_report()
        assert cost["calls"] == 3
        assert cost["bucket_hits"] == {"(16,)": 1, "(32,)": 2}
        # f32 rows of 4 (16 bytes): padded (16+32+32) vs true (3+20+20)
        assert cost["padded_bytes"] == 80 * 16
        assert cost["true_bytes"] == 43 * 16
        assert cost["pad_waste_ratio"] == pytest.approx(37 / 80)
        pb = cost["per_bucket"]["(32,)"]
        assert pb["calls"] == 2
        assert pb["pad_waste_ratio"] == pytest.approx(24 / 64)

    def test_dispatch_overhead_timer(self):
        f = _artifact("jit")
        for _ in range(3):
            f(np.ones((3, 4), np.float32))
        cost = f.cost_report()
        # host-side dispatch wall (key + pad plan, pre-entry) and the
        # entry call are timed separately; both must tick
        assert cost["host_dispatch_seconds"] > 0.0
        assert cost["entry_seconds"] > 0.0
        pb = cost["per_bucket"]["(16,)"]
        assert pb["host_dispatch_seconds"] > 0.0
        assert pb["entry_seconds"] > 0.0

    def test_compile_and_escalation_timeline(self):
        f = disc.compile(lambda x: x * 2.0, [ArgSpec(("S", 4), jnp.float32)],
                         options=disc.CompileOptions(
                             pipeline="jit", escalation_threshold=2))
        for _ in range(3):
            f(np.ones((5, 4), np.float32))
        tl = obs_metrics.REGISTRY.snapshot()["timeline"]
        kinds = [ev["event"] for ev in tl]
        assert "compile.bucket" in kinds
        assert "escalate" in kinds
        esc = next(ev for ev in tl if ev["event"] == "escalate")
        assert esc["key"] == "(5,)"


# ------------------------------------------------- disabled == no-op -----

class TestDisabledNoOp:
    @pytest.mark.parametrize("pipeline", PIPELINES)
    def test_dispatch_source_identical_with_tracer(self, pipeline):
        off = _artifact(pipeline)
        off(np.ones((3, 4), np.float32))
        with obs_trace.tracing():
            on = _artifact(pipeline)
            on(np.ones((3, 4), np.float32))
        assert off.dispatch_source == on.dispatch_source

    def test_no_events_recorded_when_disabled(self, tiny):
        cfg, model, params = tiny
        assert obs_trace.ACTIVE is None
        eng = _engine(model, params)
        eng.submit(_requests(cfg.vocab, [5, 9]))
        eng.run_until_done(max_steps=200)
        tr = obs_trace.install()
        try:
            assert tr.events == []
        finally:
            obs_trace.clear()

    def test_hot_path_never_grows_timeline(self, tiny):
        cfg, model, params = tiny
        eng = _engine(model, params)
        eng.submit(_requests(cfg.vocab, [5, 9]))
        eng.run_until_done(max_steps=200)       # warm: compiles journaled
        n0 = len(obs_metrics.REGISTRY.snapshot()["timeline"])
        eng.submit(_requests(cfg.vocab, [5, 9]))
        eng.run_until_done(max_steps=200)       # all-hit steady state
        assert len(obs_metrics.REGISTRY.snapshot()["timeline"]) == n0


# ----------------------------------------------------- typed reset -------

class TestResetStats:
    def test_reset_preserves_types(self, tiny):
        cfg, model, params = tiny
        eng = _engine(model, params, replicas=2, max_batch=1)
        eng.submit(_requests(cfg.vocab, [5, 9]))
        eng.run_until_done(max_steps=200)
        # regression guard: even before _refresh_stats repairs anything,
        # every key must already hold its documented type
        eng._refresh_stats = lambda: None
        eng.reset_stats()
        assert isinstance(eng.stats["per_replica"], list)
        assert len(eng.stats["per_replica"]) == 2
        for rep in eng.stats["per_replica"]:
            assert rep == {"admitted": 0, "tokens_generated": 0,
                           "requests_completed": 0, "occupied_slots": 0}
        for k in ("tokens_per_sec", "kv_pool_occupancy",
                  "kv_peak_occupancy"):
            assert isinstance(eng.stats[k], float)
        ints = set(STATS_KEYS) - {"per_replica", "tokens_per_sec",
                                  "kv_pool_occupancy", "kv_peak_occupancy"}
        assert all(eng.stats[k] == 0 and isinstance(eng.stats[k], int)
                   for k in ints)

    def test_reset_keeps_dict_identity(self, tiny):
        cfg, model, params = tiny
        eng = _engine(model, params)
        held = eng.stats                 # benchmarks hold this reference
        eng.submit(_requests(cfg.vocab, [5]))
        eng.run_until_done(max_steps=100)
        eng.reset_stats()
        assert held is eng.stats
        assert held["requests_completed"] == 0


# ------------------------------------------------------- chrome trace ----

def _validate_trace_event(ev):
    assert set(("name", "cat", "ph", "ts", "pid", "tid", "args")) <= set(ev)
    assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
    assert isinstance(ev["args"], dict)
    assert "parent" not in ev and "depth" not in ev
    if ev["ph"] == "X":
        assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
    elif ev["ph"] == "i":
        assert ev["s"] == "t"
    elif ev["ph"] in ("b", "e"):
        assert isinstance(ev["id"], str)
    else:
        assert ev["ph"] == "C"


class TestChromeExport:
    def test_schema_and_roundtrip(self, tiny, tmp_path):
        cfg, model, params = tiny
        eng = _engine(model, params)
        disc.observe.start_trace()
        try:
            eng.submit(_requests(cfg.vocab, [5, 9]))
            eng.run_until_done(max_steps=200)
            path = tmp_path / "trace.json"
            disc.observe.export_chrome_trace(path)
        finally:
            tr = disc.observe.stop_trace()
        doc = json.loads(path.read_text())
        assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
        assert doc["displayTimeUnit"] == "ms"
        assert len(doc["traceEvents"]) == len(tr.events)
        phases = set()
        for ev in doc["traceEvents"]:
            _validate_trace_event(ev)
            phases.add(ev["ph"])
        assert {"X", "b", "e"} <= phases
        # spans and their async pairs must be time-ordered in µs
        ts = [ev["ts"] for ev in doc["traceEvents"]]
        assert ts == sorted(ts)

    def test_export_without_tracer_raises(self, tmp_path):
        with pytest.raises(RuntimeError, match="no active tracer"):
            disc.observe.export_chrome_trace(tmp_path / "x.json")


# ------------------------------------------------------------- clocks ----

class TestClocks:
    def test_clock_fixed_source(self):
        t = [10.0]
        with CLOCK.fixed(lambda: t[0]):
            assert CLOCK() == 10.0
            t[0] = 11.5
            assert CLOCK() == 11.5
        assert CLOCK() != 11.5      # perf_counter restored

    def test_heartbeat_monitor_injected_clock(self):
        t = [0.0]
        mon = HeartbeatMonitor(["h0", "h1"], deadline_s=5.0,
                               clock=lambda: t[0])
        mon.beat("h0")
        mon.beat("h1")
        t[0] = 4.0
        assert mon.dead_hosts() == []
        mon.beat("h1")
        t[0] = 6.0
        assert mon.dead_hosts() == ["h0"]   # h1 beat at t=4, alive

    def test_monitor_defaults_to_obs_clock(self):
        t = [100.0]
        mon = HeartbeatMonitor(["h0"], deadline_s=1.0)
        with CLOCK.fixed(lambda: t[0]):
            mon.beat("h0")
            t[0] = 102.0
            assert mon.dead_hosts() == ["h0"]

    def test_tracer_timestamps_use_injected_clock(self):
        t = [0.0]
        with CLOCK.fixed(lambda: t[0]):
            tr = obs_trace.Tracer()
            sp = tr.begin("a")
            t[0] = 0.25
            sp.end()
        ev = tr.events[0]
        assert ev["ts"] == 0.0 and ev["dur"] == 0.25


# ------------------------------------------------------ profiler mirror --

def _intervals(trace_dir, names):
    """Host-plane events of the newest profile under ``trace_dir``, by
    name: ``{name: [(start_ns, end_ns), ...]}``."""
    path = max(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(path)
    out = {n: [] for n in names}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in out:
                    out[ev.name].append((ev.start_ns,
                                         ev.start_ns + ev.duration_ns))
    return out


def _inside(child, parents):
    return any(a <= child[0] and child[1] <= b for a, b in parents)


def _parent_name(tr, ev):
    return tr.events[ev["parent"]]["name"] if ev["parent"] >= 0 else None


class TestProfilerMirror:
    def test_engine_spans_on_the_host_plane(self, tiny, tmp_path):
        cfg, model, params = tiny
        eng = _engine(model, params)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        with obs_trace.tracing(obs_trace.Tracer(profiler=True)) as tr:
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                eng.submit(_requests(cfg.vocab, [5, 9, 12]))
                eng.run_until_done(max_steps=200)
            finally:
                jax.profiler.stop_trace()
        names = ("serve.step", "serve.prefill_group", "serve.decode_step",
                 "serve.prefill", "serve.tokens")
        host = _intervals(tmp_path, names)
        for n in names:
            assert len(host[n]) == len(tr.spans(n)) > 0, n
        assert all(_inside(g, host["serve.step"])
                   for g in host["serve.prefill_group"])
        assert all(_inside(d, host["serve.step"])
                   for d in host["serve.decode_step"])
        assert all(_inside(p, host["serve.prefill_group"])
                   for p in host["serve.prefill"])
        assert all(_inside(t, host["serve.decode_step"])
                   for t in host["serve.tokens"])

    def test_buffer_nesting_and_origin(self, tiny):
        cfg, model, params = tiny
        eng = _engine(model, params)
        t0 = CLOCK()
        with obs_trace.tracing() as tr:
            eng.submit(_requests(cfg.vocab, [5, 9, 12]))
            eng.run_until_done(max_steps=200)
        t1 = CLOCK()
        want = {"serve.admit": "serve.step",
                "serve.prefill_group": "serve.step",
                "serve.decode_step": "serve.step",
                "serve.rows.gather": "serve.prefill_group",
                "serve.rows.scatter": "serve.prefill_group",
                "serve.first_tokens": "serve.prefill_group",
                "serve.prefill": "serve.prefill_group",
                "serve.decode": "serve.decode_step",
                "serve.tokens": "serve.decode_step"}
        for child, parent in want.items():
            spans = tr.spans(child)
            assert spans, child
            assert {_parent_name(tr, e) for e in spans} == {parent}, child
        assert all(_parent_name(tr, e) is None
                   for e in tr.spans("serve.step"))
        steps = tr.spans("serve.step")
        assert t0 <= steps[0]["ts"] + tr.origin
        assert steps[-1]["ts"] + steps[-1]["dur"] + tr.origin <= t1
        group = tr.spans("serve.prefill_group")[0]
        assert group["args"]["rids"] == [0, 1]
        assert group["args"]["lens"] == [5, 9]
        assert (group["args"]["B"], group["args"]["S"]) == (2, 16)
        admits = [e["args"]["rids"] for e in tr.spans("serve.admit")]
        assert sorted(r for a in admits for r in a) == [0, 1, 2]
        dec = tr.spans("serve.decode_step")
        assert len(dec) == eng.stats["decode_steps"]
        assert set(dec[0]["args"]["rids"]) <= {0, 1}


class TestRequestLifecycle:
    def test_queued_from_submit_to_admission(self, tiny):
        cfg, model, params = tiny
        eng = _engine(model, params)            # two slots, three requests
        with obs_trace.tracing() as tr:
            eng.submit(_requests(cfg.vocab, [5, 9, 12]))
            n_submit = len(tr.events)
            eng.run_until_done(max_steps=200)
        queued = tr.find("request.queued")
        opens = [e for e in queued if e["ph"] == "b"]
        assert [e["id"] for e in opens] == ["0", "1", "2"]
        assert all(tr.events.index(e) < n_submit for e in opens)
        assert opens[2]["args"]["prompt_len"] == 12
        for rid in ("0", "1", "2"):
            ev = [e for e in tr.events if e.get("id") == rid]
            # queued opens, queued closes, then the request span opens
            assert [(e["name"], e["ph"]) for e in ev][:3] == [
                ("request.queued", "b"), ("request.queued", "e"),
                ("request", "b")]
            close = ev[1]
            admit = next(a for a in tr.spans("serve.admit")
                         if int(rid) in a["args"]["rids"])
            assert admit["ts"] <= close["ts"] <= admit["ts"] + admit["dur"]
        # the third waits for a slot: admitted after a request retired
        retired = min(e["ts"] for e in tr.find("request") if e["ph"] == "e")
        assert ev[1]["ts"] >= retired

    @pytest.mark.parametrize("chunk", [None, 4])
    def test_first_token_once_per_request(self, tiny, chunk):
        cfg, model, params = tiny
        eng = _engine(model, params, prefill_chunk=chunk)
        with obs_trace.tracing() as tr:
            eng.submit(_requests(cfg.vocab, [5, 9, 12]))
            eng.run_until_done(max_steps=400)
        firsts = tr.find("request.first_token")
        assert sorted(e["args"]["rid"] for e in firsts) == [0, 1, 2]
        assert all(e["ph"] == "i" and _parent_name(tr, e) ==
                   "serve.prefill_group" for e in firsts)
        # the token is on the host: after its group's wait for logits
        for e in firsts:
            (wait,) = [w for w in tr.spans("serve.first_tokens")
                       if w["parent"] == e["parent"]]
            assert e["ts"] >= wait["ts"] + wait["dur"]

    def test_queued_request_failing_closes_queued_span(self, tiny):
        cfg, model, params = tiny
        eng = _engine(model, params, max_batch=1)
        eng._clock = lambda: 100.0
        with obs_trace.tracing() as tr:
            eng.submit([Request(rid=5, tokens=np.arange(4, dtype=np.int32),
                                max_new_tokens=2, deadline_s=-200.0)])
            eng.step()
        q = tr.find("request.queued")
        assert [e["ph"] for e in q] == ["b", "e"]
        assert q[1]["args"]["failed"] is True


# ------------------------------------------------------ compile spans --

class TestCompileSpans:
    def test_jit_compile_span_is_the_first_entry_call(self):
        f = _artifact("jit")
        with obs_trace.tracing() as tr:
            f(np.ones((3, 4), np.float32))      # miss: traces + compiles
            f(np.ones((3, 4), np.float32))      # hit
        comp = tr.spans("compile.bucket")
        assert len(comp) == 1
        c = comp[0]
        miss = tr.spans("dispatch")[0]
        assert c["parent"] == tr.events.index(miss)
        # JAX's own trace and backend compile ran inside the span
        assert c["args"]["trace_s"] > 0.0
        assert c["args"]["backend_compile_s"] > 0.0
        assert c["dur"] >= (c["args"]["trace_s"] + c["args"]["lower_s"]
                            + c["args"]["backend_compile_s"])
        assert c["args"]["persistent_cache_hit"] in (True, False)
        # the span is the entry call, inside the dispatch's timed call
        assert c["dur"] <= miss["args"]["entry_seconds"]
        st = f.cache.stats
        assert st.compile_seconds == pytest.approx(c["dur"], rel=0.05,
                                                   abs=1e-3)
        # a later call to the bucket compiles nothing
        with obs_trace.tracing() as tr2:
            f(np.ones((5, 4), np.float32))
        assert not tr2.spans("compile.bucket")
        assert f.cache.stats.compile_seconds == st.compile_seconds

    def test_jit_exact_compile_span_is_the_first_call(self):
        f = disc.compile(lambda x: x * 2.0, [ArgSpec(("S", 4), jnp.float32)],
                         options=disc.CompileOptions(
                             pipeline="jit", escalation_threshold=2))
        with obs_trace.tracing() as tr:
            for _ in range(3):
                f(np.ones((5, 4), np.float32))
        exact = tr.spans("compile.exact")
        assert len(exact) == 1
        assert exact[0]["args"]["backend_compile_s"] > 0.0
        assert exact[0]["dur"] >= exact[0]["args"]["backend_compile_s"]

    def test_dhlo_compile_span_times_the_build(self):
        f = _artifact("dhlo")
        with obs_trace.tracing() as tr:
            f(np.ones((3, 4), np.float32))
        (c,) = tr.spans("compile.bucket")
        assert c["args"]["backend_compile_s"] > 0.0
        assert c["dur"] >= c["args"]["backend_compile_s"]
        assert f.cache.stats.compile_seconds == pytest.approx(
            c["dur"], rel=0.05, abs=1e-3)


# -------------------------------------------------------- model scopes --

_METADATA = re.compile(r",? metadata=\{[^}]*\}")


def _program(hlo):
    """The computations of a compiled HLO text without their metadata
    (the source-location tables between the header and the first
    computation go too)."""
    return [_METADATA.sub("", ln) for ln in hlo.splitlines()
            if ln.startswith(("%", "ENTRY", "}", "  "))]


def _prefill_hlo(model, params):
    cache = model.init_cache(2, 32)
    tokens = jnp.zeros((2, 8), jnp.int32)
    lens = jnp.asarray([5, 8], jnp.int32)
    offsets = jnp.zeros((2,), jnp.int32)
    return jax.jit(lambda *a: model.prefill(*a)).lower(
        params, cache, tokens, lens, offsets).compile().as_text()


class TestModelScopes:
    def test_scopes_are_metadata_only(self, tiny, monkeypatch):
        cfg, model, params = tiny
        scoped = _prefill_hlo(model, params)
        ops = set(re.findall(r'op_name="([^"]*)"', scoped))
        for scope in ("embed", "norm", "attn/qkv", "attn/cache_write",
                      "attn/core", "attn/out", "mlp", "head"):
            assert any(f"/{scope}/" in o for o in ops), scope
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        plain = _prefill_hlo(model, params)
        assert "attn/cache_write" not in plain
        assert _program(scoped) == _program(plain)
        assert len(_program(plain)) > 100
