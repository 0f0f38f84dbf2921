"""The closed loop at tiny size, with the engine splitting prompts into
chunks: every key of the configuration's ``serve`` block reaches the
engine, set-up warms the chunks' buckets, never more than ``clients``
requests are in flight, a client sends its next request before the step
after its last one finished, and the readers count nothing of the
lead-in."""
import json

import pytest

from bench_tiny import TINY_ARCH, TINY_TRAFFIC, make_root
from bench.lib import harness, readings, trace
from bench.lib.engine import no_span, trace_span

SEED = 2**31 + 5501
NAME = "tiny_chunked.closed"
SERVE = {"max_batch": 3, "max_seq": 128, "prefill_chunk": 32,
         "prefill_interleave": 2}
CLIENTS = TINY_TRAFFIC["closed"]["clients"]


@pytest.fixture(scope="module")
def closed(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("closed"))
    (root / "bench" / "configs" / "tiny_chunked.json").write_text(json.dumps({
        "model": "dense_decoder", "arch": TINY_ARCH["dense_mqa"],
        "serve": SERVE,
        "check": {"requests": 1000, "min_tokens": 8, "logit_gap": 0.007}}))
    (root / "bench" / "traffic" / "closed.json").write_text(
        json.dumps(TINY_TRAFFIC["closed"]))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": NAME, "config": "tiny_chunked",
                               "traffic": "closed", "chips": 1,
                               "why": "CPU test"})
    for m in bench["end_to_end"]:
        if m["name"] == "output_tok_s":
            m["workloads"].append(NAME)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.Cell(root, NAME, require_tpu=False)
    cell.build(SEED)
    run = cell.run(SEED, 1.5)
    return cell, run


def finished(cell, run):
    """Finish times (the observation after the step that finished each
    request), in order."""
    return sorted(r["tokens"][-1] for r in run["requests"]
                  if r["rid"] in cell.engine.done)


def test_serve_keys_reach_the_engine(closed):
    cell, _ = closed
    scfg = cell.engine.scfg
    assert {k: getattr(scfg, k) for k in SERVE} == SERVE
    assert scfg.eos_id == -1


def test_chunks_are_warmed_and_the_run_is_correct(closed):
    cell, run = closed
    assert run["window_compiles"] == 0
    assert max(r["plen"] for r in run["requests"]) > SERVE["prefill_chunk"]
    assert max(n for p in run["prefills"] for n in p[2]) \
        <= SERVE["prefill_chunk"]
    checks = cell.check(run, cell.sample(run, SEED), SEED)
    assert harness.is_correct(checks), checks


def test_never_more_than_clients_in_flight(closed):
    cell, run = closed
    reqs = run["requests"]
    done = cell.engine.done

    def in_flight(t):
        return sum(1 for q in reqs if q["arrival"] <= t
                   and (q["rid"] not in done or q["tokens"][-1] > t))

    counts = [in_flight(r["arrival"]) for r in reqs]
    assert max(counts) == CLIENTS
    assert len(reqs) > 3 * CLIENTS


def test_next_request_is_sent_before_the_next_step(closed):
    cell, run = closed
    sends = sorted(r["arrival"] for r in run["requests"])
    assert sends[:CLIENTS] == [sends[0]] * CLIENTS
    launches = sorted(x[0] for x in run["prefills"] + run["decodes"])
    fin = finished(cell, run)
    paired = list(zip(fin, sends[CLIENTS:]))
    assert len(paired) == len(sends) - CLIENTS > 0
    for f, a in paired:
        assert f <= a
        assert not any(f < s < a for s in launches), (f, a)
    # nothing is sent once the window has closed
    assert sends[-1] < run["seconds"] <= run["closed_at"]


def test_the_lead_in_is_not_counted(closed):
    cell, run = closed
    reqs = run["requests"]
    s = run["seconds"]
    assert run["loop"] == "closed" and run["lead_in_s"] > 0
    assert run["lead_in_steps"] == TINY_TRAFFIC["closed"]["lead_in_steps"]
    assert any(r["arrival"] < 0 for r in reqs)
    assert any(x[0] < 0 for x in run["decodes"])
    assert all(0 <= r["arrival"] < s for r in readings.window_requests(run))
    assert all(d[0] >= 0 for d in readings.decodes(run))
    assert all(p[0] >= 0 for p in readings.prefills(run))
    early = sum(1 for r in reqs for t in r["tokens"] if t < 0)
    inside = sum(1 for r in reqs for t in r["tokens"] if 0 <= t <= s)
    assert early > 0
    run["setup_s"] = 1.0
    got = harness.read_metrics(
        cell.root, harness.cell_metrics(cell.bench, NAME, False), run)
    assert got["output_tok_s"]["value"] == pytest.approx(inside / s)


def test_the_window_opens_after_the_lead_in_steps(closed):
    """Every engine step of this loop launches a prefill or a decode
    step; the window opens after ``lead_in_steps`` of them."""
    _, run = closed
    before = [x for x in run["prefills"] + run["decodes"] if x[0] < 0]
    assert len(before) == run["lead_in_steps"]


def test_every_run_opens_at_the_same_step(closed):
    """With no think time the step sequence is the same under every
    seed, and the window opens at the same point of it: the same
    requests sent, the same tokens returned and the same launches made
    before it."""
    cell, run = closed

    def before(r):
        return ([(q["rid"], len([t for t in q["tokens"] if t < 0]))
                 for q in r["requests"] if q["arrival"] < 0],
                [x[2] for x in r["prefills"] if x[0] < 0],
                [x[2] for x in r["decodes"] if x[0] < 0])

    other = cell.run(SEED + 2, 1.0)
    assert before(other) == before(run)


def test_the_traced_window_opens_after_the_lead_in(closed, tmp_path):
    """The profiler's ``bench.window`` span is made when the window
    opens (a span's start is taken when it is made), so the trace
    reduction sees the window alone."""
    cell, _ = closed
    cell.span = trace_span
    try:
        run = cell.run(SEED + 1, 0.5, trace_dir=str(tmp_path))
    finally:
        cell.span = no_span
    wins = [e for e in trace.load_events(str(tmp_path))
            if e.name == trace.WINDOW]
    assert len(wins) == 1
    assert wins[0].dur_ns / 1e9 == pytest.approx(run["closed_at"],
                                                 abs=0.05)
