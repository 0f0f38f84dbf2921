"""Traffic generation: deterministic in the seed, stratified lengths
that match the traffic files' medians and clips."""
import json

import numpy as np
import pytest

from bench_tiny import ROOT, TINY_TRAFFIC
from bench.lib.traffic import LengthDist, Traffic, window_rate

SEED = 2**31 + 12345   # larger than 32 signed bits hold
NAMES = ["code_completion", "tiny_open"]
CLOSED = ["chat_backlog", "tiny_closed"]


def spec(name):
    if name.startswith("tiny_"):
        return TINY_TRAFFIC[name[len("tiny_"):]]
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", NAMES)
def test_same_seed_same_requests(name):
    a, b = Traffic(spec(name), SEED, 50000), Traffic(spec(name), SEED, 50000)
    for i in range(70):
        assert a.prompt_len(i) == b.prompt_len(i)
        assert a.output_len(i) == b.output_len(i)
        assert np.array_equal(a.tokens(i), b.tokens(i))
    assert [a.arrival(i) for i in range(70)] == \
        [b.arrival(i) for i in range(70)]


@pytest.mark.parametrize("name", NAMES)
def test_other_seed_same_schedule_other_tokens(name):
    s = spec(name)
    n = 2 * s["block"]
    a, b = Traffic(s, SEED, 50000), Traffic(s, SEED + 1, 50000)
    for f in ("prompt_len", "output_len", "arrival"):
        assert [getattr(a, f)(i) for i in range(n)] == \
            [getattr(b, f)(i) for i in range(n)]
    assert not np.array_equal(a.tokens(0), b.tokens(0))
    # each block holds the quantiles, in an order of its own
    pa = [a.prompt_len(i) for i in range(n)]
    assert sorted(pa[:n // 2]) == sorted(pa[n // 2:])
    assert pa[:n // 2] != pa[n // 2:]


@pytest.mark.parametrize("name", NAMES + CLOSED)
@pytest.mark.parametrize("which", ["prompt_len", "output_len"])
def test_lengths_match_median_and_clip(name, which):
    s = spec(name)
    d = s[which]
    tr = Traffic(s, SEED, 50000)
    xs = np.array([getattr(tr, which)(i) for i in range(4 * s["block"])])
    assert xs.min() >= d["min"] and xs.max() <= d["max"]
    assert abs(np.median(xs) - d["median"]) <= 0.03 * d["median"] + 1
    assert len(tr.tokens(0)) == tr.prompt_len(0)


def test_quantiles_are_lognormal():
    q = LengthDist(100.0, 0.5, 1, 10**6).quantiles(1000)
    assert abs(np.median(q) - 100) <= 1
    # the 84th percentile of a lognormal is median * e^sigma
    assert abs(np.percentile(q, 84.13) / 100 - np.exp(0.5)) < 0.02


def test_open_loop_rate_and_token_ids():
    s = spec("code_completion")
    tr = Traffic(s, SEED, 1000)
    n = s["block"]
    mean_gap = tr.arrival(4 * n - 1) / (4 * n)
    assert abs(mean_gap * s["rate_per_s"] - 1) < 0.02
    ids = np.concatenate([tr.tokens(i) for i in range(20)])
    assert ids.min() >= 2 and ids.max() < 1000


def pow2(n):
    return max(16, 1 << (n - 1).bit_length())


def test_clip_buckets():
    tr = Traffic(spec("code_completion"), SEED, 1000)
    pairs = tr.clip_buckets(pow2)
    assert pairs == [(64, 64), (128, 128), (256, 256), (512, 512),
                     (1024, 1024), (2048, 1920)]
    # chunks no shorter than the longest prompt change nothing
    assert tr.clip_buckets(pow2, 1920) == pairs


def test_clip_buckets_of_chunked_prompts():
    """Prompts of 4-1024 tokens in chunks of 256: every chunk is at most
    256 long, and the last chunk of a prompt can be any length."""
    tr = Traffic(spec("chat_backlog"), SEED, 1000)
    assert tr.clip_buckets(pow2, 256) == [(16, 16), (32, 32), (64, 64),
                                          (128, 128), (256, 256)]
    assert tr.clip_buckets(pow2) == [(16, 16), (32, 32), (64, 64),
                                     (128, 128), (256, 256), (512, 512),
                                     (1024, 1024)]


@pytest.mark.parametrize("which,mean", [("prompt_len", 161.31),
                                        ("output_len", 337.99)])
def test_chat_backlog_has_the_sources_means(which, mean):
    """The ShareGPT means that the vLLM paper gives (Sec. 6.1, Fig. 11):
    every block of stratified lengths has them, within 0.1%."""
    s = spec("chat_backlog")
    tr = Traffic(s, SEED, 50000)
    n = s["block"]
    for b in range(3):
        xs = [getattr(tr, which)(i) for i in range(b * n, (b + 1) * n)]
        assert np.mean(xs) == pytest.approx(mean, rel=1e-3)


@pytest.mark.parametrize("name", CLOSED)
def test_closed_loop_lengths_depend_on_the_index_alone(name):
    """Request i has the same lengths under every seed (and in an open
    loop of the same distributions), whichever client sends it; the seed
    draws its token ids."""
    s = spec(name)
    a, b = Traffic(s, SEED, 50000), Traffic(s, SEED + 1, 50000)
    opened = Traffic(dict(s, loop="open", rate_per_s=1.0), SEED, 50000)
    n = 2 * s["block"]
    for f in ("prompt_len", "output_len"):
        assert [getattr(a, f)(i) for i in range(n)] == \
            [getattr(b, f)(i) for i in range(n)] == \
            [getattr(opened, f)(i) for i in range(n)]
    assert not np.array_equal(a.tokens(0), b.tokens(0))
    assert (a.clients, a.think_s, a.lead_in_steps) == \
        (s["clients"], s["think_s"], s["lead_in_steps"])


@pytest.mark.parametrize("bad", [{"loop": "poisson"}, {"clients": 0},
                                 {"lead_in_steps": -1}])
def test_a_malformed_loop_is_refused(bad):
    with pytest.raises(ValueError):
        Traffic(dict(spec("tiny_closed"), **bad), SEED, 1000)


@pytest.mark.parametrize("seed", [SEED, 7, 2**33 + 5])
def test_window_holds_exactly_one_block(seed):
    """At the traffic file's rate, a window of ``run_seconds`` holds the
    whole first block and nothing of the next."""
    s = spec("code_completion")
    seconds = json.loads((ROOT / "BENCHMARK.json").read_text())[
        "run_seconds"]
    assert s["rate_per_s"] == pytest.approx(
        window_rate(s["block"], seconds), rel=1e-5)
    tr = Traffic(s, seed, 1000)
    n = s["block"]
    assert tr.arrival(n - 1) < seconds < tr.arrival(n)
