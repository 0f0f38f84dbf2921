"""Each metric reader on a canned run record."""
import importlib.util
import json

import pytest

from bench_tiny import DENSE, ROOT, TINY_ARCH
from bench.lib import flops

A = TINY_ARCH["dense_gqa"]
PEAKS = {"flops_bf16": 1e12, "hbm_bw": 1e11, "hbm_bytes": 16e9}


def canned(**kw):
    run = {
        "seconds": 10.0, "closed_at": 10.2, "end": 11.0, "setup_s": 42.0,
        "arch": A, "peaks": PEAKS,
        "model": str(DENSE.__file__),
        "requests": [
            {"rid": 0, "arrival": 1.0, "admit": 1.5, "tokens": [2.0, 3.0, 4.0]},
            {"rid": 1, "arrival": 5.0, "admit": 5.1, "tokens": [5.5, 10.5]},
            {"rid": 2, "arrival": 9.5, "admit": 10.6, "tokens": [10.8]},
        ],
        "prefills": [(1.4, 2.0, [100, 50]), (10.1, 10.8, [30])],
        "decodes": [(2.5, 3.0, [101, 51]), (3.5, 4.0, [102])],
        "prefill_buckets": {"2,128": 1},
        "prefill_dispatch": {"calls": 1, "host_s": 0.0002},
        "trace": {"window_s": 10.0, "busy_s": 7.0,
                  "program_s": {"prefill": 0.5, "decode": 0.8, "other": 0.0}},
    }
    run.update(kw)
    return run


def read(name, run):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"m_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def least(f, b):
    return flops.least_time(f, b, PEAKS["flops_bf16"], PEAKS["hbm_bw"])


PF = flops.prefill_flops(DENSE, A, [100, 50])
DF = (flops.decode_flops(DENSE, A, [101, 51])
      + flops.decode_flops(DENSE, A, [102]))
EXPECTED = {
    # tokens back by 10 s: 3 of r0, 1 of r1
    "output_tok_s": 0.4,
    # first tokens after arrival: 1.0, 0.5, 1.3 s
    "ttft_p90_ms": 1240.0,
    # gaps that closed by 10 s: r0's two 1 s gaps
    "itl_p99_ms": 1000.0,
    "setup_s": 42.0,
    # admission after arrival: 0.5, 0.1, 1.1 s
    "queue_wait_p90_ms": 980.0,
    "prefill_pad_share": 100 * (1 - 150 / 256),
    "prefill_dispatch_host_us": 200.0,
    "prefill_roofline":
    100 * least(PF, flops.prefill_bytes(DENSE, A, [100, 50])) / 0.5,
    "prefill_mfu": 100 * PF / (0.6 * 1e12),
    "decode_rows_per_step": 1.5,
    "decode_roofline": 100 * (
        least(flops.decode_flops(DENSE, A, [101, 51]),
              flops.decode_bytes(DENSE, A, [101, 51]))
        + least(flops.decode_flops(DENSE, A, [102]),
                flops.decode_bytes(DENSE, A, [102])))
    / 0.8,
    "step_mfu": 100 * (PF + DF) / (10 * 1e12),
    "device_idle_share": 30.0,
}
# the closed-loop cell's split of the same readings (its end-to-end
# one carries a bound of its own), and the open-loop cell's per-layer
# token gap
EXPECTED.update({f"{n}.backlog": EXPECTED[n] for n in (
    "output_tok_s", "step_mfu", "decode_roofline", "device_idle_share")})
EXPECTED["itl_p99_ms.open"] = EXPECTED["itl_p99_ms"]


def test_every_metric_has_a_reader_and_a_case():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    readers = {p.stem for p in (ROOT / "bench" / "metrics").glob("*.py")}
    assert names <= readers == set(EXPECTED)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_canned_run(name):
    assert read(name, canned()) == pytest.approx(EXPECTED[name])


def with_lead_in():
    """The canned run after a closed loop's lead-in: a request sent, a
    token returned, a prefill and a decode step, all before the window
    opened."""
    run = canned()
    run["requests"].append({"rid": 9, "arrival": -2.0, "admit": -1.5,
                            "tokens": [-1.0, -0.5, 0.5, 11.0]})
    run["prefills"].insert(0, (-1.8, -1.0, [64]))
    run["decodes"].insert(0, (-0.9, -0.5, [64, 12]))
    return run


@pytest.mark.parametrize("name", sorted(set(EXPECTED) - {
    "output_tok_s", "output_tok_s.backlog", "itl_p99_ms",
    "itl_p99_ms.open"}))
def test_reader_ignores_the_lead_in(name):
    assert read(name, with_lead_in()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", ["output_tok_s", "output_tok_s.backlog"])
def test_tokens_of_the_lead_in_are_not_counted(name):
    # the lead-in request's one token inside the window counts
    assert read(name, with_lead_in()) == pytest.approx(0.5)


@pytest.mark.parametrize("name", ["itl_p99_ms", "itl_p99_ms.open"])
def test_gaps_of_the_lead_in_are_not_counted(name):
    # its gaps that closed in the window: 0.5 - (-0.5) = 1.0 s, as r0's
    assert read(name, with_lead_in()) == pytest.approx(1000.0)


@pytest.mark.parametrize("name", ["prefill_roofline", "decode_roofline",
                                  "device_idle_share"])
def test_trace_readers_read_nothing_without_a_trace(name):
    assert read(name, canned(trace=None)) is None


@pytest.mark.parametrize("name", ["prefill_roofline", "decode_roofline",
                                  "prefill_mfu", "step_mfu"])
def test_peak_readers_read_nothing_without_peaks(name):
    assert read(name, canned(peaks=None)) is None


@pytest.mark.parametrize("name", ["prefill_pad_share", "prefill_mfu",
                                  "prefill_roofline",
                                  "prefill_dispatch_host_us"])
def test_prefill_readers_read_nothing_without_prefills(name):
    run = canned(prefills=[], prefill_buckets={},
                 prefill_dispatch={"calls": 0, "host_s": 0.0})
    run["trace"]["program_s"]["prefill"] = 0.0
    assert read(name, run) is None
