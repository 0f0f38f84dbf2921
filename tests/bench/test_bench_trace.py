"""The trace reduction on a small synthetic trace: busy union, program
time, top operations and idle time named by host spans."""
import pytest

from bench_tiny import ROOT  # noqa: F401  (puts the repository on the path)
from bench.lib.trace import Event, classify, reduce_events, union

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def ev(plane, line, name, a, b):
    return Event(plane, line, name, float(a), float(b - a))


def synthetic(two_devices=False):
    evs = [
        ev(HOST, "python", "bench.window", 0, 40),
        ev(HOST, "python", "bench.prefill", 0, 16),
        ev(HOST, "python", "bench.idle", 16, 19),
        ev(HOST, "python", "bench.decode", 19, 31),
        ev(HOST, "python", "bench.launch.decode", 19, 20),
        ev(HOST, "python", "not.ours", 30, 40),
        ev(DEV0, "XLA Ops", "fusion.1", -5, 10),    # starts before window
        ev(DEV0, "XLA Ops", "convolution.2", 5, 15),
        ev(DEV0, "XLA Ops", "fusion.1", 20, 30),
        ev(DEV0, "XLA Modules", "jit__prefill_call(7)", -5, 15),
        ev(DEV0, "XLA Modules", "jit__decode_step(9)", 20, 30),
        ev(DEV0, "XLA Modules", "jit_argmax", 45, 50),   # after window
        ev(DEV0, "Steps", "ignored", 0, 40),
    ]
    if two_devices:
        evs += [ev(DEV1, "XLA Ops", "fusion.1", 0, 5),
                ev(DEV1, "XLA Modules", "jit__decode_step(9)", 0, 5)]
    return evs


def test_busy_union_programs_and_idle():
    r = reduce_events(synthetic())
    assert r["window_s"] == pytest.approx(40e-9)
    assert r["busy_s"] == pytest.approx(25e-9)        # [0,15] + [20,30]
    assert r["program_s"] == pytest.approx(
        {"prefill": 15e-9, "decode": 10e-9, "other": 0.0})
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(20e-9)]
    assert r["device_ops"][1] == ["convolution.2", pytest.approx(10e-9)]
    # gap [15, 20] falls in bench.idle; gap [30, 40] in no bench span
    assert r["idle_gaps"] == [["host: no bench span", pytest.approx(10e-9)],
                              ["bench.idle", pytest.approx(5e-9)]]


def test_innermost_span_names_a_gap():
    evs = [ev(HOST, "t", "bench.window", 0, 100),
           ev(HOST, "t", "bench.decode", 0, 100),
           ev(HOST, "t", "bench.launch.decode", 40, 60),
           ev(DEV0, "XLA Ops", "op", 0, 45),
           ev(DEV0, "XLA Ops", "op", 55, 100)]
    assert reduce_events(evs)["idle_gaps"] == [
        ["bench.launch.decode", pytest.approx(10e-9)]]


def test_devices_are_averaged():
    r = reduce_events(synthetic(two_devices=True))
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((25e-9 + 5e-9) / 2)
    assert r["program_s"]["decode"] == pytest.approx((10e-9 + 5e-9) / 2)


def test_missing_window_or_device_is_an_error():
    with pytest.raises(ValueError):
        reduce_events([e for e in synthetic() if e.name != "bench.window"])
    with pytest.raises(ValueError):
        reduce_events([e for e in synthetic() if e.plane == HOST])


def test_union_and_classify():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert classify("jit__prefill_call") == "prefill"
    assert classify("jit_decode_step.3") == "decode"
    assert classify("jit_argmax") == "other"
