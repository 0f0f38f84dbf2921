"""The reduction of the program's spans against a synthetic device trace:
scope self times under a layer-scan ``while``, idle time split by engine
work, and the readings taken off the tracer's buffer."""
import pytest

from bench_tiny import ROOT  # noqa: F401  (puts the repository on the path)
from bench.lib.spans import (Op, admit_waits, hlo_head, op_names, reduce,
                             scope_of, self_times, setup_compile_s)

DEV, HOST = "/device:TPU:0", "/host:CPU"


def op(name, a, b, scope="", plane=DEV, line="XLA Ops"):
    return Op(plane, line, name, float(a), float(b - a), scope)


def host(name, a, b):
    return op(name, a, b, plane=HOST, line="python")


def test_self_time_of_a_while_holding_fusions():
    ops = [op("while.51", 0, 100),
           op("fusion.1", 10, 30, "attn/cache_write"),
           op("fusion.2", 30, 35, "attn/core"),
           op("copy.3", 40, 45, "attn/cache_write"),   # inside fusion.4
           op("fusion.4", 40, 60, "mlp"),
           op("fusion.5", 120, 130, "head")]
    ops[3], ops[4] = ops[4], ops[3]       # order on the line is free
    st = dict(zip((o.name for o in ops), self_times(ops)))
    assert st == {"while.51": 100 - 20 - 5 - 20, "fusion.1": 20,
                  "fusion.2": 5, "fusion.4": 20 - 5, "copy.3": 5,
                  "fusion.5": 10}


def synthetic():
    return [
        host("bench.window", 0, 100),
        host("bench.submit", 0, 2),
        host("serve.step", 2, 40),
        host("serve.prefill_group", 3, 39),
        host("bench.idle", 40, 60),
        host("serve.step", 60, 98),
        op("jit__prefill_call(3)", 5, 35, line="XLA Modules"),
        op("while.1", 5, 35),
        op("fusion.1", 5, 25, "attn/cache_write"),
        op("fusion.2", 25, 30, "attn/core"),
        op("jit__decode_step(4)", 70, 90, line="XLA Modules"),
        op("while.2", 70, 90),
        op("fusion.1", 70, 80, "mlp"),
        op("fusion.9", 80, 85),
    ]


def test_scopes_idle_split_and_accounting():
    r = reduce(synthetic())
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["scope_s"]["prefill"] == pytest.approx(
        {"attn/cache_write": 20e-9, "attn/core": 5e-9, "unscoped": 5e-9})
    assert r["scope_s"]["decode"] == pytest.approx(
        {"mlp": 10e-9, "unscoped": 10e-9})
    # idle: [0,5] [35,70] [90,100]; a step was open over [2,5] [35,40]
    # [60,70] [90,98]
    assert r["idle_s"] == pytest.approx(50e-9)
    assert r["idle_with_work_s"] == pytest.approx((3 + 5 + 10 + 8) * 1e-9)
    assert r["accounted_s"] == pytest.approx(98e-9)   # all but [98,100]
    assert r["serve_steps"] == 2


def test_ops_outside_the_window_are_cut():
    evs = synthetic() + [op("jit__prefill_call(3)", 95, 115,
                            line="XLA Modules"),
                         op("fusion.7", 95, 115, "attn/cache_write")]
    r = reduce(evs)
    assert r["scope_s"]["prefill"]["attn/cache_write"] == pytest.approx(
        25e-9)
    assert r["idle_s"] == pytest.approx(45e-9)


def test_missing_window_or_device_is_an_error():
    with pytest.raises(ValueError):
        reduce([e for e in synthetic() if e.name != "bench.window"])
    with pytest.raises(ValueError):
        reduce([e for e in synthetic() if e.plane == HOST])


def test_scope_names_from_hlo_text():
    assert scope_of("jit(_prefill_call)/while/body/closed_call/attn/"
                    "cache_write/jit(take_along_axis)/gather") == \
        "attn/cache_write"
    assert scope_of("norm/reduce_sum") == "norm"
    assert scope_of("jit(_prefill_call)/while") == ""
    # two programs as their compiled text prints them; the profile names
    # an operation by its instruction with typed operands
    one = "\n".join([
        "HloModule jit__prefill_call",
        "  %fusion.1 = bf16[8]{0:T(128)} fusion(%p), kind=kCustom, "
        'metadata={op_name="jit(f)/while/body/attn/cache_write/gather"}',
        "  ROOT %while.2 = (s32[]{:T(128)}, bf16[1,8]{1,0}) while(%t), "
        'condition=%c, body=%b, metadata={op_name="jit(f)/while"}',
        '  %copy.3 = bf16[8]{0} copy(%x), metadata={op_name="jit(f)/mlp/x"}',
    ])
    two = "\n".join([
        "  %fusion.1 = bf16[16]{0:T(128)} fusion(%p), "
        'metadata={op_name="jit(f)/while/body/mlp/dot_general"}',
        '  %copy.3 = bf16[8]{0} copy(%y), metadata={op_name="jit(g)/head"}',
    ])
    names = op_names([one, two])
    assert names == {
        ("%fusion.1", "bf16[8]{0:T(128)}"):
            "jit(f)/while/body/attn/cache_write/gather",
        ("%fusion.1", "bf16[16]{0:T(128)}"):
            "jit(f)/while/body/mlp/dot_general",
        ("%while.2", "(s32[]{:T(128)}, bf16[1,8]{1,0})"): "jit(f)/while",
    }                                       # copy.3 is ambiguous
    event = ("%fusion.1 = bf16[16]{0:T(128)} fusion(bf16[2,8]{1,0} %p), "
             "kind=kLoop, calls=%fused_computation")
    assert scope_of(names[hlo_head(event)]) == "mlp"


def test_admit_waits_and_setup_compile_from_the_buffer():
    events = [
        {"name": "compile.bucket", "ph": "X", "ts": 1.0, "dur": 2.0},
        {"name": "request.queued", "ph": "b", "ts": 3.5, "id": "-1"},
        {"name": "compile.exact", "ph": "X", "ts": 4.0, "dur": 0.5},
        {"name": "compile.bucket", "ph": "i", "ts": 6.0},
        {"name": "request.queued", "ph": "b", "ts": 10.0, "id": "0"},
        {"name": "request.queued", "ph": "b", "ts": 10.0, "id": "1"},
        {"name": "request.queued", "ph": "e", "ts": 10.25, "id": "0"},
        {"name": "compile.bucket", "ph": "X", "ts": 11.0, "dur": 1.0},
        {"name": "request.queued", "ph": "e", "ts": 12.0, "id": "1"},
    ]
    reqs = [{"rid": 0, "arrival": 0.5, "submit": 0.5},
            {"rid": 1, "arrival": 0.25, "submit": 0.5},
            {"rid": 2, "arrival": 0.75, "submit": None}]
    assert admit_waits(events, reqs) == pytest.approx({0: 0.25, 1: 2.25})
    assert setup_compile_s(events) == pytest.approx(2.5)
