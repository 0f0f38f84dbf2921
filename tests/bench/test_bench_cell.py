"""Whole runs of the harness on the CPU at tiny sizes: the entry refuses
to run without a chip; past that check, a sound run is correct, and each
fault of the timed path and the float8 control make it incorrect."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_tiny import ROOT, fault_engine_cls, make_root
from bench.lib import harness

CELLS = ["minitron_4b.code_completion", "tiny_mqa.code_completion"]
FAULTS = ["stale_state", "half_batch", "altered_token"]
SEED = 2**31 + 4321


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


def test_run_exits_nonzero_without_a_chip():
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_cpu_env(), capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_cpu_env(), capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.fixture(scope="module", params=CELLS)
def cell(request, tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("bench"))
    c = harness.Cell(root, request.param, require_tpu=False,
                     engine_cls=fault_engine_cls())
    c.build(SEED)
    return c


def _run(cell, fault=None, *, control=False):
    cell.engine.fault = fault
    try:
        run = cell.run(SEED, 1.5)
    finally:
        cell.engine.fault = None
    picked = cell.sample(run, SEED)
    checks = cell.check(run, picked, SEED, control=control)
    return run, checks


def test_sound_run_is_correct(cell):
    run, checks = _run(cell)
    assert harness.is_correct(checks), checks
    assert run["window_compiles"] == 0
    assert checks["served_tokens"]["value"] >= 8
    run["setup_s"], run["trace"] = 1.0, None
    got = harness.read_metrics(
        cell.root, harness.cell_metrics(cell.bench, cell.name, False), run)
    want = {m["name"] for m in cell.bench["end_to_end"]
            if cell.name in m.get("workloads", [cell.name])}
    assert set(got) == want


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_makes_the_run_incorrect(cell, fault):
    _, checks = _run(cell, fault)
    assert not harness.is_correct(checks), checks


def test_control_reads_past_the_limit(cell):
    """The control, put in the program's place, is not correct by the
    harness's own verdict; the program on the same requests is."""
    _, checks = _run(cell, control=True)
    assert max(cell.gaps["control"]) > checks["logit_gap"]["limit"], \
        cell.gaps
    assert harness.is_correct(checks), checks
    assert not harness.is_correct(harness.control_checks(checks, cell.gaps))


def test_cell_metrics_follow_the_workloads_lists():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name in [w["name"] for w in bench["workloads"]]:
        per = harness.cell_metrics(bench, name, True)
        assert per and all(name in m["workloads"] for m in per)
        e2e = {m["name"] for m in harness.cell_metrics(bench, name, False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert all(m["moves"] in e2e for m in per)
