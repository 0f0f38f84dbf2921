"""A model of another family comes in as new files only: under a
temporary root, the program's ``moe`` family at tiny size gets its own
model module (``tests/bench/room/tiny_moe.py``), a configuration, a cell
and a reader (``tests/bench/room/decode_weight_mb_per_token.py``), and
only ``BENCHMARK.json`` changes. The program's ``moe`` family scans one
stack of like layers, so the tiny model has no leading dense layer
(``test_bench_models.py`` stacks one apart in the weights alone).

The configuration is float32, so that the program and the reference
route every token alike: in bfloat16 a token whose second and third
experts score within rounding of each other goes either way, which
swings the widest gap of a sound run from 0.03 to 0.70 on the CPU. At
float32 sound runs read 0 on four seeds; with ``capacity_factor`` 0.25
a prefill drops tokens and the same seeds read 3.4 to 3.9.
"""
import json
import shutil

import pytest

from bench_tiny import ROOT, make_root
from bench.lib import flops, harness, models

SEED = 2**31 + 4409
ROOM = ROOT / "tests" / "bench" / "room"
MOE = {"name": "tiny-moe", "family": "moe", "n_layers": 2, "d_model": 64,
       "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
       "vocab": 512, "n_experts": 4, "top_k": 2, "n_shared_experts": 1,
       "d_expert": 32, "dtype": "f32", "act": "silu", "norm": "rmsnorm",
       "rope_theta": 10000.0, "tie_embeddings": False, "remat": "none",
       "max_seq": 128}
# capacity n_experts / top_k holds every token a launch routes; 0.25
# holds an eighth of them in a long prefill
CAPACITY = {"tiny_moe": 2.0, "tiny_moe_dropping": 0.25}
READER = "decode_weight_mb_per_token"
PEAKS = {"flops_bf16": 1e12, "hbm_bw": 1e11, "hbm_bytes": 16e9}


@pytest.fixture(scope="module")
def room(tmp_path_factory):
    """The root before and after the new family's files are added."""
    root = make_root(tmp_path_factory.mktemp("room"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    shutil.copy(ROOM / "tiny_moe.py", root / "bench" / "models")
    shutil.copy(ROOM / f"{READER}.py", root / "bench" / "metrics")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name, cf in CAPACITY.items():
        (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps({
            "model": "tiny_moe", "arch": dict(MOE, capacity_factor=cf),
            "serve": {"max_batch": 4, "max_seq": 128},
            "check": {"requests": 1000, "min_tokens": 8,
                      "logit_gap": 0.05}}))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": f"{name}.code_completion",
                                   "config": name,
                                   "traffic": "open", "chips": 1,
                                   "why": "test"})
    cells = [f"{n}.code_completion" for n in CAPACITY]
    for m in bench["end_to_end"]:
        if m["name"] == "output_tok_s":
            m["workloads"] += cells
    bench["per_layer"].append({"name": READER, "unit": "MB",
                               "better": "lower",
                               "source": "program_counter",
                               "layer": "model step",
                               "moves": "output_tok_s",
                               "workloads": cells})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before


def _run(root, name):
    cell = harness.Cell(root, name, require_tpu=False)
    cell.build(SEED)
    run = cell.run(SEED, 1.5)
    checks = cell.check(run, cell.sample(run, SEED), SEED)
    run["setup_s"] = 1.0
    return cell, run, checks


def test_only_the_index_file_changed(room):
    root, before = room
    changed = {p for p, b in before.items() if p.read_bytes() != b}
    assert changed == {root / "BENCHMARK.json"}


def test_new_family_runs_correct_and_reads(room):
    root, _ = room
    cell, run, checks = _run(root, "tiny_moe.code_completion")
    assert harness.is_correct(checks), checks
    assert run["window_compiles"] == 0
    mod = models.of_run(run)
    assert mod is cell.module and mod.__name__ == "bench_model_tiny_moe"
    run["trace"] = None
    per = harness.read_metrics(
        root, harness.cell_metrics(cell.bench, cell.name, True), run)
    assert set(per) == {READER}
    e2e = harness.read_metrics(
        root, harness.cell_metrics(cell.bench, cell.name, False), run)
    assert set(e2e) == {"output_tok_s", "setup_s"}
    # the rooflines read the new module's counts: given a trace and
    # peaks, each reads its least time over the program's time
    run["peaks"] = PEAKS
    run["trace"] = {"window_s": 1.5, "busy_s": 1.0,
                    "program_s": {"prefill": 0.5, "decode": 0.4,
                                  "other": 0.0}}
    roof = [m for m in cell.bench["per_layer"]
            if m["name"] in ("prefill_roofline", "decode_roofline")]
    got = harness.read_metrics(root, roof, run)
    a = run["arch"]

    def least(f, b):
        return flops.least_time(f, b, PEAKS["flops_bf16"], PEAKS["hbm_bw"])

    ps = [p for p in run["prefills"] if p[1] <= run["closed_at"]]
    ds = [d for d in run["decodes"] if d[1] <= run["closed_at"]]
    assert ps and ds
    want_p = sum(least(flops.prefill_flops(mod, a, p[2]),
                       flops.prefill_bytes(mod, a, p[2])) for p in ps)
    want_d = sum(least(flops.decode_flops(mod, a, d[2]),
                       flops.decode_bytes(mod, a, d[2])) for d in ds)
    assert got["prefill_roofline"]["value"] == pytest.approx(
        100 * want_p / 0.5)
    assert got["decode_roofline"]["value"] == pytest.approx(
        100 * want_d / 0.4)
    # routed experts: a token multiplies top_k of them, not all
    assert mod.token_matmul_params(a) < mod.token_matmul_params(
        dict(a, top_k=a["n_experts"]))


def test_dropped_tokens_make_the_run_incorrect(room):
    root, _ = room
    _, _, checks = _run(root, "tiny_moe_dropping.code_completion")
    assert checks["logit_gap"]["value"] > 10 * checks["logit_gap"]["limit"]
    assert not harness.is_correct(checks), checks
