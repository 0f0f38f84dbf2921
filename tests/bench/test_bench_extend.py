"""A later change adds a configuration, a traffic mix and a per-layer
metric as new files and entries only: the harness finds them by name."""
import json

from bench_tiny import TINY_ARCH, make_root
from bench.lib import harness

NEW_METRIC = '''"""Prompt tokens per request admitted in the window."""


def read(run):
    reqs = [r for r in run["requests"] if r["admit"] is not None]
    return sum(r["plen"] for r in reqs) / len(reqs) if reqs else None
'''


def test_new_files_are_picked_up(tmp_path):
    root = make_root(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    arch = dict(TINY_ARCH["dense_gqa"], name="tiny-new", n_layers=1)
    (root / "bench" / "configs" / "tiny_new.json").write_text(json.dumps({
        "arch": arch, "serve": {"max_batch": 2, "max_seq": 64},
        "check": {"requests": 2, "min_tokens": 2, "logit_gap": 0.04}}))
    (root / "bench" / "traffic" / "short_burst.json").write_text(json.dumps({
        "loop": "open", "rate_per_s": 50, "block": 8,
        "prompt_len": {"median": 12, "sigma": 0.3, "min": 8, "max": 16},
        "output_len": {"median": 3, "sigma": 0.3, "min": 2, "max": 4}}))
    (root / "bench" / "metrics" / "prompt_tokens_mean.py").write_text(
        NEW_METRIC)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_new", "source": "test",
                             "file": "bench/configs/tiny_new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_new.short_burst",
                               "config": "tiny_new",
                               "traffic": "short_burst", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "prompt_tokens_mean", "unit": "tokens",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "serving engine",
                               "moves": "setup_s",
                               "workloads": ["tiny_new.short_burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.Cell(root, "tiny_new.short_burst", require_tpu=False)
    cell.build(3)
    run = cell.run(3, 1.0)
    run["setup_s"], run["trace"] = 1.0, None
    checks = cell.check(run, cell.sample(run, 3), 3)
    assert harness.is_correct(checks), checks
    per = harness.read_metrics(
        root, harness.cell_metrics(cell.bench, cell.name, True), run)
    assert set(per) == {"prompt_tokens_mean"}
    assert 8 <= per["prompt_tokens_mean"]["value"] <= 16
    e2e = harness.read_metrics(
        root, harness.cell_metrics(cell.bench, cell.name, False), run)
    assert set(e2e) == {m["name"] for m in bench["end_to_end"]
                        if "workloads" not in m}
    # nothing that was there before changed, but the one index file
    changed = {p for p, b in before.items() if p.read_bytes() != b}
    assert changed == {root / "BENCHMARK.json"}
