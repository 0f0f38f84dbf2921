"""Tiny cells for the benchmark's CPU tests: a root that holds a
``BENCHMARK.json``, configuration and traffic files of CPU size, and the
real model modules and metric readers."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.lib import models  # noqa: E402

DENSE = models.load(ROOT / "bench" / "models" / "dense_decoder.py")

TINY_ARCH = {
    "dense_gqa": {"name": "tiny-gqa", "family": "dense", "n_layers": 2,
                  "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                  "head_dim": 16, "d_ff": 128, "vocab": 512, "dtype": "bf16",
                  "act": "silu", "norm": "rmsnorm", "rope_theta": 10000.0,
                  "tie_embeddings": False, "remat": "none", "max_seq": 128},
    "dense_mqa": {"name": "tiny-mqa", "family": "dense", "n_layers": 2,
                  "d_model": 64, "n_heads": 4, "n_kv_heads": 1,
                  "head_dim": 16, "d_ff": 256, "vocab": 384, "dtype": "bf16",
                  "act": "gelu", "norm": "layernorm", "rope_theta": 10000.0,
                  "tie_embeddings": True, "remat": "none", "max_seq": 128},
}

TINY_TRAFFIC = {
    "open": {"loop": "open", "rate_per_s": 100,
             "prompt_len": {"median": 40, "sigma": 0.5, "min": 8, "max": 100},
             "output_len": {"median": 6, "sigma": 0.5, "min": 1, "max": 16},
             "block": 16},
    "closed": {"loop": "closed", "clients": 6, "think_s": 0.0,
               "lead_in_steps": 12,
               "prompt_len": {"median": 40, "sigma": 0.5, "min": 8,
                              "max": 100},
               "output_len": {"median": 6, "sigma": 0.5, "min": 2,
                              "max": 16},
               "block": 16},
}

# limits on the widest logit gap at these sizes, set from six seeds on
# the CPU: the bf16 program read up to 0.028 (gqa) and 0.0012 (mqa)
# against the float32 reference; the float8 control read 0.11 to 0.50
# (gqa) and 0.019 to 0.041 (mqa, whose tied head gives logits of about
# 0.16), but 0 on one mqa seed, so the tests use a seed where it reads
TINY_GAP_LIMIT = {"dense_gqa": 0.06, "dense_mqa": 0.007}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """A benchmark root with tiny cells (the open-loop GQA one and the
    closed-loop MQA one named as the cells on the chip, so that they
    report those cells' metrics), the repository's model modules and its
    metric readers. Every finished request is compared, so that a fault
    which alters some rows of a launch cannot slip past the sample."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir(parents=True)
    for d in ("metrics", "models"):
        shutil.copytree(ROOT / "bench" / d, tmp / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cells = {"minitron_4b.code_completion": ("dense_gqa", "open"),
             "tiny_mqa.code_completion": ("dense_mqa", "open"),
             "granite_20b.chat_backlog": ("dense_mqa", "closed")}
    for cfg, arch in TINY_ARCH.items():
        (tmp / "bench" / "configs" / f"{cfg}.json").write_text(json.dumps({
            "model": "dense_decoder",
            "arch": arch, "serve": {"max_batch": 4, "max_seq": 128},
            "check": {"requests": 1000, "min_tokens": 8,
                      "logit_gap": TINY_GAP_LIMIT[cfg]}}))
    for name, spec in TINY_TRAFFIC.items():
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(spec))
    bench["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                           "why": "CPU test"} for n, (c, t) in cells.items()]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def fault_engine_cls():
    """``BenchEngine`` that can break the timed path underneath the
    harness, at the host boundary of each launch (so one compiled engine
    serves every fault):

    * ``"stale_state"``: a decode step returns the cache it was given;
    * ``"half_batch"``: a launch computes only the first half of its
      rows, rounded up: in a prefill the rest get the first row's
      prompt, in a decode step the rest of the active rows get the
      first active row's logits;
    * ``"altered_token"``: a decode step's logits are shifted by one id,
      so every decoded token is altered where it is produced.
    """
    import numpy as np
    import jax.numpy as jnp
    from bench.lib.engine import BenchEngine

    class FaultEngine(BenchEngine):
        fault = None

        def _launch(self, kind, fn, *args):
            if kind == "prefill" and self.fault == "half_batch":
                tokens = np.array(args[2])
                lens = np.array(args[3])
                keep = -(-len(lens) // 2) if len(lens) > 1 else 0
                tokens[keep:] = tokens[0]
                args = args[:2] + (tokens,) + args[3:]
            out = super()._launch(kind, fn, *args)
            if kind == "decode" and self.fault == "half_batch":
                # (params, cache, tokens, lens, active)
                rows = np.flatnonzero(np.asarray(args[4]))
                lost = rows[-(len(rows) // 2):] if len(rows) > 1 else rows[:0]
                out = (out[0].at[lost].set(out[0][rows[0]]), out[1])
            if kind == "decode" and self.fault == "stale_state":
                out = (out[0], args[1])
            if kind == "decode" and self.fault == "altered_token":
                out = (jnp.roll(out[0], 1, axis=-1), out[1])
            return out

    return FaultEngine
