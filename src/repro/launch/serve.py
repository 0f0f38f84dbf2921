"""Serving launcher: DISC-bucketed continuous batching.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama_11b \
        --requests 16 --reduced
    PYTHONPATH=src python -m repro.launch.serve --arch deepseek_v2_236b \
        --dry-run        # full config decode_32k: lower+compile only
"""
import argparse
import dataclasses
import sys

import jax

from ..api import ServeConfig, ServeEngine
from ..api.persistent_cache import enable_persistent_cache
from ..configs import ARCH_IDS, get_config
from ..obs.clock import CLOCK as _clock
from ..data.pipeline import VarLenRequestStream
from ..models.registry import get_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--dry-run", action="store_true")
    args = ap.parse_args()

    if args.dry_run:
        from .dryrun import lower_cell
        lower_cell(args.arch, "decode_32k", multi_pod=False)
        return

    enable_persistent_cache()
    dev = jax.devices()[0]
    print(f"serving on {dev.platform} ({dev.device_kind}) "
          f"x{len(jax.devices())}")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(cfg.reduced(), max_seq=args.max_seq)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params,
                         ServeConfig(max_batch=args.max_batch,
                                     max_seq=args.max_seq))
    stream = VarLenRequestStream(vocab=cfg.vocab, min_len=4,
                                 max_len=args.max_seq // 2, seed=0)
    reqs = stream.sample(args.requests)
    t0 = _clock()
    engine.submit(reqs)
    done = engine.run_until_done()
    dt = _clock() - t0
    print(f"{len(done)}/{args.requests} requests in {dt:.1f}s; "
          f"{engine.stats['tokens_generated']} tokens; "
          f"prefill compiles {engine.stats['prefill_compiles']}")
    if engine.failed or engine.rejected or len(done) != args.requests:
        print(f"failed: {engine.failed}; rejected: {engine.rejected}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
