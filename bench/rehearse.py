#!/usr/bin/env python3
"""Compile a configuration's serving programs for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --config <name> \
        --max-batch <n> --prefill <B>x<S> [<B>x<S> ...] [--decode]

Builds the engine's prefill and decode steps at the configuration's
shapes (``bench/configs/<name>.json``; ``--max-batch`` overrides its
``serve.max_batch``) and compiles each for one chip of a described
``v5e:2x2`` with no chip attached, printing the bytes of arguments,
outputs and temporaries that the chip's compiler reports. A program that
does not fit one chip's memory raises here, as it would on the chip.
"""
import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.api import ServeConfig, ServeEngine  # noqa: E402
from repro.models.common import ArchConfig  # noqa: E402
from repro.models.registry import get_model  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--max-batch", type=int)
    ap.add_argument("--prefill", nargs="*", default=[])
    ap.add_argument("--decode", action="store_true")
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    conf = json.loads((ROOT / "bench" / "configs" /
                       f"{args.config}.json").read_text())
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    model = get_model(ArchConfig(**conf["arch"]))
    nb = args.max_batch or conf["serve"]["max_batch"]
    max_seq = conf["serve"]["max_seq"]

    def sds(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    params = sds(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = sds(jax.eval_shape(lambda: model.init_cache(nb, max_seq)))
    eng = ServeEngine.__new__(ServeEngine)
    eng.model = model
    eng._prefill_impl = model.prefill
    i32 = jnp.int32

    def report(label, lowered):
        try:
            m = lowered.compile().memory_analysis()
        except jax.errors.JaxRuntimeError as e:
            print(json.dumps({"program": label, "error":
                              str(e).splitlines()[0]}), flush=True)
            return
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(json.dumps({"program": label, "arguments":
                          m.argument_size_in_bytes,
                          "outputs": m.output_size_in_bytes,
                          "temp": m.temp_size_in_bytes,
                          "alias": m.alias_size_in_bytes,
                          "total": total}), flush=True)

    for bs in args.prefill:
        b, s = (int(x) for x in bs.split("x"))
        rows = sds(jax.eval_shape(lambda: model.init_cache(b, max_seq)))
        report(f"prefill {b}x{s}", jax.jit(
            lambda p, r, t, l, o: ServeEngine._prefill_call(eng, p, r, t, l,
                                                            o)).lower(
            params, rows,
            jax.ShapeDtypeStruct((b, s), i32, sharding=one),
            jax.ShapeDtypeStruct((b,), i32, sharding=one),
            jax.ShapeDtypeStruct((b,), i32, sharding=one)))
    if args.decode:
        report(f"decode {nb}", jax.jit(
            lambda p, c, t, l, a: ServeEngine._decode_step(eng, p, c, t, l,
                                                           a)).lower(
            params, cache,
            jax.ShapeDtypeStruct((nb, 1), i32, sharding=one),
            jax.ShapeDtypeStruct((nb,), i32, sharding=one),
            jax.ShapeDtypeStruct((nb,), jnp.bool_, sharding=one)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
