"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape x mesh) cell on the production mesh and dump
memory/cost/roofline analysis.

The forced host device count below is added to the caller's
``XLA_FLAGS`` before jax is imported — jax locks the device count on
first init.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama_11b \
        --cell train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Results land in reports/dryrun/<mesh>/<arch>__<cell>.json plus stdout.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=512").strip()

import argparse
import json
import pathlib
import traceback
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, get_config
from ..obs.clock import CLOCK as _clock
from ..dist.context import use_mesh
from ..dist.spmd import fit_spec as _fit_spec
from ..models.registry import get_model
from ..roofline.analysis import analyze_compiled
from ..train.step import TrainConfig, make_train_step, train_state_init
from .mesh import make_production_mesh
from .shapes import SHAPE_CELLS, cells_for_arch, input_specs

REPORT_DIR = pathlib.Path(__file__).resolve().parents[3] / "reports" / "dryrun"
# the host devices of the production mesh stand in for a TPU v5e pod:
# roofline terms use that chip's peaks
TARGET_DEVICE_KIND = "TPU v5 lite"


# spec fitting (drop axes that don't divide the dim) lives in
# repro.dist.spmd.fit_spec now — shared with the SPMD planner
def _shardings(tree_specs, tree_sds, mesh):
    return jax.tree.map(
        lambda s, v: NamedSharding(mesh, _fit_spec(v.shape, s, mesh)),
        tree_specs, tree_sds,
        is_leaf=lambda s: isinstance(s, P))


def _batch_shardings(batch_sds, mesh):
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    out = {}
    for k, v in batch_sds.items():
        spec = P(dp) if k == "lens" else P(*((dp,) + (None,) * (len(v.shape) - 1)))
        out[k] = NamedSharding(mesh, _fit_spec(v.shape, spec, mesh))
    return out


def _model_flops(cfg, cell) -> float:
    """MODEL_FLOPS = 6·N_active·D (train: fwd+bwd; inference: 2·N·D per tok)."""
    n_act = cfg.n_active_params()
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n_act * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n_act * tokens
    return 2.0 * n_act * cell.global_batch  # decode: 1 token per row


def lower_cell(arch_id: str, cell_name: str, *, multi_pod: bool,
               verbose: bool = True, microbatches: int = 1):
    cfg = get_config(arch_id)
    # §Perf H2 iter3: ZeRO-3 (fsdp profile) is a TRAINING layout — serving
    # it would all-gather every weight per token.  Inference cells run TP.
    if SHAPE_CELLS[cell_name].kind != "train" and \
            cfg.sharding_profile == "fsdp":
        import dataclasses
        cfg = dataclasses.replace(cfg, sharding_profile="tp")
    model = get_model(cfg)
    cell = SHAPE_CELLS[cell_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = _clock()

    with use_mesh(mesh):
        batch_sds = input_specs(cfg, cell)
        batch_sh = _batch_shardings(batch_sds, mesh)

        if cell.kind == "train":
            tcfg = TrainConfig(microbatches=microbatches)
            train_step = make_train_step(model, tcfg)
            state_sds = jax.eval_shape(
                lambda: train_state_init(model, jax.random.PRNGKey(0), tcfg))
            pspecs = model.specs()
            psh = _shardings(pspecs, state_sds.params, mesh)
            rep = NamedSharding(mesh, P())
            state_sh = type(state_sds)(
                params=psh,
                opt=type(state_sds.opt)(step=rep, mu=psh, nu=psh),
                residual=(),
            )
            jfn = jax.jit(train_step, in_shardings=(state_sh, batch_sh),
                          donate_argnums=(0,))
            lowered = jfn.lower(state_sds, batch_sds)
        elif cell.kind == "prefill":
            def prefill(params, batch):
                return model.forward(params, batch)

            params_sds = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0)))
            psh = _shardings(model.specs(), params_sds, mesh)
            jfn = jax.jit(prefill, in_shardings=(psh, batch_sh))
            lowered = jfn.lower(params_sds, batch_sds)
        else:  # decode
            max_len = cell.seq_len
            b = cell.global_batch

            def serve_step(params, cache, batch):
                kw = {}
                if "enc_out" in batch:
                    kw["enc_out"] = batch["enc_out"]
                return model.decode_step(params, cache, batch["tokens"],
                                         batch["lens"], **kw)

            params_sds = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0)))
            cache_sds = jax.eval_shape(lambda: model.init_cache(b, max_len))
            psh = _shardings(model.specs(), params_sds, mesh)
            csh = _shardings(model.cache_specs(), cache_sds, mesh)
            jfn = jax.jit(serve_step, in_shardings=(psh, csh, batch_sh),
                          donate_argnums=(1,))
            lowered = jfn.lower(params_sds, cache_sds, batch_sds)

        t_lower = _clock() - t0
        compiled = lowered.compile()
        t_compile = _clock() - t0 - t_lower

    mem = compiled.memory_analysis()
    terms = analyze_compiled(compiled, arch=arch_id, cell=cell_name,
                             mesh_name=mesh_name, chips=chips,
                             model_flops=_model_flops(cfg, cell),
                             device_kind=TARGET_DEVICE_KIND)
    result = terms.as_dict()
    result.update({
        "lower_seconds": round(t_lower, 2),
        "compile_seconds": round(t_compile, 2),
        "memory_analysis": {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
        "status": "ok",
    })
    if verbose:
        print(f"[dryrun] {arch_id} x {cell_name} on {mesh_name}: "
              f"compile={t_compile:.1f}s flops={terms.hlo_flops:.3e} "
              f"bytes={terms.hlo_bytes:.3e} coll={terms.coll_bytes:.3e} "
              f"dominant={terms.dominant} "
              f"roofline_frac={terms.roofline_fraction:.3f}")
        print(f"  memory_analysis: {result['memory_analysis']}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--cell", choices=list(SHAPE_CELLS))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    if args.all:
        cells = [(a, c) for a in ARCH_IDS for c in cells_for_arch(a)]
    else:
        assert args.arch and args.cell, "--arch/--cell or --all"
        cells = [(args.arch, args.cell)]

    failures = []
    for multi_pod in meshes:
        mesh_name = "2x16x16" if multi_pod else "16x16"
        outdir = REPORT_DIR / mesh_name
        outdir.mkdir(parents=True, exist_ok=True)
        for arch_id, cell_name in cells:
            out_path = outdir / f"{arch_id}__{cell_name}.json"
            try:
                result = lower_cell(arch_id, cell_name, multi_pod=multi_pod,
                                    microbatches=args.microbatches)
            except Exception as e:  # a failure here is a bug in the system
                traceback.print_exc()
                result = {"arch": arch_id, "cell": cell_name,
                          "mesh": mesh_name, "status": "FAIL",
                          "error": repr(e)}
                failures.append((mesh_name, arch_id, cell_name, repr(e)))
            out_path.write_text(json.dumps(result, indent=2))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nAll dry-run cells compiled successfully.")


if __name__ == "__main__":
    main()
