"""A configuration's model module, and the leaves it describes.

A configuration file names its module by the key ``"model"``: the file
``bench/models/<model>.py`` beside the configuration. The module is all
that knows the shapes of one family of models, as the program lays them
out; everything in ``bench/lib`` reads those shapes through it. A later
family adds a module, never an edit here. It provides:

* its leaves: ``top_leaves(arch)`` and ``layer_leaves(arch, layer)``,
  lists of :class:`Leaf`; and ``stacks(arch)``, how the layers stack in
  the program's tree: ``[(path, layers), ...]``, each stack a subtree at
  ``path`` whose leaves carry a leading axis over ``layers`` (a list of
  layer indices), every layer in one stack with the same leaves;
* its float32 reference (``bench/lib/reference.py`` applies it):
  ``embed(arch, top, tokens, q)``, the rows the tokens look up;
  ``layer(arch, w, x)``, one layer's forward pass over ``x`` (B, S, D)
  at positions ``0..S-1``, every product at ``Precision.HIGHEST``, with
  ``w`` the layer's weights in float32 (rounded where the control asks);
  ``logits(arch, top, h, q)``, the final norm and the head; and
  ``rows_per_block(arch, s)``, the rows of length ``s`` that one block
  of the reference may hold. ``q(x, axis)`` turns a top-level table or a
  slice of it into float32 and, in the control, rounds it to float8
  along ``axis``, its fan-in: the module applies it to what it reads,
  so that a large table is never held rounded whole;
* its work counts (``bench/lib/flops.py`` composes them):
  ``token_matmul_params(arch)``, the matmul weights one token multiplies
  through every layer; ``head_matmul_params(arch)``, those of the head
  for one token; ``attn_flops_per_pair(arch)``, attention FLOPs per
  query-key pair over every layer; ``kv_bytes_per_token(arch)``;
  ``embed_bytes_per_token(arch)``, the table row one token reads; and
  ``prefill_weight_bytes(arch)`` and ``decode_weight_bytes(arch)``,
  the weight bytes a prefill launch and a decode step must read at the
  least (where a family routes tokens: every token to the same
  experts).
"""
from __future__ import annotations

import importlib.util
import pathlib
from types import ModuleType
from typing import Dict, NamedTuple, Optional, Tuple

__all__ = ["Leaf", "load", "of_config", "of_run"]


class Leaf(NamedTuple):
    """One weight: its path in the program's tree, its shape, its kind
    (``matrix``, ``embed``, ``scale`` or ``bias``), the axis it reduces
    over in a product (its fan-in), and its dtype where that is not the
    served one (a router the program keeps in float32: ``"f32"``)."""

    path: Tuple[str, ...]
    shape: Tuple[int, ...]
    kind: str
    fan_in: int = 0
    dtype: Optional[str] = None


# one module object per file, as sys.modules keeps one per import: the
# weights and the reference pass it to jax.jit as a static argument, and
# a second object of the same file would compile every program again
_LOADED: Dict[str, ModuleType] = {}


def load(path) -> ModuleType:
    """The model module in the file at ``path``, loaded once."""
    path = str(pathlib.Path(path).resolve())
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(
            f"bench_model_{pathlib.Path(path).stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def of_config(root: pathlib.Path, config: Dict, name: str) -> pathlib.Path:
    """The file of the module that configuration ``name`` names."""
    if "model" not in config:
        raise KeyError(f"configuration {name!r} names no model: give it "
                       f"a \"model\" key, the stem of a file in "
                       f"bench/models/")
    path = pathlib.Path(root) / "bench" / "models" / f"{config['model']}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"configuration {name!r} names model {config['model']!r}: "
            f"there is no {path}")
    return path


def of_run(run: Dict) -> ModuleType:
    """The model module of the configuration a run record was made with."""
    return load(run["model"])
