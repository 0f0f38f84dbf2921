"""Seeded random weights, made by the benchmark for both sides.

Every weight is drawn from a key of its own: the run's seed, the
weight's name and, for a layer's weights, the layer's index. The leaves,
and how the layers stack in the program's tree, come from the
configuration's model module (``bench/lib/models.py``). The program gets
the whole tree at once, in the layout its model expects, from one jitted
call on the device (:func:`program_params`); the reference draws one
layer at a time (:func:`layer_weights`, :func:`top_weights`) and gets
the same numbers, so neither side takes anything the other made.

Matrices are normal with standard deviation ``1/sqrt(fan_in)``, the size
of the leaf's fan-in axis; the embedding table 0.02; norm scales
``1 + 0.1 N(0, 1)`` and norm biases ``0.1 N(0, 1)``, so that a scale or
a bias left out shows. Matrices and the embedding are in the served
dtype unless the leaf names its own; norms are float32, as the model
keeps them.
"""
from __future__ import annotations

import functools
import math
import zlib
from types import ModuleType
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .models import Leaf

__all__ = ["seed_key", "program_params", "layer_weights", "top_weights",
           "stack_of", "frozen"]

DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


def frozen(arch: Dict) -> Tuple:
    """A hashable form of an ``arch`` dict (a static jit argument)."""
    return tuple(sorted(arch.items()))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    seed = int(seed)
    k = jax.random.fold_in(jax.random.PRNGKey(0), seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def stack_of(model: ModuleType, arch: Dict, layer: int) -> int:
    """The index, in ``model.stacks(arch)``, of the stack holding
    ``layer``."""
    for i, (_, layers) in enumerate(model.stacks(arch)):
        if layer in layers:
            return i
    raise ValueError(f"layer {layer} is in no stack of the model")


def stack_leaves(model: ModuleType, arch: Dict, i: int):
    """The leaves every layer of stack ``i`` holds."""
    _, layers = model.stacks(arch)[i]
    leaves = model.layer_leaves(arch, layers[0])
    for layer in layers[1:]:
        if model.layer_leaves(arch, layer) != leaves:
            raise ValueError(f"layers {layers[0]} and {layer} share a "
                             f"stack but not their leaves")
    return leaves


def _draw(key, leaf: Leaf, dtype, layer=None) -> jax.Array:
    key = jax.random.fold_in(key, zlib.crc32("/".join(leaf.path).encode()))
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    z = jax.random.normal(key, leaf.shape, jnp.float32)
    if leaf.kind == "scale":
        return 1.0 + 0.1 * z
    if leaf.kind == "bias":
        return 0.1 * z
    std = 0.02 if leaf.kind == "embed" else \
        1.0 / math.sqrt(leaf.shape[leaf.fan_in])
    return (z * std).astype(DTYPES[leaf.dtype] if leaf.dtype else dtype)


def _set(tree: Dict, path: Tuple[str, ...], value) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


@functools.partial(jax.jit, static_argnums=(0, 1))
def _program_params(model: ModuleType, arch_t: Tuple, key) -> Dict:
    a = dict(arch_t)
    dt = DTYPES[a["dtype"]]
    tree: Dict = {}
    for leaf in model.top_leaves(a):
        _set(tree, leaf.path, _draw(key, leaf, dt))
    for i, (prefix, layers) in enumerate(model.stacks(a)):
        idx = jnp.asarray(layers, jnp.int32)
        for leaf in stack_leaves(model, a, i):
            stacked = jax.vmap(lambda l, lf=leaf:
                               _draw(key, lf, dt, layer=l))(idx)
            _set(tree, tuple(prefix) + leaf.path, stacked)
    return tree


def program_params(model: ModuleType, arch: Dict, seed: int) -> Dict:
    """The whole tree in the model's layout, layers stacked as
    ``model.stacks`` says, made on the default device in one jitted
    call."""
    return _program_params(model, frozen(arch), seed_key(seed))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer_weights(model: ModuleType, arch_t: Tuple, stack: int, key,
                   layer) -> Dict:
    a = dict(arch_t)
    dt = DTYPES[a["dtype"]]
    out: Dict = {}
    for leaf in stack_leaves(model, a, stack):
        _set(out, leaf.path, _draw(key, leaf, dt, layer=layer))
    return out


def layer_weights(model: ModuleType, arch: Dict, seed: int, layer: int,
                  key: Optional[jax.Array] = None) -> Dict:
    """Layer ``layer``'s weights, as :func:`program_params` has them."""
    key = seed_key(seed) if key is None else key
    return _layer_weights(model, frozen(arch), stack_of(model, arch, layer),
                          key, jnp.int32(layer))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _top_weights(model: ModuleType, arch_t: Tuple, key) -> Dict:
    a = dict(arch_t)
    dt = DTYPES[a["dtype"]]
    out: Dict = {}
    for leaf in model.top_leaves(a):
        _set(out, leaf.path, _draw(key, leaf, dt))
    return out


def top_weights(model: ModuleType, arch: Dict, seed: int,
                key: Optional[jax.Array] = None) -> Dict:
    key = seed_key(seed) if key is None else key
    return _top_weights(model, frozen(arch), key)
