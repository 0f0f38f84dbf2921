"""Seeded random weights, made by the benchmark for both sides.

Every weight is drawn from a key of its own: the run's seed, the
weight's name and, for a layer's weights, the layer's index. The program
gets the whole tree at once, in the layout its model expects, from one
jitted call on the device (:func:`program_params`); the reference draws
one layer at a time (:func:`layer_weights`, :func:`top_weights`) and
gets the same numbers, so neither side takes anything the other made.

Matrices are normal with standard deviation ``1/sqrt(fan_in)``; the
embedding table 0.02; norm scales ``1 + 0.1 N(0, 1)`` and norm biases
``0.1 N(0, 1)``, so that a scale or a bias left out shows. Matrices and
the embedding are in the served dtype; norms are float32, as the model
keeps them.
"""
from __future__ import annotations

import functools
import math
import zlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["seed_key", "layer_leaves", "top_leaves", "program_params",
           "layer_weights", "top_weights", "frozen"]

DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


def frozen(arch: Dict) -> Tuple:
    """A hashable form of an ``arch`` dict (a static jit argument)."""
    return tuple(sorted(arch.items()))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    seed = int(seed)
    k = jax.random.fold_in(jax.random.PRNGKey(0), seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _hd(a: Dict) -> int:
    return int(a.get("head_dim") or a["d_model"] // a["n_heads"])


def _norm_leaves(a: Dict, name: str) -> List[Tuple]:
    d = a["d_model"]
    out = [((name, "scale"), (d,), "scale")]
    if a["norm"] == "layernorm":
        out.append(((name, "bias"), (d,), "bias"))
    return out


def layer_leaves(a: Dict) -> List[Tuple]:
    """``(path, shape, kind)`` of one layer's weights."""
    d, f, h, hkv, hd = (a["d_model"], a["d_ff"], a["n_heads"],
                        a["n_kv_heads"], _hd(a))
    out = _norm_leaves(a, "ln1") + _norm_leaves(a, "ln2") + [
        (("attn", "wq"), (d, h * hd), "matrix"),
        (("attn", "wk"), (d, hkv * hd), "matrix"),
        (("attn", "wv"), (d, hkv * hd), "matrix"),
        (("attn", "wo"), (h * hd, d), "matrix"),
        (("ffn", "w_in"), (d, f), "matrix"),
        (("ffn", "w_out"), (f, d), "matrix"),
    ]
    if a["act"] == "silu":
        out.append((("ffn", "w_gate"), (d, f), "matrix"))
    return out


def top_leaves(a: Dict) -> List[Tuple]:
    out = [(("embed",), (a["vocab"], a["d_model"]), "embed")]
    out += _norm_leaves(a, "ln_f")
    if not a["tie_embeddings"]:
        out.append((("head",), (a["d_model"], a["vocab"]), "matrix"))
    return out


def _draw(key, path: Tuple[str, ...], shape, kind: str, dtype,
          layer=None) -> jax.Array:
    key = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "scale":
        return 1.0 + 0.1 * z
    if kind == "bias":
        return 0.1 * z
    std = 0.02 if kind == "embed" else 1.0 / math.sqrt(shape[0])
    return (z * std).astype(dtype)


def _set(tree: Dict, path: Tuple[str, ...], value) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


@functools.partial(jax.jit, static_argnums=0)
def _program_params(arch_t: Tuple, key) -> Dict:
    a = dict(arch_t)
    dt = DTYPES[a["dtype"]]
    tree: Dict = {}
    for path, shape, kind in top_leaves(a):
        _set(tree, path, _draw(key, path, shape, kind, dt))
    layers = jnp.arange(a["n_layers"])
    for path, shape, kind in layer_leaves(a):
        leaf = jax.vmap(lambda l, p=path, s=shape, k=kind:
                        _draw(key, p, s, k, dt, layer=l))(layers)
        _set(tree, ("blocks",) + path, leaf)
    return tree


def program_params(arch: Dict, seed: int) -> Dict:
    """The whole tree in the model's layout (``embed``, ``blocks`` with
    layer-stacked leaves, ``ln_f``, ``head`` unless tied), made on the
    default device in one jitted call."""
    return _program_params(frozen(arch), seed_key(seed))


@functools.partial(jax.jit, static_argnums=0)
def _layer_weights(arch_t: Tuple, key, layer) -> Dict:
    a = dict(arch_t)
    dt = DTYPES[a["dtype"]]
    out: Dict = {}
    for path, shape, kind in layer_leaves(a):
        _set(out, path, _draw(key, path, shape, kind, dt, layer=layer))
    return out


def layer_weights(arch: Dict, seed: int, layer: int,
                  key: Optional[jax.Array] = None) -> Dict:
    """Layer ``layer``'s weights, as :func:`program_params` has them."""
    key = seed_key(seed) if key is None else key
    return _layer_weights(frozen(arch), key, jnp.int32(layer))


@functools.partial(jax.jit, static_argnums=0)
def _top_weights(arch_t: Tuple, key) -> Dict:
    a = dict(arch_t)
    dt = DTYPES[a["dtype"]]
    out: Dict = {}
    for path, shape, kind in top_leaves(a):
        _set(out, path, _draw(key, path, shape, kind, dt))
    return out


def top_weights(arch: Dict, seed: int,
                key: Optional[jax.Array] = None) -> Dict:
    key = seed_key(seed) if key is None else key
    return _top_weights(frozen(arch), key)
