"""Plain float32 reference of a served model, and its control.

The model's own equations come from its module (``bench/lib/models.py``:
``embed``, ``layer``, ``logits``), written in plain ``jax.numpy``,
independent of the program: no kernels, no cache, no batching buckets.
Every matrix product runs at ``Precision.HIGHEST`` (true float32 on a
TPU). One layer is made and applied at a time, over blocks of rows, so
the reference fits beside nothing else on one chip.

The control is the same computation with every matrix and the embedding
that the model serves in bfloat16 rounded to float8 (e4m3, one scale per
output channel, taken along the leaf's fan-in axis): the precision below
the bfloat16 the configurations serve in. A leaf the program keeps in
float32 (a router) stays float32.
"""
from __future__ import annotations

import functools
from types import ModuleType
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

__all__ = ["compare", "fake_fp8", "forward"]

_F8_MAX = 448.0   # largest finite float8_e4m3fn


def fake_fp8(w: jax.Array, axis: int) -> jax.Array:
    """Round ``w`` to float8 e4m3 with one scale per slice along the
    reduction ``axis``, and return it as float32."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / _F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _q(control: bool):
    """What a model module applies to a top-level table it reads."""
    if control:
        return fake_fp8
    return lambda x, axis: x.astype(jnp.float32)


def _rounded(model: ModuleType, arch: Dict, layer: int) -> Tuple:
    """``(path, fan_in)`` of the layer's leaves the control rounds."""
    leaves = W.stack_leaves(model, arch, W.stack_of(model, arch, layer))
    return tuple((lf.path, lf.fan_in) for lf in leaves
                 if lf.kind == "matrix" and lf.dtype is None)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _apply_layer(model: ModuleType, arch_t: Tuple, rounded: Tuple,
                 control: bool, w: Dict, x):
    a = dict(arch_t)
    axes = dict(rounded)

    def prep(path, leaf):
        leaf = leaf.astype(jnp.float32)
        key = tuple(k.key for k in path)
        if control and key in axes:
            return fake_fp8(leaf, axis=axes[key])
        return leaf

    w = jax.tree_util.tree_map_with_path(prep, w)
    return model.layer(a, w, x)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _embed(model: ModuleType, arch_t: Tuple, control: bool, top, tokens):
    return model.embed(dict(arch_t), top, tokens, _q(control))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _gaps(model: ModuleType, arch_t: Tuple, chunk: int, top: Dict, h_ref,
          h_ctrl, tokens):
    """Per position: the reference's best logit minus its logit of the
    served token, and (with ``h_ctrl``) minus its logit of the control's
    first choice; ``chunk`` positions at a time."""
    a = dict(arch_t)

    def one(xs):
        hr, hc, tok = xs
        ref = model.logits(a, top, hr, _q(False))
        best = ref.max(-1)
        served = best - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
        if hc is None:
            return served, served
        pick = model.logits(a, top, hc, _q(True)).argmax(-1)
        return served, best - jnp.take_along_axis(ref, pick[:, None],
                                                  -1)[:, 0]

    def split(x):
        return None if x is None else x.reshape((-1, chunk) + x.shape[1:])

    served, ctrl = jax.lax.map(one, (split(h_ref), split(h_ctrl),
                                     split(tokens)))
    return served.reshape(-1), ctrl.reshape(-1)


def _hidden(model: ModuleType, arch: Dict, seed: int,
            seqs: Sequence[np.ndarray], positions: Sequence[Sequence[int]],
            modes: Sequence[bool], key, top: Dict,
            pad: int) -> Dict[bool, jax.Array]:
    """Hidden states after the last layer at ``positions[i]`` of each
    sequence, concatenated over sequences and zero-padded to ``pad``
    rows, per mode (False: reference, True: control)."""
    a = dict(arch)
    arch_t = W.frozen(a)
    n = len(seqs)
    s = -(-max(len(t) for t in seqs) // 512) * 512
    rb = min(model.rows_per_block(a, s), n)
    nb = -(-n // rb)
    toks = np.zeros((nb * rb, s), np.int32)
    for i, t in enumerate(seqs):
        toks[i, :len(t)] = t
    xs = {c: [_embed(model, arch_t, c, top,
                     jnp.asarray(toks[j * rb:(j + 1) * rb]))
              for j in range(nb)] for c in modes}
    for layer in range(a["n_layers"]):
        w = W.layer_weights(model, a, seed, layer, key)
        rounded = _rounded(model, a, layer)
        for c in modes:
            xs[c] = [_apply_layer(model, arch_t, rounded, c, w, x)
                     for x in xs[c]]
        del w
    flat = np.concatenate([i * s + np.asarray(p, np.int64)
                           for i, p in enumerate(positions)])
    flat = np.pad(flat, (0, pad - len(flat)))
    return {c: _take_rows(jnp.concatenate(xs[c]), jnp.asarray(flat))
            for c in modes}


@jax.jit
def _take_rows(x, flat):
    return x.reshape(-1, x.shape[-1])[flat]


def compare(model: ModuleType, arch: Dict, seed: int,
            seqs: Sequence[Tuple[np.ndarray, int, np.ndarray]],
            *, control: bool = False,
            chunk: int = 256) -> Dict[str, Optional[List[float]]]:
    """Gaps of served tokens against the reference.

    ``seqs`` holds ``(prompt, plen, served)`` per request: the served
    token ``j`` came from the logits after position ``plen - 1 + j``.
    A gap is the reference's best logit minus its logit of the token
    judged: the token served (``"served"``) and, with ``control``, the
    control's first choice (``"control"``). Returns the widest gap of
    each request under each key."""
    fulls, poss, toks = [], [], []
    for prompt, plen, served in seqs:
        served = np.asarray(served, np.int32)
        fulls.append(np.concatenate([np.asarray(prompt, np.int32)[:plen],
                                     served[:-1]]))
        poss.append(list(range(plen - 1, plen - 1 + len(served))))
        toks.append(served)
    modes = (False, True) if control else (False,)
    key = W.seed_key(seed)
    top = W.top_weights(model, arch, seed, key)
    tok = np.concatenate(toks)
    t = len(tok)
    pad = -(-t // chunk) * chunk
    tok = np.pad(tok, (0, pad - t))
    hid = _hidden(model, arch, seed, fulls, poss, modes, key, top, pad)
    sg, cg = _gaps(model, W.frozen(arch), chunk, top, hid[False],
                   hid[True] if control else None, jnp.asarray(tok))
    bounds = np.cumsum([0] + [len(x) for x in toks])

    def per_request(g):
        g = np.asarray(g)[:t]
        return [float(g[b0:b1].max()) for b0, b1 in zip(bounds, bounds[1:])]

    return {"served": per_request(sg),
            "control": per_request(cg) if control else None}


def forward(model: ModuleType, arch: Dict, seed: int, tokens: np.ndarray, *,
            control: bool = False) -> np.ndarray:
    """Logits at every position of ``tokens`` (B, S): the whole forward
    in one block, for small sizes."""
    a = dict(arch)
    arch_t = W.frozen(a)
    key = W.seed_key(seed)
    top = W.top_weights(model, a, seed, key)
    x = _embed(model, arch_t, control, top, jnp.asarray(tokens))
    for layer in range(a["n_layers"]):
        x = _apply_layer(model, arch_t, _rounded(model, a, layer), control,
                         W.layer_weights(model, a, seed, layer, key), x)
    return np.asarray(jax.jit(lambda t, h: model.logits(a, t, h,
                                                        _q(control)))(top, x))
