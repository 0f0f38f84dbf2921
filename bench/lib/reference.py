"""Plain float32 reference of the served dense decoders, and its control.

Written from the architecture's equations in plain ``jax.numpy``,
independent of the program: no kernels, no cache, no batching buckets.
Every matrix product runs at ``Precision.HIGHEST`` (true float32 on a
TPU). One layer is made and applied at a time, over blocks of rows, so
the reference fits beside nothing else on one chip.

Per layer: pre-norm (RMSNorm, eps 1e-6, with a scale; or LayerNorm,
eps 1e-5, with a scale and a bias), causal self-attention with rotary
positions (the two halves of each head rotated, base ``rope_theta``),
grouped query heads (query head ``h`` reads key/value head
``h // (n_heads / n_kv_heads)``), then the MLP: ``silu`` is gated,
``silu(x W_gate) * (x W_in) W_out``; ``gelu`` is ``gelu(x W_in) W_out``
with the tanh form of GELU. A final norm and the head (the embedding
table's transpose where tied) give the logits.

The control is the same computation with every matrix and the embedding
rounded to float8 (e4m3, one scale per output channel): the precision
below the bfloat16 the configurations serve in.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

__all__ = ["compare", "fake_fp8", "forward"]

HIGHEST = jax.lax.Precision.HIGHEST
_F8_MAX = 448.0   # largest finite float8_e4m3fn


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def fake_fp8(w: jax.Array, axis: int) -> jax.Array:
    """Round ``w`` to float8 e4m3 with one scale per slice along the
    reduction ``axis``, and return it as float32."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / _F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _norm(a: Dict, p: Dict, x):
    if a["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * p["scale"]


def _rope(x, pos, theta: float):
    """x (B, S, H, hd), pos (S,)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv          # (S, hd/2)
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _layer(a: Dict, w: Dict, x):
    """One decoder layer over x (B, S, D), positions 0..S-1."""
    b, s, _ = x.shape
    h, hkv = a["n_heads"], a["n_kv_heads"]
    hd = int(a.get("head_dim") or a["d_model"] // h)
    pos = jnp.arange(s)
    y = _norm(a, w["ln1"], x)
    q = _rope(_mm(y, w["attn"]["wq"]).reshape(b, s, h, hd), pos,
              a["rope_theta"])
    k = _rope(_mm(y, w["attn"]["wk"]).reshape(b, s, hkv, hd), pos,
              a["rope_theta"])
    v = _mm(y, w["attn"]["wv"]).reshape(b, s, hkv, hd)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                   precision=HIGHEST).reshape(b, s, h * hd)
    x = x + _mm(o, w["attn"]["wo"])
    y = _norm(a, w["ln2"], x)
    f = w["ffn"]
    if a["act"] == "silu":
        u = jax.nn.silu(_mm(y, f["w_gate"])) * _mm(y, f["w_in"])
    else:
        u = _gelu_tanh(_mm(y, f["w_in"]))
    return x + _mm(u, f["w_out"])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _apply_layer(arch_t: Tuple, control: bool, w: Dict, x):
    a = dict(arch_t)

    def prep(path, leaf):
        leaf = leaf.astype(jnp.float32)
        if control and leaf.ndim == 2:
            return fake_fp8(leaf, axis=0)
        return leaf

    w = jax.tree_util.tree_map_with_path(prep, w)
    return _layer(a, w, x)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _embed(arch_t: Tuple, control: bool, table, tokens):
    rows = jnp.take(table, tokens, axis=0).astype(jnp.float32)
    return fake_fp8(rows, axis=-1) if control else rows


def _head(a: Dict, top: Dict, control: bool, h):
    ln = jax.tree.map(lambda t: t.astype(jnp.float32), top["ln_f"])
    y = _norm(a, ln, h)
    if a["tie_embeddings"]:
        head = top["embed"].astype(jnp.float32).T
    else:
        head = top["head"].astype(jnp.float32)
    if control:
        head = fake_fp8(head, axis=0)
    return _mm(y, head)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _gaps(arch_t: Tuple, chunk: int, top: Dict, h_ref, h_ctrl, tokens):
    """Per position: the reference's best logit minus its logit of the
    served token, and (with ``h_ctrl``) minus its logit of the control's
    first choice; ``chunk`` positions at a time."""
    a = dict(arch_t)

    def one(xs):
        hr, hc, tok = xs
        ref = _head(a, top, False, hr)
        best = ref.max(-1)
        served = best - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
        if hc is None:
            return served, served
        pick = _head(a, top, True, hc).argmax(-1)
        return served, best - jnp.take_along_axis(ref, pick[:, None],
                                                  -1)[:, 0]

    def split(x):
        return None if x is None else x.reshape((-1, chunk) + x.shape[1:])

    served, ctrl = jax.lax.map(one, (split(h_ref), split(h_ctrl),
                                     split(tokens)))
    return served.reshape(-1), ctrl.reshape(-1)


def _rows_per_block(a: Dict, s: int, budget: float = 1.5e9) -> int:
    hd = int(a.get("head_dim") or a["d_model"] // a["n_heads"])
    per_row = 4.0 * s * (2 * a["n_heads"] * s + 3 * a["d_ff"]
                         + 4 * a["n_heads"] * hd)
    return max(1, int(budget // per_row))


def _hidden(arch: Dict, seed: int, seqs: Sequence[np.ndarray],
            positions: Sequence[Sequence[int]], modes: Sequence[bool],
            key, top: Dict, pad: int) -> Dict[bool, jax.Array]:
    """Hidden states after the last layer at ``positions[i]`` of each
    sequence, concatenated over sequences and zero-padded to ``pad``
    rows, per mode (False: reference, True: control)."""
    a = dict(arch)
    arch_t = W.frozen(a)
    n = len(seqs)
    s = -(-max(len(t) for t in seqs) // 512) * 512
    rb = min(_rows_per_block(a, s), n)
    nb = -(-n // rb)
    toks = np.zeros((nb * rb, s), np.int32)
    for i, t in enumerate(seqs):
        toks[i, :len(t)] = t
    xs = {c: [_embed(arch_t, c, top["embed"],
                     jnp.asarray(toks[j * rb:(j + 1) * rb]))
              for j in range(nb)] for c in modes}
    for layer in range(a["n_layers"]):
        w = W.layer_weights(a, seed, layer, key)
        for c in modes:
            xs[c] = [_apply_layer(arch_t, c, w, x) for x in xs[c]]
        del w
    flat = np.concatenate([i * s + np.asarray(p, np.int64)
                           for i, p in enumerate(positions)])
    flat = np.pad(flat, (0, pad - len(flat)))
    return {c: _take_rows(jnp.concatenate(xs[c]), jnp.asarray(flat))
            for c in modes}


@jax.jit
def _take_rows(x, flat):
    return x.reshape(-1, x.shape[-1])[flat]


def compare(arch: Dict, seed: int, seqs: Sequence[Tuple[np.ndarray, int,
                                                        np.ndarray]],
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
    top = W.top_weights(arch, seed, key)
    tok = np.concatenate(toks)
    t = len(tok)
    pad = -(-t // chunk) * chunk
    tok = np.pad(tok, (0, pad - t))
    hid = _hidden(arch, seed, fulls, poss, modes, key, top, pad)
    sg, cg = _gaps(W.frozen(arch), chunk, top, hid[False],
                   hid[True] if control else None, jnp.asarray(tok))
    bounds = np.cumsum([0] + [len(x) for x in toks])

    def per_request(g):
        g = np.asarray(g)[:t]
        return [float(g[b0:b1].max()) for b0, b1 in zip(bounds, bounds[1:])]

    return {"served": per_request(sg),
            "control": per_request(cg) if control else None}


def forward(arch: Dict, seed: int, tokens: np.ndarray, *,
            control: bool = False) -> np.ndarray:
    """Logits at every position of ``tokens`` (B, S): the whole forward
    in one block, for small sizes."""
    a = dict(arch)
    arch_t = W.frozen(a)
    key = W.seed_key(seed)
    top = W.top_weights(a, seed, key)
    x = _embed(arch_t, control, top["embed"], jnp.asarray(tokens))
    for layer in range(a["n_layers"]):
        x = _apply_layer(arch_t, control, W.layer_weights(a, seed, layer,
                                                          key), x)
    return np.asarray(jax.jit(lambda t, h: _head(a, t, control, h))(top, x))
