"""The dense decoder: its leaves, its float32 reference and its work.

The program's ``dense`` family (``models/transformer.py``): ``n_layers``
identical layers stacked under ``blocks``, each a pre-norm block of
causal self-attention with grouped query heads and an MLP; a token
embedding, a final norm and a head, the embedding table's transpose
where ``tie_embeddings``. The keys read from ``arch`` are ``n_layers``,
``d_model``, ``n_heads``, ``n_kv_heads``, ``d_ff``, ``vocab``, ``act``,
``norm``, ``rope_theta``, ``tie_embeddings`` and optionally
``head_dim``. Weights and cache are bfloat16 (2 bytes), norms float32.

The reference, written from the architecture's equations in plain
``jax.numpy``, independent of the program. Per layer: pre-norm (RMSNorm,
eps 1e-6, with a scale; or LayerNorm, eps 1e-5, with a scale and a
bias), causal self-attention with rotary positions (the two halves of
each head rotated, base ``rope_theta``), grouped query heads (query head
``h`` reads key/value head ``h // (n_heads / n_kv_heads)``), then the
MLP: ``silu`` is gated, ``silu(x W_gate) * (x W_in) W_out``; ``gelu`` is
``gelu(x W_in) W_out`` with the tanh form of GELU. A final norm and the
head give the logits.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp

from bench.lib.models import Leaf

HIGHEST = jax.lax.Precision.HIGHEST
BYTES = 2  # bfloat16 weights and cache


def head_dim(a: Dict) -> int:
    return int(a.get("head_dim") or a["d_model"] // a["n_heads"])


# --------------------------------------------------------------- leaves --
def norm_leaves(a: Dict, name: str) -> List[Leaf]:
    d = a["d_model"]
    out = [Leaf((name, "scale"), (d,), "scale")]
    if a["norm"] == "layernorm":
        out.append(Leaf((name, "bias"), (d,), "bias"))
    return out


def attn_leaves(a: Dict) -> List[Leaf]:
    d, h, hkv, hd = a["d_model"], a["n_heads"], a["n_kv_heads"], head_dim(a)
    return [Leaf(("attn", "wq"), (d, h * hd), "matrix"),
            Leaf(("attn", "wk"), (d, hkv * hd), "matrix"),
            Leaf(("attn", "wv"), (d, hkv * hd), "matrix"),
            Leaf(("attn", "wo"), (h * hd, d), "matrix")]


def layer_leaves(a: Dict, layer: int) -> List[Leaf]:
    d, f = a["d_model"], a["d_ff"]
    out = norm_leaves(a, "ln1") + norm_leaves(a, "ln2") + attn_leaves(a) + [
        Leaf(("ffn", "w_in"), (d, f), "matrix"),
        Leaf(("ffn", "w_out"), (f, d), "matrix"),
    ]
    if a["act"] == "silu":
        out.append(Leaf(("ffn", "w_gate"), (d, f), "matrix"))
    return out


def top_leaves(a: Dict) -> List[Leaf]:
    out = [Leaf(("embed",), (a["vocab"], a["d_model"]), "embed", fan_in=1)]
    out += norm_leaves(a, "ln_f")
    if not a["tie_embeddings"]:
        out.append(Leaf(("head",), (a["d_model"], a["vocab"]), "matrix"))
    return out


def stacks(a: Dict):
    return [(("blocks",), list(range(a["n_layers"])))]


# ------------------------------------------------------------ reference --
def mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def norm(a: Dict, p: Dict, x):
    if a["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * p["scale"]


def rope(x, pos, theta: float):
    """x (B, S, H, hd), pos (S,)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv          # (S, hd/2)
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def attention(a: Dict, w: Dict, y):
    """Causal self-attention of the normed input y (B, S, D), positions
    0..S-1: the output projection's result."""
    b, s, _ = y.shape
    h, hkv = a["n_heads"], a["n_kv_heads"]
    hd = head_dim(a)
    pos = jnp.arange(s)
    q = rope(mm(y, w["wq"]).reshape(b, s, h, hd), pos, a["rope_theta"])
    k = rope(mm(y, w["wk"]).reshape(b, s, hkv, hd), pos, a["rope_theta"])
    v = mm(y, w["wv"]).reshape(b, s, hkv, hd)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                   precision=HIGHEST).reshape(b, s, h * hd)
    return mm(o, w["wo"])


def layer(a: Dict, w: Dict, x):
    """One decoder layer over x (B, S, D), positions 0..S-1."""
    x = x + attention(a, w["attn"], norm(a, w["ln1"], x))
    y = norm(a, w["ln2"], x)
    f = w["ffn"]
    if a["act"] == "silu":
        u = jax.nn.silu(mm(y, f["w_gate"])) * mm(y, f["w_in"])
    else:
        u = gelu_tanh(mm(y, f["w_in"]))
    return x + mm(u, f["w_out"])


def embed(a: Dict, top: Dict, tokens, q):
    return q(jnp.take(top["embed"], tokens, axis=0), -1)


def logits(a: Dict, top: Dict, h, q):
    ln = jax.tree.map(lambda t: t.astype(jnp.float32), top["ln_f"])
    y = norm(a, ln, h)
    if a["tie_embeddings"]:
        head = q(top["embed"].T, 0)
    else:
        head = q(top["head"], 0)
    return mm(y, head)


def rows_per_block(a: Dict, s: int, budget: float = 1.5e9) -> int:
    per_row = 4.0 * s * (2 * a["n_heads"] * s + 3 * a["d_ff"]
                         + 4 * a["n_heads"] * head_dim(a))
    return max(1, int(budget // per_row))


# ----------------------------------------------------------- work counts --
def layer_matmul_params(a: Dict) -> int:
    """Weights one token multiplies in one layer (norms left out):
    attention ``d*h*hd + 2*d*hkv*hd + h*hd*d``, then 3 (gated) or 2
    matrices of ``d x d_ff``."""
    d, f = a["d_model"], a["d_ff"]
    h, hkv, hd = a["n_heads"], a["n_kv_heads"], head_dim(a)
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    ffn = (3 if a["act"] == "silu" else 2) * d * f
    return attn + ffn


def token_matmul_params(a: Dict) -> int:
    return a["n_layers"] * layer_matmul_params(a)


def head_matmul_params(a: Dict) -> int:
    return a["d_model"] * a["vocab"]


def attn_flops_per_pair(a: Dict) -> int:
    """QK^T and PV: 2 x 2 x head_dim per head and layer."""
    return 4 * a["n_layers"] * a["n_heads"] * head_dim(a)


def kv_bytes_per_token(a: Dict) -> int:
    return a["n_layers"] * 2 * a["n_kv_heads"] * head_dim(a) * BYTES


def embed_bytes_per_token(a: Dict) -> int:
    return a["d_model"] * BYTES


def weight_bytes(a: Dict) -> int:
    """Weights a launch reads once: every layer's matrices and norms, the
    final norm and the output head (the embedding table is read only at
    the rows looked up, counted per token)."""
    d = a["d_model"]
    nrm = d * (2 if a.get("norm") == "layernorm" else 1) * 4  # float32
    per_layer = layer_matmul_params(a) * BYTES + 2 * nrm
    return a["n_layers"] * per_layer + nrm + d * a["vocab"] * BYTES


def prefill_weight_bytes(a: Dict) -> int:
    return weight_bytes(a)


def decode_weight_bytes(a: Dict) -> int:
    return weight_bytes(a)
