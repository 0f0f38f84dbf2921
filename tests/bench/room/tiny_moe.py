"""Top-k routed experts after grouped-query attention: the program's
``moe`` family (``models/layers.py``, ``moe_init`` and ``moe_apply``),
which a later change brings into the benchmark as this file, a
configuration, a cell and a reader.

Per layer, after the dense decoder's attention block: a float32 router
``(d_model, n_experts)``, a softmax over every expert, the ``top_k``
largest probabilities renormalised to sum to one as the gates; each
expert ``e`` is ``silu(x W_gate[e]) * (x W_in[e]) W_out[e]`` with
``d_expert`` columns, and ``n_shared_experts`` more of that width run
for every token as one MLP. No token is dropped: the reference is the
model, and a program that drops tokens at its capacity departs from it.
"""
import pathlib

import jax
import jax.numpy as jnp

from bench.lib import models
from bench.lib.models import Leaf

dense = models.load(pathlib.Path(__file__).with_name("dense_decoder.py"))
HIGHEST = dense.HIGHEST
BYTES = dense.BYTES

top_leaves = dense.top_leaves
stacks = dense.stacks
embed = dense.embed
logits = dense.logits
head_matmul_params = dense.head_matmul_params
attn_flops_per_pair = dense.attn_flops_per_pair
kv_bytes_per_token = dense.kv_bytes_per_token
embed_bytes_per_token = dense.embed_bytes_per_token


def layer_leaves(a, layer):
    d, e, f = a["d_model"], a["n_experts"], a["d_expert"]
    out = (dense.norm_leaves(a, "ln1") + dense.norm_leaves(a, "ln2")
           + dense.attn_leaves(a) + [
               Leaf(("ffn", "router"), (d, e), "matrix", dtype="f32"),
               Leaf(("ffn", "w_in"), (e, d, f), "matrix", fan_in=1),
               Leaf(("ffn", "w_gate"), (e, d, f), "matrix", fan_in=1),
               Leaf(("ffn", "w_out"), (e, f, d), "matrix", fan_in=1)])
    fs = f * a["n_shared_experts"]
    if fs:
        out += [Leaf(("ffn", "shared", "w_in"), (d, fs), "matrix"),
                Leaf(("ffn", "shared", "w_gate"), (d, fs), "matrix"),
                Leaf(("ffn", "shared", "w_out"), (fs, d), "matrix")]
    return out


def experts(a, w, y):
    """The routed and shared experts' sum over y (B, S, D)."""
    ein = lambda spec, *xs: jnp.einsum(spec, *xs, precision=HIGHEST)
    probs = jax.nn.softmax(dense.mm(y, w["router"]), axis=-1)
    g, ids = jax.lax.top_k(probs, a["top_k"])
    g = g / g.sum(-1, keepdims=True)
    gate = (jax.nn.one_hot(ids, a["n_experts"]) * g[..., None]).sum(-2)
    h = (jax.nn.silu(ein("bsd,edf->bsef", y, w["w_gate"]))
         * ein("bsd,edf->bsef", y, w["w_in"]))
    out = ein("bse,bsed->bsd", gate, ein("bsef,efd->bsed", h, w["w_out"]))
    if "shared" in w:
        sh = w["shared"]
        out = out + dense.mm(jax.nn.silu(dense.mm(y, sh["w_gate"]))
                             * dense.mm(y, sh["w_in"]), sh["w_out"])
    return out


def layer(a, w, x):
    x = x + dense.attention(a, w["attn"], dense.norm(a, w["ln1"], x))
    return x + experts(a, w["ffn"], dense.norm(a, w["ln2"], x))


def rows_per_block(a, s, budget=1.5e9):
    per_row = 4.0 * s * (2 * a["n_heads"] * s
                         + 3 * a["n_experts"] * a["d_expert"])
    return max(1, int(budget // per_row))


def _expert_params(a):
    return 3 * a["d_model"] * a["d_expert"]


def _fixed_params(a):
    """A layer's matmul weights that every token multiplies: attention,
    the router and the shared experts."""
    d = a["d_model"]
    return (dense.layer_matmul_params(dict(a, d_ff=0)) + d * a["n_experts"]
            + a["n_shared_experts"] * _expert_params(a))


def token_matmul_params(a):
    return a["n_layers"] * (_fixed_params(a) + a["top_k"] * _expert_params(a))


def _weight_bytes(a, experts_read):
    d = a["d_model"]
    nrm = d * (2 if a["norm"] == "layernorm" else 1) * 4
    per_layer = ((_fixed_params(a) - d * a["n_experts"]) * BYTES
                 + d * a["n_experts"] * 4 + 2 * nrm
                 + experts_read * _expert_params(a) * BYTES)
    return a["n_layers"] * per_layer + nrm + d * a["vocab"] * BYTES


def prefill_weight_bytes(a):
    """At the least, every token routed to the same ``top_k`` experts."""
    return _weight_bytes(a, a["top_k"])


def decode_weight_bytes(a):
    """At the least, every row routed to the same ``top_k`` experts."""
    return _weight_bytes(a, a["top_k"])
