"""Operations and bytes that serving a dense decoder needs.

Counted from the configuration's shapes and the true (unpadded) lengths,
whatever implements them: a launch padded in batch or sequence, or a
decode step that moves the whole cache, does more work than this, and a
change that stops doing it raises the share of this least time.

``arch`` is the ``arch`` dict of a configuration file: ``n_layers``,
``d_model``, ``n_heads``, ``n_kv_heads``, ``d_ff``, ``vocab``, ``act``
(``silu`` is gated: three ``d_model x d_ff`` matrices; ``gelu`` two),
``tie_embeddings`` and optionally ``head_dim``. Weights and cache are
bfloat16 (2 bytes).
"""
from __future__ import annotations

from typing import Dict, Sequence

__all__ = ["head_dim", "layer_matmul_params", "kv_bytes_per_token",
           "weight_bytes", "prefill_flops", "prefill_bytes",
           "decode_flops", "decode_bytes", "least_time"]

BYTES = 2  # bfloat16 weights and cache


def head_dim(arch: Dict) -> int:
    return int(arch.get("head_dim") or arch["d_model"] // arch["n_heads"])


def layer_matmul_params(arch: Dict) -> int:
    """Weights one token multiplies in one layer (norms left out)."""
    d, f = arch["d_model"], arch["d_ff"]
    h, hkv, hd = arch["n_heads"], arch["n_kv_heads"], head_dim(arch)
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    ffn = (3 if arch["act"] == "silu" else 2) * d * f
    return attn + ffn


def kv_bytes_per_token(arch: Dict) -> int:
    return arch["n_layers"] * 2 * arch["n_kv_heads"] * head_dim(arch) * BYTES


def weight_bytes(arch: Dict) -> int:
    """Weights a launch reads once: every layer's matrices and norms, the
    final norm and the output head (the embedding table is read only at
    the rows looked up, counted per token)."""
    d = arch["d_model"]
    norm = d * (2 if arch.get("norm") == "layernorm" else 1) * 4  # float32
    per_layer = layer_matmul_params(arch) * BYTES + 2 * norm
    return arch["n_layers"] * per_layer + norm + d * arch["vocab"] * BYTES


def _attn_flops_prefix(arch: Dict, n: int) -> float:
    """Causal attention of ``n`` tokens over themselves: QK^T and PV,
    ``n (n + 1) / 2`` query-key pairs per head and layer."""
    return (4.0 * arch["n_layers"] * arch["n_heads"] * head_dim(arch)
            * n * (n + 1) / 2)


def prefill_flops(arch: Dict, lens: Sequence[int]) -> float:
    """One prefill launch over prompts of ``lens`` tokens: every layer at
    every prompt token, and the head at each prompt's last token."""
    per_tok = 2.0 * arch["n_layers"] * layer_matmul_params(arch)
    head = 2.0 * arch["d_model"] * arch["vocab"]
    return sum(per_tok * n + _attn_flops_prefix(arch, n) + head
               for n in lens)


def prefill_bytes(arch: Dict, lens: Sequence[int]) -> float:
    """Weights once, the embedding rows looked up, and the K/V written."""
    toks = sum(lens)
    return (weight_bytes(arch) + toks * arch["d_model"] * BYTES
            + toks * kv_bytes_per_token(arch))


def decode_flops(arch: Dict, fills: Sequence[int]) -> float:
    """One decode step over rows whose caches hold ``fills`` positions:
    one token each through every layer and the head, attending over its
    ``fill + 1`` positions."""
    per_tok = (2.0 * arch["n_layers"] * layer_matmul_params(arch)
               + 2.0 * arch["d_model"] * arch["vocab"])
    att = 4.0 * arch["n_layers"] * arch["n_heads"] * head_dim(arch)
    return sum(per_tok + att * (n + 1) for n in fills)


def decode_bytes(arch: Dict, fills: Sequence[int]) -> float:
    """Weights once, each row's K/V up to its true length read, the one
    new position written, and one embedding row per token."""
    kv = kv_bytes_per_token(arch)
    return (weight_bytes(arch)
            + sum(kv * n + kv + arch["d_model"] * BYTES for n in fills))


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> float:
    """The roofline's least time: the larger of the two bounds."""
    return max(flops / peak_flops, nbytes / peak_bw)
