"""Operations and bytes that serving a model needs.

Counted from the configuration's shapes and the true (unpadded) lengths,
whatever implements them: a launch padded in batch or sequence, or a
decode step that moves the whole cache, does more work than this, and a
change that stops doing it raises the share of this least time.

The counts of one token, one query-key pair and one launch's weights
come from the configuration's model module (``bench/lib/models.py``);
this file composes them into the counts of a launch.
"""
from __future__ import annotations

from types import ModuleType
from typing import Dict, Sequence

__all__ = ["prefill_flops", "prefill_bytes", "decode_flops",
           "decode_bytes", "least_time"]


def prefill_flops(model: ModuleType, arch: Dict,
                  lens: Sequence[int]) -> float:
    """One prefill launch over prompts of ``lens`` tokens: every layer at
    every prompt token, causal attention over ``n (n + 1) / 2``
    query-key pairs of each prompt, and the head at each prompt's last
    token."""
    per_tok = 2.0 * model.token_matmul_params(arch)
    head = 2.0 * model.head_matmul_params(arch)
    pair = model.attn_flops_per_pair(arch)
    return sum(per_tok * n + pair * n * (n + 1) / 2 + head for n in lens)


def prefill_bytes(model: ModuleType, arch: Dict,
                  lens: Sequence[int]) -> float:
    """The weights the launch must read, the embedding rows looked up,
    and the K/V written."""
    toks = sum(lens)
    return (model.prefill_weight_bytes(arch)
            + toks * model.embed_bytes_per_token(arch)
            + toks * model.kv_bytes_per_token(arch))


def decode_flops(model: ModuleType, arch: Dict,
                 fills: Sequence[int]) -> float:
    """One decode step over rows whose caches hold ``fills`` positions:
    one token each through every layer and the head, attending over its
    ``fill + 1`` positions."""
    per_tok = (2.0 * model.token_matmul_params(arch)
               + 2.0 * model.head_matmul_params(arch))
    pair = model.attn_flops_per_pair(arch)
    return sum(per_tok + pair * (n + 1) for n in fills)


def decode_bytes(model: ModuleType, arch: Dict,
                 fills: Sequence[int]) -> float:
    """The weights the step must read, each row's K/V up to its true
    length read, the one new position written, and one embedding row per
    token."""
    kv = model.kv_bytes_per_token(arch)
    emb = model.embed_bytes_per_token(arch)
    return (model.decode_weight_bytes(arch)
            + sum(kv * n + kv + emb for n in fills))


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> float:
    """The roofline's least time: the larger of the two bounds."""
    return max(flops / peak_flops, nbytes / peak_bw)
