"""jit wrappers: prefill (varlen causal FA) and decode (one-token) paths.

Version selection (§4.3 shape-adaptive configuration): block sizes chosen
per runtime sequence length — short sequences use small K blocks so the
skip-guard granularity matches the work, long sequences use MXU-saturating
128×128 blocks.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_kernel

_BLOCK_VERSIONS = ((128, 128), (64, 128), (8, 128))


def _pick_blocks(sq: int, sk: int):
    for bq, bk in _BLOCK_VERSIONS:
        if sq % bq == 0 and sk % bk == 0:
            return bq, bk
    return 0, 0


def flash_attention(q, k, v, lens=None, *, causal=True, scale=None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None) -> jax.Array:
    """q (B,H,Sq,D) × kv (B,Hkv,Sk,D), per-batch valid kv lens."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if lens is None:
        lens = jnp.full((b,), sk, jnp.int32)
    if block_q is None or block_k is None:
        bq, bk = _pick_blocks(sq, sk)
        if bq == 0:  # unaligned: pad q/k to the smallest version
            bq, bk = _BLOCK_VERSIONS[-1]
            pad_q = (-sq) % bq
            pad_k = (-sk) % bk
            qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
            kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
            vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
            out = flash_attention_kernel(qp, kp, vp, lens, causal=causal,
                                         scale=scale, block_q=bq, block_k=bk)
            return out[:, :, :sq]
        block_q, block_k = bq, bk
    return flash_attention_kernel(q, k, v, lens, causal=causal, scale=scale,
                                  block_q=block_q, block_k=block_k)


def flash_decode(q, k_cache, v_cache, lens, *, scale=None) -> jax.Array:
    """Single-token decode: q (B,H,1,D) against cache (B,Hkv,Smax,D).

    Reuses the prefill kernel at block_q=8 (first row valid) — correct for
    any cache fill level via the lens mask + block skipping.  A dedicated
    decode kernel with H-packed rows is a target-hardware optimization
    recorded in EXPERIMENTS.md §Perf.
    """
    b, h, sq, d = q.shape
    assert sq == 1
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, 7), (0, 0)))
    out = flash_attention(qp, k_cache, v_cache, lens, causal=False,
                          scale=scale)
    return out[:, :, :1]
