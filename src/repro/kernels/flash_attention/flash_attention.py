"""Variable-length flash attention — beyond-paper fused kernel (DESIGN §9).

DISC predates FlashAttention; its fusion scope stops at loop/input fusion.
For the serving path the dominant memory-bound pattern *is* attention, so
we extend the paper's "one artifact, any runtime shape" contract to it:

* per-sequence KV lengths arrive via **scalar prefetch** (`lens`);
* K-blocks entirely beyond a sequence's length (or above the causal
  diagonal) are *skipped* with ``pl.when`` — padded buckets cost no MXU
  flops, which is what makes bucket-compiled attention competitive with
  exact-shape compilation (benchmarks/bench_fig4_static_gap.py);
* online-softmax accumulation in f32 scratch across the innermost K-block
  grid dimension (canonical TPU FA schedule: grid (B, H, nQ, nK), scratch
  persists across the sequential nK steps);
* GQA: the K/V BlockSpec index maps query head h -> kv head h//group, so
  grouped heads share one VMEM copy.

Blocks are MXU-aligned (block_q, block_k multiples of 128 on target;
tests use smaller interpret-mode blocks).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import pallas_call

__all__ = ["flash_attention_kernel"]

_NEG_INF = -1e30


def _fa_body(lens_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
             *, scale: float, causal: bool, block_q: int, block_k: int):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = lens_ref[b]
    k_start = ik * block_k
    q_start = iq * block_q

    in_range = k_start < kv_len
    if causal:
        in_range = jnp.logical_and(in_range,
                                   k_start <= q_start + block_q - 1)

    @pl.when(in_range)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)

        k_idx = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_idx < kv_len
        if causal:
            q_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = jnp.logical_and(mask, k_idx <= q_idx)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]                          # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jax.Array,       # (B, H, Sq, D)
    k: jax.Array,       # (B, Hkv, Sk, D)
    v: jax.Array,       # (B, Hkv, Sk, D)
    lens: jax.Array,    # (B,) i32 actual kv lengths
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert h % hkv == 0
    group = h // hkv
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    nq, nk = sq // block_q, sk // block_k

    body = functools.partial(_fa_body, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k)
    return pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b_, h_, iq, ik, s: (b_, h_, iq, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b_, h_, iq, ik, s: (b_, h_ // group, ik, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b_, h_, iq, ik, s: (b_, h_ // group, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, d),
                                   lambda b_, h_, iq, ik, s: (b_, h_, iq, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
    )(jnp.asarray(lens, jnp.int32), q, k, v)
