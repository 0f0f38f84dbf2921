"""Mamba-2 SSD (state-space dual) chunked scan kernel.

Per head with state h ∈ R^{N×P} (N = ssm state dim, P = head dim):

    h_t = a_t · h_{t-1} + b_t x_tᵀ        (a_t ∈ (0,1) scalar per head)
    y_t = c_tᵀ h_t

TPU schedule mirrors the SSD paper's chunking: grid (B, H, T/chunk) with
the f32 state in VMEM scratch persisting across sequential chunks.  Inside
a chunk, the intra-chunk part is computed in *parallel* form —
``y_intra = (L ⊙ (C Bᵀ)) X`` with L the causal decay-product mask — and
the inter-chunk part flows through the carried state.  This keeps MXU
matmuls dense (chunk × chunk) instead of a length-T serial loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import pallas_call

__all__ = ["mamba2_kernel"]


def _body(x_ref, a_ref, b_ref, c_ref, o_ref, h_scr, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)   # (chunk, P)
    a = a_ref[0, 0].astype(jnp.float32)   # (chunk, 1) decay in (0,1)
    bmat = b_ref[0, 0].astype(jnp.float32)  # (chunk, N)
    cmat = c_ref[0, 0].astype(jnp.float32)  # (chunk, N)

    # cumulative decay products within the chunk: g_t = prod_{s<=t} a_s
    log_a = jnp.log(jnp.maximum(a, 1e-37))            # (chunk, 1)
    cum = jnp.cumsum(log_a, axis=0)                    # (chunk, 1)
    g = jnp.exp(cum)                                   # (chunk, 1)

    # inter-chunk: y_inter[t] = g_t * (c_t · h_prev)
    h_prev = h_scr[...]                                # (N, P)
    y_inter = g * (cmat @ h_prev)                      # (chunk, P)

    # intra-chunk parallel form: L[t,s] = prod_{s<r<=t} a_r for s<=t
    # L[t,s] = g_t / g_s * a_s^{-1} ... using g shifted: decay from s to t
    # exclusive of a_s (state update applies a_t before adding b_t x_t? --
    # with h_t = a_t h_{t-1} + b_t x_t, contribution of s to t is
    # (prod_{r=s+1..t} a_r) * c_t·b_s * x_s, and s=t term is c_t·b_t x_t.
    ratio = jnp.exp(cum - cum.T)                       # (chunk, chunk): g_t/g_s
    t_idx = jax.lax.broadcasted_iota(jnp.int32, ratio.shape, 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, ratio.shape, 1)
    l_mask = jnp.where(t_idx >= s_idx, ratio, 0.0)     # causal decay mask
    scores = (cmat @ bmat.T) * l_mask                  # (chunk, chunk)
    y_intra = scores @ x                               # (chunk, P)

    o_ref[0, 0] = (y_inter + y_intra).astype(o_ref.dtype)

    # state carry: h_new = (prod a) h_prev + sum_s (prod_{r>s} a_r) b_s x_sT
    decay_to_end = jnp.exp(cum[-1] - cum)              # (chunk, 1)
    h_new = g[-1] * h_prev + (bmat * decay_to_end).T @ x  # (N, P)
    h_scr[...] = h_new


def mamba2_kernel(x, a, b, c, *, chunk: int = 16) -> jax.Array:
    """x: (B,H,T,P); a: (B,H,T,1); b,c: (B,H,T,N).  Returns (B,H,T,P)."""
    bsz, h, t, p = x.shape
    n = b.shape[-1]
    assert t % chunk == 0, (t, chunk)
    grid = (bsz, h, t // chunk)
    spec_x = pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, ic: (b_, h_, ic, 0))
    spec_a = pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, ic: (b_, h_, ic, 0))
    spec_bn = pl.BlockSpec((1, 1, chunk, n), lambda b_, h_, ic: (b_, h_, ic, 0))
    return pallas_call(
        functools.partial(_body, chunk=chunk),
        grid=grid,
        in_specs=[spec_x, spec_a, spec_bn, spec_bn],
        out_specs=spec_x,
        out_shape=jax.ShapeDtypeStruct((bsz, h, t, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
    )(x, a, b, c)
