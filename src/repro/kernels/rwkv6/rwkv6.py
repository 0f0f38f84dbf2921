"""RWKV-6 (Finch) WKV recurrence kernel — data-dependent decay scan.

Per head with state S ∈ R^{K×V}:

    y_t = r_t · (S + diag(u) k_t v_tᵀ)
    S  ← diag(w_t) S + k_t v_tᵀ

(w_t data-dependent decay in (0,1), u the "bonus" for the current token.)

TPU schedule: grid (B, H, T/chunk); the f32 state matrix lives in VMEM
scratch and persists across the sequential chunk dimension; within a chunk
a ``fori_loop`` performs the recurrence on VMEM-resident (chunk, K/V)
tiles.  O(1) state in sequence length — this is what makes the rwkv6-3b
``long_500k`` cell tractable (DESIGN §4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import pallas_call

__all__ = ["rwkv6_kernel"]


def _body(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, 0].astype(jnp.float32)  # (chunk, K)
    k = k_ref[0, 0].astype(jnp.float32)  # (chunk, K)
    v = v_ref[0, 0].astype(jnp.float32)  # (chunk, V)
    w = w_ref[0, 0].astype(jnp.float32)  # (chunk, K) decay in (0,1)
    u = u_ref[...].astype(jnp.float32).reshape(-1, 1)  # (K, 1) bonus

    def step(t, carry):
        s, out = carry
        rt = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)      # (1, K)
        kt = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)      # (1, K)
        vt = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)      # (1, V)
        wt = jax.lax.dynamic_slice_in_dim(w, t, 1, 0)      # (1, K)
        kv = kt.T @ vt                                     # (K, V)
        yt = rt @ (s + u * kv)                             # (1, V)
        s = wt.T * s + kv
        out = jax.lax.dynamic_update_slice_in_dim(out, yt, t, 0)
        return s, out

    s0 = s_scr[...]
    out0 = jnp.zeros((chunk, v.shape[1]), jnp.float32)
    s_fin, out = jax.lax.fori_loop(0, chunk, step, (s0, out0))
    s_scr[...] = s_fin
    o_ref[0, 0] = out.astype(o_ref.dtype)


def rwkv6_kernel(r, k, v, w, u, *, chunk: int = 16) -> jax.Array:
    """r,k,w: (B,H,T,K); v: (B,H,T,V); u: (H,K). Returns (B,H,T,V)."""
    b, h, t, dk = r.shape
    dv = v.shape[-1]
    assert t % chunk == 0, (t, chunk)
    grid = (b, h, t // chunk)
    spec_k = pl.BlockSpec((1, 1, chunk, dk), lambda b_, h_, c: (b_, h_, c, 0))
    spec_v = pl.BlockSpec((1, 1, chunk, dv), lambda b_, h_, c: (b_, h_, c, 0))
    spec_u = pl.BlockSpec((1, dk), lambda b_, h_, c: (h_, 0))
    return pallas_call(
        functools.partial(_body, chunk=chunk),
        grid=grid,
        in_specs=[spec_k, spec_k, spec_v, spec_k, spec_u],
        out_specs=spec_v,
        out_shape=jax.ShapeDtypeStruct((b, h, t, dv), r.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
    )(r, k, v, w, u)
