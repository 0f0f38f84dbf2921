"""Fused LayerNorm kernel (row-blocked, single VMEM pass, f32 accumulation)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import pallas_call

__all__ = ["layernorm_kernel"]


def _body(x_ref, g_ref, b_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (block_r, D)
    g = g_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps) * g + b
    o_ref[...] = y.astype(o_ref.dtype)


def layernorm_kernel(x: jax.Array, g: jax.Array, b: jax.Array, *,
                     eps: float = 1e-5, block_r: int = 8) -> jax.Array:
    r, d = x.shape
    assert r % block_r == 0, (r, block_r)
    return pallas_call(
        functools.partial(_body, eps=eps),
        grid=(r // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), x.dtype),
    )(x, g.reshape(1, d), b.reshape(1, d))
