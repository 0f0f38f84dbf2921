"""Fused RMSNorm kernel (row-blocked, VMEM-resident single pass).

RMSNorm (Zhang & Sennrich) over the feature axis: y = x/rms(x) * w.
The feature dim is static per model; the *row* count (batch·seq) is the
dynamic-shape axis — garbage rows in padded buckets are computed and
discarded, no cross-row mixing, so no masking is needed in-kernel.
Accumulation in f32 regardless of input dtype (bf16-safe).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import pallas_call

__all__ = ["rmsnorm_kernel"]


def _body(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (block_r, D)
    w = w_ref[...].astype(jnp.float32)  # (1, D)
    ms = jnp.mean(x * x, axis=1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * w
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm_kernel(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
                   block_r: int = 8) -> jax.Array:
    r, d = x.shape
    assert r % block_r == 0, (r, block_r)
    import functools
    return pallas_call(
        functools.partial(_body, eps=eps),
        grid=(r // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), x.dtype),
    )(x, w.reshape(1, d))
