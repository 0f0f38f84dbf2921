"""Masked row-softmax kernel — the canonical memory-bound fusion pattern.

XLA emits softmax as reduce→broadcast→elementwise→reduce→broadcast→div
(5+ HBM round-trips when unfused); this kernel does one VMEM-resident pass
per row block.  The valid row length arrives via scalar prefetch so a
single bucket-compiled artifact serves every sequence length ≤ bucket —
padded columns get probability exactly 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import pallas_call

__all__ = ["masked_softmax_kernel"]


def _body(len_ref, x_ref, o_ref):
    x = x_ref[...]  # (block_r, C)
    c = x.shape[1]
    n = len_ref[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    valid = col < n
    neg = jnp.asarray(-jnp.inf, x.dtype)
    xm = jnp.where(valid, x, neg)
    m = jnp.max(xm, axis=1, keepdims=True)
    # rows fully out of range: keep m finite to avoid nan from (-inf - -inf)
    m = jnp.where(jnp.isfinite(m), m, jnp.zeros_like(m))
    e = jnp.exp(xm - m)
    e = jnp.where(valid, e, jnp.zeros_like(e))
    s = jnp.sum(e, axis=1, keepdims=True)
    s = jnp.where(s == 0, jnp.ones_like(s), s)
    o_ref[...] = e / s


def masked_softmax_kernel(x: jax.Array, n_valid, *,
                          block_r: int = 8) -> jax.Array:
    """Softmax over axis 1 of (R, C) with valid length ``n_valid``."""
    r, c = x.shape
    assert r % block_r == 0, (r, block_r)
    spec = pl.BlockSpec((block_r, c), lambda i, s: (i, 0))
    return pallas_call(
        _body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r // block_r,),
            in_specs=[spec],
            out_specs=spec,
        ),
        out_shape=jax.ShapeDtypeStruct((r, c), x.dtype),
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), x)
