"""jit wrapper for fused LayerNorm."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layernorm import layernorm_kernel

ROW_VERSIONS = (8, 64, 256)
_VMEM_BUDGET = 4 * 1024 * 1024


def layernorm(x: jax.Array, g: jax.Array, b: jax.Array, *,
              eps: float = 1e-5) -> jax.Array:
    lead = x.shape[:-1]
    d = x.shape[-1]
    flat = x.reshape(-1, d)
    r = flat.shape[0]
    item = jnp.dtype(x.dtype).itemsize
    fits = [v for v in ROW_VERSIONS
            if r % v == 0 and v * d * item <= _VMEM_BUDGET]
    if fits:
        out = layernorm_kernel(flat, g, b, eps=eps, block_r=max(fits))
    else:
        v = ROW_VERSIONS[0]
        pad = (-r) % v
        out = layernorm_kernel(jnp.pad(flat, ((0, pad), (0, 0))), g, b,
                               eps=eps, block_r=v)[:r]
    return out.reshape(*lead, d)
