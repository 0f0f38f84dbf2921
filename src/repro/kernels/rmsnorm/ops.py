"""jit wrapper for fused RMSNorm."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .rmsnorm import rmsnorm_kernel

ROW_VERSIONS = (8, 64, 256)
_VMEM_BUDGET = 4 * 1024 * 1024


def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6) -> jax.Array:
    lead = x.shape[:-1]
    d = x.shape[-1]
    flat = x.reshape(-1, d)
    r = flat.shape[0]
    item = jnp.dtype(x.dtype).itemsize
    fits = [b for b in ROW_VERSIONS
            if r % b == 0 and b * d * item <= _VMEM_BUDGET]
    if fits:
        out = rmsnorm_kernel(flat, w, eps=eps, block_r=max(fits))
    else:
        b = ROW_VERSIONS[0]
        pad = (-r) % b
        out = rmsnorm_kernel(jnp.pad(flat, ((0, pad), (0, 0))), w, eps=eps,
                             block_r=b)[:r]
    return out.reshape(*lead, d)
