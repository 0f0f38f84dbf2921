"""jit wrapper for the RWKV-6 scan with chunk-size version selection."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .rwkv6 import rwkv6_kernel

CHUNK_VERSIONS = (16, 64, 128)


def rwkv6_scan(r, k, v, w, u) -> jax.Array:
    t = r.shape[2]
    fits = [c for c in CHUNK_VERSIONS if t % c == 0]
    if fits:
        return rwkv6_kernel(r, k, v, w, u, chunk=max(fits))
    c = CHUNK_VERSIONS[0]
    pad = (-t) % c
    pads = ((0, 0), (0, 0), (0, pad), (0, 0))
    out = rwkv6_kernel(jnp.pad(r, pads), jnp.pad(k, pads), jnp.pad(v, pads),
                       # pad decay with 1.0 (identity) to keep state stable
                       jnp.pad(w, pads, constant_values=1.0), u,
                       chunk=c)
    return out[:, :, :t]
