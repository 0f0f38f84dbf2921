"""jit wrapper for the Mamba-2 SSD scan with chunk version selection."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .mamba2 import mamba2_kernel

CHUNK_VERSIONS = (16, 64, 128)


def mamba2_scan(x, a, b, c) -> jax.Array:
    t = x.shape[2]
    fits = [ck for ck in CHUNK_VERSIONS if t % ck == 0]
    if fits:
        return mamba2_kernel(x, a, b, c, chunk=max(fits))
    ck = CHUNK_VERSIONS[0]
    pad = (-t) % ck
    pads = ((0, 0), (0, 0), (0, pad), (0, 0))
    out = mamba2_kernel(
        jnp.pad(x, pads),
        jnp.pad(a, pads, constant_values=1.0),  # identity decay in padding
        jnp.pad(b, pads), jnp.pad(c, pads),
        chunk=ck)
    return out[:, :, :t]
