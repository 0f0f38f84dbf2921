"""jit wrapper for masked softmax with row-block version selection."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .softmax import masked_softmax_kernel

ROW_VERSIONS = (8, 64, 256)
_VMEM_BUDGET = 4 * 1024 * 1024


def masked_softmax(x: jax.Array, n_valid):
    """Softmax over the last axis with dynamic valid length (leading dims
    flattened into rows)."""
    lead = x.shape[:-1]
    c = x.shape[-1]
    flat = x.reshape(-1, c)
    r = flat.shape[0]
    item = jnp.dtype(x.dtype).itemsize
    fits = [b for b in ROW_VERSIONS
            if r % b == 0 and b * c * item <= _VMEM_BUDGET]
    if fits:
        out = masked_softmax_kernel(flat, n_valid, block_r=max(fits))
    else:
        b = ROW_VERSIONS[0]
        pad = (-r) % b
        out = masked_softmax_kernel(jnp.pad(flat, ((0, pad), (0, 0))),
                                    n_valid, block_r=b)
        out = out[:r]
    return out.reshape(*lead, c)
