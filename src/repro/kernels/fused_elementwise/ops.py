"""jit wrapper + shape-adaptive version selection — DISC §4.3.

    "we generate different versions of kernels, and generate selection
     logic from host-side to launch a proper kernel at runtime for each
     incoming shape."

Versions differ in VMEM block size (launch dimensions / vectorization
granularity).  ``select_version`` is the generated host-side selection
logic: biggest block that divides the padded size, preferring larger
blocks for fewer grid steps while keeping ≥4 grid steps for pipelining
when the array is large.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp

from .fused_elementwise import fused_elementwise_kernel

# block-size versions (elements): multiples of the 8x128 f32 TPU tile
VERSIONS = (1024, 4096, 16384, 65536)


def select_version(total_padded: int) -> int:
    candidates = [b for b in VERSIONS if total_padded % b == 0]
    if not candidates:
        return 0  # no aligned version: caller pads or falls back to XLA
    # prefer the largest block that still leaves ≥4 grid steps (pipelining),
    # else the largest divisor
    pipelined = [b for b in candidates if total_padded // b >= 4]
    return max(pipelined) if pipelined else max(candidates)


def fused_elementwise(expr: Callable, inputs: Sequence[jax.Array], n_valid,
                      out_dtypes: Sequence = None) -> List[jax.Array]:
    """Flatten inputs, pick a kernel version, run the fused cluster."""
    shape = inputs[0].shape
    flat = [jnp.ravel(x) for x in inputs]
    total = flat[0].shape[0]
    if out_dtypes is None:
        out_dtypes = [inputs[0].dtype]
    block = select_version(total)
    if block == 0:
        # unaligned fallback: pad to the smallest version boundary
        b = VERSIONS[0]
        pad = (-total) % b
        flat = [jnp.pad(x, (0, pad)) for x in flat]
        block = select_version(total + pad)
        outs = fused_elementwise_kernel(expr, flat, n_valid, out_dtypes,
                                        block=block)
        return [o[:total].reshape(shape) for o in outs]
    outs = fused_elementwise_kernel(expr, flat, n_valid, out_dtypes,
                                    block=block)
    return [o.reshape(shape) for o in outs]
