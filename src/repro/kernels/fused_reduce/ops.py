"""jit wrapper + row-block version selection for the kInput kernel.

The Pallas kernel itself only knows one layout — rows = kept axes,
columns = reduced axis.  :func:`fused_reduce` normalizes *any single
reduce axis* onto it with a transpose of the producer inputs: the fused
producer expression is elementwise, so it commutes with the permutation,
and the kept axes preserve their relative order (the transposed result
reshapes directly to the reduce's output shape).
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from .fused_reduce import fused_reduce_kernel

ROW_VERSIONS = (8, 64, 256)
_VMEM_BUDGET = 4 * 1024 * 1024  # bytes per operand tile we allow


def select_row_block(r: int, c: int, itemsize: int = 4) -> int:
    fits = [b for b in ROW_VERSIONS
            if r % b == 0 and b * c * itemsize <= _VMEM_BUDGET]
    if not fits:
        return 0
    pipelined = [b for b in fits if r // b >= 2]
    return max(pipelined) if pipelined else max(fits)


def fused_reduce(expr: Callable, inputs: Sequence[jax.Array], n_valid_cols,
                 kind: str = "sum", *, axis: int = -1) -> jax.Array:
    """Reduce ``expr(*inputs)`` over ``axis`` with dynamic valid length.

    ``axis`` may be any single dimension; non-last axes are moved last by
    transposing the inputs (legal because ``expr`` is elementwise).
    Returns the reduced array with the kept axes in their original order.
    """
    rank = inputs[0].ndim
    axis = axis % rank
    if axis != rank - 1:
        perm = [a for a in range(rank) if a != axis] + [axis]
        inputs = [jnp.transpose(x, perm) for x in inputs]
    lead = inputs[0].shape[:-1]
    c = inputs[0].shape[-1]
    flat = [x.reshape(-1, c) for x in inputs]
    r = flat[0].shape[0]
    block_r = select_row_block(r, c, jnp.dtype(flat[0].dtype).itemsize)
    if block_r == 0:
        b = ROW_VERSIONS[0]
        pad = (-r) % b
        flat = [jnp.pad(x, ((0, pad), (0, 0))) for x in flat]
        out = fused_reduce_kernel(expr, flat, n_valid_cols, kind,
                                  block_r=b)
        return out[:r].reshape(lead)
    out = fused_reduce_kernel(expr, flat, n_valid_cols, kind,
                              block_r=block_r)
    return out.reshape(lead)
