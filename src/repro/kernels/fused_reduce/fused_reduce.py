"""kInput fusion kernel: elementwise producers + reduce root — DISC §4.3.

    "input fusion with reduce operation as the root"

A row-blocked Pallas kernel: each grid step loads a (block_r, C) tile into
VMEM, applies the fused producer expression (unrolled at trace time),
masks the dynamic tail of the reduced axis with the reduce identity using
the **scalar-prefetched actual length**, and reduces.  One artifact serves
every column count ≤ the bucket.

Layout: rows = kept axis (any fused batch dims flattened by ops.py),
columns = reduced axis.  block_r versions are the shape-adaptive launch
configurations.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import pallas_call, widen

__all__ = ["fused_reduce_kernel", "REDUCE_IDENTITY"]

REDUCE_IDENTITY = {"sum": 0.0, "max": -jnp.inf, "min": jnp.inf, "prod": 1.0}
_REDUCERS = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min, "prod": jnp.prod}


def _kernel_body(expr: Callable, kind: str, n_in: int):
    identity = REDUCE_IDENTITY[kind]
    reducer = _REDUCERS[kind]

    def body(len_ref, *refs):
        in_refs = refs[:n_in]
        out_ref = refs[n_in]
        xs = [widen(r[...]) for r in in_refs]  # (block_r, C)
        y = expr(*xs)
        c = y.shape[1]
        n_valid = len_ref[0]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
        y = jnp.where(col < n_valid, y, jnp.asarray(identity, y.dtype))
        out_ref[...] = reducer(y, axis=1, keepdims=True).astype(
            out_ref.dtype)

    return body


def fused_reduce_kernel(expr: Callable, inputs, n_valid_cols, kind: str,
                        *, block_r: int = 8):
    """Reduce ``expr(*inputs)`` over axis 1 with masked dynamic length.

    inputs: (R, C) arrays, R % block_r == 0.  Returns (R,).
    """
    r, c = inputs[0].shape
    assert r % block_r == 0, (r, block_r)
    spec = pl.BlockSpec((block_r, c), lambda i, s: (i, 0))
    out = pallas_call(
        _kernel_body(expr, kind, len(inputs)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r // block_r,),
            in_specs=[spec] * len(inputs),
            out_specs=pl.BlockSpec((block_r, 1), lambda i, s: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((r, 1), inputs[0].dtype),
    )(jnp.asarray(n_valid_cols, jnp.int32).reshape(1), *inputs)
    return out[:, 0]
