"""Shape-adaptive fused elementwise kernel — DISC §4.3 kLoop codegen.

One Pallas kernel executes an entire kLoop fusion cluster (an arbitrary
elementwise expression DAG) over the flattened element domain:

* the *expression program* is a Python closure built from the fusion
  cluster at compile time — it is unrolled into the kernel body during
  tracing, so there is zero runtime interpretation (the paper's
  "compile-time generated" property); a multi-output closure (a cluster
  with several live-outs) stores every result ref from the same launch,
  so multi-consumer clusters never split;
* the actual element count arrives as a **scalar-prefetch operand**; the
  padded tail of the bucket is masked on store, so one compiled kernel is
  exact for every runtime size ≤ bucket;
* VMEM tiling: 1-D blocks of ``block`` elements (multiples of 1024 =
  8 sublanes × 128 lanes, the float32 TPU tile).  ``ops.py`` selects the
  block version per runtime shape — the paper's shape-adaptive fusion
  configuration (launch-dimension selection + vectorized load/store).
"""
from __future__ import annotations

from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import pallas_call, widen

__all__ = ["fused_elementwise_kernel"]


def _kernel_body(expr: Callable, n_in: int, n_out: int):
    def body(len_ref, *refs):
        in_refs = refs[:n_in]
        out_refs = refs[n_in:n_in + n_out]
        i = pl.program_id(0)
        block = out_refs[0].shape[0]
        xs = [widen(r[...]) for r in in_refs]
        ys = expr(*xs)
        if not isinstance(ys, (tuple, list)):
            ys = (ys,)
        n_valid = len_ref[0]
        idx = jax.lax.broadcasted_iota(jnp.int32, (block,), 0) + i * block
        mask = idx < n_valid
        for r, y in zip(out_refs, ys):
            r[...] = jnp.where(mask, y, jnp.zeros_like(y)).astype(r.dtype)

    return body


def fused_elementwise_kernel(
    expr: Callable,
    inputs: Sequence[jax.Array],
    n_valid: jax.Array,
    out_dtypes: Sequence,
    *,
    block: int = 1024,
) -> List[jax.Array]:
    """Run ``expr`` (an unrolled fusion cluster) over flattened inputs.

    All inputs must share one flattened padded length divisible by
    ``block``; ``n_valid`` (i32 scalar) marks the exact element count.
    """
    total = inputs[0].shape[0]
    assert all(x.shape == (total,) for x in inputs), "flatten + equal sizes"
    assert total % block == 0, (total, block)
    n_in, n_out = len(inputs), len(out_dtypes)
    grid = (total // block,)
    spec = pl.BlockSpec((block,), lambda i, s: (i,))
    return pallas_call(
        _kernel_body(expr, n_in, n_out),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[spec] * n_in,
            out_specs=[spec] * n_out,
        ),
        out_shape=[jax.ShapeDtypeStruct((total,), dt) for dt in out_dtypes],
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), *inputs)
