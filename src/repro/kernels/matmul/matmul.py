"""Blocked MXU matmul — the static-shape kernel library body (DISC §4.5).

    "we implement an interface to choose the best kernel from a library
     according to different runtime shapes.  The library contains both
     vendor libraries ... and pre-generated kernels that has been
     hand-tuned for each shape."

This file is the *pre-generated kernel*: a classic 3-level blocked GEMM
(grid (M/bm, N/bn, K/bk), f32 VMEM accumulator persisting across the
sequential K dimension, MXU-aligned 128-multiple blocks).  ``ops.py``
holds the library: a version table of hand-picked block shapes plus the
runtime-shape selection interface; the "vendor library" entry is XLA's
native dot (jnp.dot).

:func:`matmul_epilogue_kernel` is the kDot variant (DISC §4.3 epilogue
fusion): the same blocked GEMM, but with an *elementwise epilogue*
closure (bias add / activation / residual, unrolled from the fusion
cluster at trace time) applied to the accumulator tile at the final K
step, writing N output refs.  The actual M/N/K sizes arrive as a
scalar-prefetch operand: the K tail of each accumulation step is masked
to zero (padded-bucket garbage must not enter the contraction) and the
M/N tails are masked on store, so one compiled kernel is exact for every
runtime shape ≤ its bucket.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import pallas_call, widen

__all__ = ["matmul_kernel", "matmul_epilogue_kernel"]


def _body(a_ref, b_ref, o_ref, acc_ref):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_kernel(a: jax.Array, b: jax.Array, *, block_m: int = 128,
                  block_k: int = 128, block_n: int = 128) -> jax.Array:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0
    grid = (m // block_m, n // block_n, k // block_k)
    return pallas_call(
        _body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )(a, b)


def _fused_body(epilogue, n_extra: int, n_out: int, acc_dtype):
    def body(lens_ref, a_ref, b_ref, *rest):
        extra_refs = rest[:n_extra]
        out_refs = rest[n_extra:n_extra + n_out]
        acc_ref = rest[-1]
        # grid coordinates read at body top level: inside a pl.when branch
        # (a traced cond) the interpreter has no grid context for them
        im, jn, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        nk = pl.num_programs(2)

        @pl.when(ik == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        a = a_ref[...].astype(jnp.float32)
        bk = a.shape[1]
        kcol = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1) + ik * bk
        a = jnp.where(kcol < lens_ref[2], a, 0.0)  # masked K tail
        acc_ref[...] += jax.lax.dot_general(
            a, b_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when(ik == nk - 1)
        def _store():
            bm, bn = acc_ref.shape
            row = (jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
                   + im * bm)
            col = (jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
                   + jn * bn)
            mask = (row < lens_ref[0]) & (col < lens_ref[1])  # M/N tails
            # rounded to the dot's dtype, then widened for the epilogue
            ys = epilogue(widen(acc_ref[...].astype(acc_dtype)),
                          *[widen(r[...]) for r in extra_refs])
            if not isinstance(ys, (tuple, list)):
                ys = (ys,)
            for r, y in zip(out_refs, ys):
                r[...] = jnp.where(mask, y, jnp.zeros_like(y)).astype(r.dtype)

    return body


def matmul_epilogue_kernel(a, b, extras, epilogue, valid_mnk, out_dtypes,
                           *, acc_dtype=jnp.float32, block_m: int = 128,
                           block_k: int = 128, block_n: int = 128):
    """Blocked GEMM with a fused elementwise epilogue and masked tails.

    ``extras`` are (M, N) operands the epilogue consumes alongside the
    accumulator (pre-broadcast residual/bias terms); ``valid_mnk`` is the
    i32 triple of actual sizes (scalar-prefetched).  Returns one (M, N)
    array per entry of ``out_dtypes`` — a multi-output epilogue stores
    every cluster live-out from one launch.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0
    assert all(x.shape == (m, n) for x in extras), "extras must be (M, N)"
    grid = (m // block_m, n // block_n, k // block_k)
    mn_spec = pl.BlockSpec((block_m, block_n), lambda i, j, kk, s: (i, j))
    return pallas_call(
        _fused_body(epilogue, len(extras), len(out_dtypes), acc_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k), lambda i, j, kk, s: (i, kk)),
                pl.BlockSpec((block_k, block_n), lambda i, j, kk, s: (kk, j)),
            ] + [mn_spec] * len(extras),
            out_specs=[mn_spec] * len(out_dtypes),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((m, n), dt) for dt in out_dtypes],
    )(jnp.asarray(jnp.stack([jnp.asarray(v, jnp.int32) for v in valid_mnk])),
      a, b, *extras)
