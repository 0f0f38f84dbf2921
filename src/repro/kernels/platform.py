"""How every Pallas kernel of the repo runs, decided in one place.

A kernel is interpreted on the CPU, where Pallas has no compiler, and
compiled by Mosaic on every other platform.  The choice is made when the
enclosing program is lowered, from the platform it is lowered for
(``jax.lax.platform_dependent``): only the matching branch is lowered, so a
TPU program never carries the interpreter and a CPU program never asks for
Mosaic.  It follows the program, not the process, so a program compiled
here for a described TPU gets the compiled kernel.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["pallas_call", "widen"]


def widen(x: jax.Array) -> jax.Array:
    """A sub-32-bit float tile as float32, anything else unchanged.

    Kernel bodies compute their fused expressions on widened tiles and
    cast on store: a TPU v5e has no bf16 vector or transcendental unit,
    and Mosaic refuses (or aborts on) bf16 ``rsqrt``/``logistic``.
    """
    if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype.itemsize < 4:
        return x.astype(jnp.float32)
    return x


def pallas_call(kernel: Callable, **kwargs) -> Callable:
    """``pl.pallas_call(kernel, **kwargs)``, interpreted only on the CPU."""
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)
    compiled = pl.pallas_call(kernel, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, cpu=interpreted,
                                          default=compiled)

    return call
