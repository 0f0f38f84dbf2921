"""Static-shape kernel library interface — DISC §4.5.

    "we implement an interface to choose the best kernel from a library
     according to different runtime shapes.  The library contains both
     vendor libraries such as cuBLAS/cuDNN, and pre-generated kernels that
     has been hand-tuned for each shape."

The library itself lives with the kernels (`kernels/matmul`): a version
table of hand-tuned block shapes plus the vendor entry (XLA's native dot,
our cuBLAS analogue).  This module is the compiler-side interface: the
codegen layer asks :func:`pick` for a compute-intensive op's backend at
dispatch time, keyed on the *runtime* shape — the §4.5 balance between
dynamism (any shape works) and performance (tuned kernels where shapes
align).
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

__all__ = ["pick", "LibraryChoice"]


class LibraryChoice:
    def __init__(self, name: str, fn: Callable):
        self.name = name
        self.fn = fn

    def __call__(self, *args, **kwargs):
        return self.fn(*args, **kwargs)

    def __repr__(self) -> str:  # pragma: no cover
        return f"<LibraryChoice {self.name}>"


def pick(m: int, k: int, n: int) -> LibraryChoice:
    """Choose the GEMM implementation for a runtime (m, k, n)."""
    from ..kernels.matmul.ops import matmul, select_gemm_version

    version = select_gemm_version(m, k, n)
    if version is None:
        import jax.numpy as jnp
        return LibraryChoice("vendor:xla_dot", jnp.dot)
    return LibraryChoice(
        f"library:{version}",
        lambda a, b: matmul(a, b, version=version))
