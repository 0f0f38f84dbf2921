"""The static-shape kernel library + runtime selection — DISC §4.5.

``GEMM_LIBRARY`` maps a named version to block shapes "hand-tuned" for a
shape regime; :func:`select_gemm_version` is the runtime-shape selection
interface.  Unaligned/small shapes route to the vendor entry (XLA dot) —
exactly the paper's vendor-library/pre-generated-kernel mix.

:func:`matmul_fused` is the kDot entry used by the Pallas backend's
cluster codegen: it pads operands to the selected block grid, runs
:func:`~repro.kernels.matmul.matmul.matmul_epilogue_kernel` (fused
elementwise epilogue, masked M/N/K tails from the runtime lens), and
slices the block padding back off.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .matmul import matmul_epilogue_kernel, matmul_kernel

# name -> (block_m, block_k, block_n): tuned per shape regime
GEMM_LIBRARY = {
    "square_big": (256, 128, 256),   # large square-ish GEMMs
    "balanced": (128, 128, 128),     # default MXU tile
    "skinny_m": (8, 128, 128),       # small-M (decode-style GEMV-ish)
    "skinny_n": (128, 128, 8),       # small-N
    "deep_k": (128, 512, 128),       # reduction-dominated
}


def select_gemm_version(m: int, k: int, n: int) -> Optional[str]:
    """Pick a library kernel for a runtime shape; None -> vendor (XLA)."""
    def fits(name):
        bm, bk, bn = GEMM_LIBRARY[name]
        return m % bm == 0 and k % bk == 0 and n % bn == 0

    if m >= 1024 and n >= 1024 and fits("square_big"):
        return "square_big"
    if m <= 32 and fits("skinny_m"):
        return "skinny_m"
    if n <= 32 and fits("skinny_n"):
        return "skinny_n"
    if k >= 4 * max(m, n) and fits("deep_k"):
        return "deep_k"
    if fits("balanced"):
        return "balanced"
    return None  # vendor library (XLA dot)


def matmul(a: jax.Array, b: jax.Array, *,
           version: Optional[str] = None) -> jax.Array:
    m, k = a.shape
    _, n = b.shape
    if version is None:
        version = select_gemm_version(m, k, n)
    if version is None:
        return jnp.dot(a, b)  # vendor entry
    bm, bk, bn = GEMM_LIBRARY[version]
    return matmul_kernel(a, b, block_m=bm, block_k=bk, block_n=bn)


# block-size preference ladders for the fused (kDot) entry: the largest
# aligned version wins; misaligned sizes are padded up to the smallest.
# M is only ever a sublane (second-to-last) block dim, which Mosaic takes
# in multiples of 8; N and K are the lane (last) dim of some operand's
# block, which it takes in multiples of 128 or as the whole dim.
_FUSED_M_BLOCKS = (128, 64, 32, 16, 8)
_FUSED_N_BLOCKS = (128,)
_FUSED_K_BLOCKS = (512, 256, 128)
_LANES = 128


def _pick_block(size: int, prefs: Tuple[int, ...]) -> Tuple[int, int]:
    """(block, padded_size): largest preferred block dividing ``size``, else
    the smallest block with ``size`` rounded up to its multiple."""
    for b in prefs:
        if size % b == 0:
            return b, size
    b = prefs[-1]
    return b, ((size + b - 1) // b) * b


def _pick_lane_block(size: int, prefs: Tuple[int, ...]) -> Tuple[int, int]:
    """:func:`_pick_block` for a lane dim: a dim narrower than one lane
    tile is a single block spanning the whole dim, unpadded."""
    if size < _LANES:
        return size, size
    return _pick_block(size, prefs)


def matmul_fused(a: jax.Array, b: jax.Array, extras: Sequence[jax.Array],
                 epilogue: Callable, *, valid_mnk, out_dtypes: Sequence,
                 acc_dtype=None) -> List[jax.Array]:
    """(M, K) @ (K, N) with a fused elementwise epilogue (kDot).

    ``extras`` are (M, N) epilogue operands; ``valid_mnk`` the runtime
    actual sizes (ints or traced i32 scalars) masking the padded M/N/K
    tails.  Returns one (M, N) array per ``out_dtypes`` entry.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    bm, pm = _pick_block(m, _FUSED_M_BLOCKS)
    bn, pn = _pick_lane_block(n, _FUSED_N_BLOCKS)
    bk, pk = _pick_lane_block(k, _FUSED_K_BLOCKS)

    def pad2(x, rows, cols):
        pr, pc = rows - x.shape[0], cols - x.shape[1]
        return jnp.pad(x, ((0, pr), (0, pc))) if (pr or pc) else x

    a = pad2(a, pm, pk)
    b = pad2(b, pk, pn)
    extras = [pad2(x, pm, pn) for x in extras]
    outs = matmul_epilogue_kernel(
        a, b, extras, epilogue, valid_mnk, list(out_dtypes),
        acc_dtype=acc_dtype if acc_dtype is not None else jnp.float32,
        block_m=bm, block_k=bk, block_n=bn)
    if (pm, pn) != (m, n):
        outs = [o[:m, :n] for o in outs]
    return list(outs)
