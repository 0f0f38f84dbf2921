"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
16 GB of HBM at 819 GB/s. A device kind that is not listed is an error,
never a default.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

__all__ = ["Peaks", "PEAKS", "peaks_for"]


@dataclass(frozen=True)
class Peaks:
    flops_bf16: float     # FLOP/s
    hbm_bw: float         # bytes/s
    hbm_bytes: float      # bytes of device memory


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
