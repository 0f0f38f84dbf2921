"""What the metric readers share: the window's launches, requests and
percentiles, from a run record (``harness.Cell.run``)."""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

__all__ = ["prefills", "decodes", "window_requests", "percentile",
           "peak_flops", "peak_bw"]


def prefills(run: Dict) -> List:
    """Prefill groups that returned inside the window: (start, end, lens)."""
    return [p for p in run["prefills"] if p[1] <= run["closed_at"]]


def decodes(run: Dict) -> List:
    """Decode steps that returned inside the window: (start, end, fills)."""
    return [d for d in run["decodes"] if d[1] <= run["closed_at"]]


def window_requests(run: Dict) -> List[Dict]:
    """Requests that arrived inside the window (all that were sent)."""
    return [r for r in run["requests"] if r["arrival"] < run["seconds"]]


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) \
        else None


def peak_flops(run: Dict) -> Optional[float]:
    return run["peaks"]["flops_bf16"] if run.get("peaks") else None


def peak_bw(run: Dict) -> Optional[float]:
    return run["peaks"]["hbm_bw"] if run.get("peaks") else None
