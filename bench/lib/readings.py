"""What the metric readers share: the window's launches, requests and
percentiles, from a run record (``harness.Cell.run``).

Times are seconds from the window's opening. A closed loop runs a
lead-in before it, whose launches, requests and tokens fall before 0:
the readers count none of them."""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

__all__ = ["prefills", "decodes", "window_requests", "percentile",
           "peak_flops", "peak_bw"]


def prefills(run: Dict) -> List:
    """Prefill groups launched and returned inside the window: (start,
    end, lens)."""
    return [p for p in run["prefills"]
            if 0 <= p[0] and p[1] <= run["closed_at"]]


def decodes(run: Dict) -> List:
    """Decode steps launched and returned inside the window: (start,
    end, fills)."""
    return [d for d in run["decodes"]
            if 0 <= d[0] and d[1] <= run["closed_at"]]


def window_requests(run: Dict) -> List[Dict]:
    """Requests that arrived (open loop) or were sent (closed loop)
    inside the window."""
    return [r for r in run["requests"]
            if 0 <= r["arrival"] < run["seconds"]]


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) \
        else None


def peak_flops(run: Dict) -> Optional[float]:
    return run["peaks"]["flops_bf16"] if run.get("peaks") else None


def peak_bw(run: Dict) -> Optional[float]:
    return run["peaks"]["hbm_bw"] if run.get("peaks") else None
