"""Staged compiler and dispatch: the share of prefilled (B, S) bucket
positions that held no prompt token, over the window's prefill launches.
The bucket positions are the prefill artifact's ``bucket_hits`` counted
over the window, times B x S; the true tokens are the launched lengths."""
from bench.lib.readings import prefills


def read(run):
    padded = sum(n * b * s for k, n in run["prefill_buckets"].items()
                 for b, s in [map(int, k.split(","))])
    if not padded:
        return None
    true = sum(sum(p[2]) for p in prefills(run))
    return 100.0 * (1.0 - true / padded)
