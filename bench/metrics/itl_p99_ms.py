"""99th percentile of the gap between consecutive tokens of one request,
over every gap that closed inside the window."""
from bench.lib.readings import percentile


def read(run):
    s = run["seconds"]
    gaps = [b - a for r in run["requests"]
            for a, b in zip(r["tokens"], r["tokens"][1:]) if b <= s]
    p = percentile(gaps, 99)
    return None if p is None else 1000.0 * p
