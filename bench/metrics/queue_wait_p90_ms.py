"""Serving engine: 90th percentile of the time from a request's
scheduled arrival to the end of the step after which it holds a slot
(the harness reads ``engine.slots`` after each step)."""
from bench.lib.readings import percentile, window_requests


def read(run):
    xs = [(r["admit"] if r["admit"] is not None else run["end"])
          - r["arrival"] for r in window_requests(run)]
    p = percentile(xs, 90)
    return None if p is None else 1000.0 * p
