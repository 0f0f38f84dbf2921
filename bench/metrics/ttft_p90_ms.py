"""90th percentile, over every request scheduled to arrive inside the
window, of the time from its scheduled arrival to the host time its
first token came back. Requests still waiting at the window's close are
served, with no new arrivals, until each has its first token; one that
failed counts the whole run."""
from bench.lib.readings import percentile, window_requests


def read(run):
    xs = [(r["tokens"][0] if r["tokens"] else run["end"]) - r["arrival"]
          for r in window_requests(run)]
    p = percentile(xs, 90)
    return None if p is None else 1000.0 * p
