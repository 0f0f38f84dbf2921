"""Staged compiler and dispatch: host time of the prefill artifact's
generated dispatch per launch (padding, bucket lookup, staging), from
its ``host_dispatch_seconds`` and ``calls`` counted over the window."""


def read(run):
    d = run["prefill_dispatch"]
    return 1e6 * d["host_s"] / d["calls"] if d["calls"] else None
