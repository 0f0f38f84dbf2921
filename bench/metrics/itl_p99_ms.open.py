"""``itl_p99_ms`` in an open-loop cell at low load: its tail is that of
the few gaps that hold a prefill launch (about 5 of 440 in a window), so
whether the p99 lands on one of them turns on a single gap, and runs of
one seed read about 25 ms or about 100 ms. Read per layer; the prefill
that sets it moves ``ttft_p90_ms``."""
from bench.metrics.itl_p99_ms import read  # noqa: F401
