"""``output_tok_s`` in a cell whose clients keep the engine saturated.
There the host's time between steps and its stalls set the rate, and
runs of one seed spread 0.7-0.9% on one v5e, more than half of
``output_tok_s``'s bound; so the split carries a bound of its own."""
from bench.metrics.output_tok_s import read  # noqa: F401
