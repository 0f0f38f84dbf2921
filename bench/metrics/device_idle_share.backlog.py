"""Device: ``device_idle_share`` in a cell judged by
``output_tok_s.backlog``: under a backlog the idle share is the host's
time between steps."""
from bench.metrics.device_idle_share import read  # noqa: F401
