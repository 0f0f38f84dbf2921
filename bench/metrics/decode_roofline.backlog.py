"""Kernels: ``decode_roofline`` in a cell judged by
``output_tok_s.backlog``."""
from bench.metrics.decode_roofline import read  # noqa: F401
