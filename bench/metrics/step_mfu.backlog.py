"""Model step: ``step_mfu`` in a cell judged by
``output_tok_s.backlog``."""
from bench.metrics.step_mfu import read  # noqa: F401
