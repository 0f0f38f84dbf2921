"""The benchmark: harness (lib), configurations, traffic mixes and metric readers; see run.py."""
