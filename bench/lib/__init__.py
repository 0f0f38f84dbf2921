"""Shared code of the benchmark: traffic, weights, reference, FLOP counts, peaks, trace reduction and the harness."""
