"""Serving engine: rows (tokens) per decode launch inside the window."""
from bench.lib.readings import decodes


def read(run):
    ds = decodes(run)
    return sum(len(d[2]) for d in ds) / len(ds) if ds else None
