"""Model step: the least weight bytes a decode step reads, per token it
decodes, over the window's decode steps (MB), from the configuration's
model module."""
from bench.lib import models
from bench.lib.readings import decodes


def read(run):
    ds = decodes(run)
    toks = sum(len(d[2]) for d in ds)
    if not toks:
        return None
    m, a = models.of_run(run), run["arch"]
    return len(ds) * m.decode_weight_bytes(a) / toks / 1e6
