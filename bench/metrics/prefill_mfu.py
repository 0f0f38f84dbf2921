"""Model step: model FLOPs of the window's true prompt tokens over the
host wall of their prefill launches (from the gather of rows to the
first tokens back on the host) times peak bf16 FLOP/s."""
from bench.lib import flops, models
from bench.lib.readings import peak_flops, prefills


def read(run):
    ps = prefills(run)
    wall = sum(p[1] - p[0] for p in ps)
    if not wall or not run.get("peaks"):
        return None
    m, a = models.of_run(run), run["arch"]
    work = sum(flops.prefill_flops(m, a, p[2]) for p in ps)
    return 100.0 * work / (wall * peak_flops(run))
