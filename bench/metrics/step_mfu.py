"""Model step: model FLOPs of every prefill and decode token served
inside the window, over the window's seconds times peak bf16 FLOP/s."""
from bench.lib import flops
from bench.lib.readings import decodes, peak_flops, prefills


def read(run):
    if not run.get("peaks"):
        return None
    a = run["arch"]
    work = (sum(flops.prefill_flops(a, p[2]) for p in prefills(run))
            + sum(flops.decode_flops(a, d[2]) for d in decodes(run)))
    return 100.0 * work / (run["seconds"] * peak_flops(run))
