"""Kernels: the least time of the window's prefills over their device
time. The least time of a launch is the larger of its operations over
peak bf16 FLOP/s and its bytes over HBM bandwidth, counted from the true
prompt lengths (bench/lib/flops.py); the device time is that of the
prefill programs in the profiler trace, inside the window."""
from bench.lib import flops, models
from bench.lib.readings import peak_bw, peak_flops, prefills


def read(run):
    tr = run.get("trace")
    if not tr or not tr["program_s"]["prefill"] or not run.get("peaks"):
        return None
    m, a = models.of_run(run), run["arch"]
    least = sum(flops.least_time(flops.prefill_flops(m, a, p[2]),
                                 flops.prefill_bytes(m, a, p[2]),
                                 peak_flops(run), peak_bw(run))
                for p in prefills(run))
    return 100.0 * least / tr["program_s"]["prefill"]
