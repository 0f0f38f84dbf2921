"""Kernels: the least time of the window's decode steps over their
device time. A step's least bytes are the weights read once, each
active row's K/V up to its true length and the one new position written
(bench/lib/flops.py), not the whole max_seq cache the program moves; the
device time is that of the decode program in the profiler trace."""
from bench.lib import flops, models
from bench.lib.readings import decodes, peak_bw, peak_flops


def read(run):
    tr = run.get("trace")
    if not tr or not tr["program_s"]["decode"] or not run.get("peaks"):
        return None
    m, a = models.of_run(run), run["arch"]
    least = sum(flops.least_time(flops.decode_flops(m, a, d[2]),
                                 flops.decode_bytes(m, a, d[2]),
                                 peak_flops(run), peak_bw(run))
                for d in decodes(run))
    return 100.0 * least / tr["program_s"]["decode"]
