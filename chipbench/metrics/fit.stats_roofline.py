"""Share of the roofline of the fit's statistics pass.

The least time of the pass's work, counted from (n, p, N) by
``chipbench.lib.work`` at the chip's peaks, times the fits traced, over the
device's busy time in the traced window (which holds whole fits only).  No
kernel or program name enters: whatever implements the pass, the same work
is counted against all the device time the fits took.
"""
from chipbench.lib.work import least_time, stats_pass_work

UNIT = "%"


def read(ctx):
    rec = ctx.record
    if ctx.trace is None or "fits" not in rec or ctx.trace["busy_ns"] <= 0:
        return None
    flops, nbytes = stats_pass_work(rec["n"], rec["p"], rec["n_features"])
    t_least, _ = least_time(flops, nbytes, ctx.peaks)
    return 100.0 * rec["fits"] * t_least / (ctx.trace["busy_ns"] * 1e-9)
