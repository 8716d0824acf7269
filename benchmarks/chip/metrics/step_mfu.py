"""Whole round: the share of the chip's peak that the published work of
the traced window needed: the least device time of its rounds at the
peaks (each update read once, the parameters read and written once per
round, from shapes) over the window's length."""
from chipbench import cost, peaks


def read(ctx):
    if not ctx.rounds or ctx.window_s <= 0:
        return None
    peak = peaks.peak_for(ctx.device_kind)
    least, _b, _f = cost.window_min_seconds(
        ctx.n, [int(r["outcome"].accepted) for r in ctx.rounds],
        ctx.update_dtype, peak)
    return 100.0 * least / ctx.window_s
