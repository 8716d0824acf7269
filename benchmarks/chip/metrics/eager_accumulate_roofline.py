"""Kernel: ``eager_accumulate`` (acc += w*u, the Pallas fold the
in-process engine runs once per arriving update) against its roofline:
the least bytes of each call at the chip's peak HBM bandwidth, over
the summed device time of the kernel's events in the trace."""
from chipbench import cost, peaks

KERNEL = "eager_accumulate"


def read(ctx):
    if ctx.trace is None:
        return None
    calls, secs = ctx.trace.kernel(KERNEL)
    if calls == 0 or secs <= 0:
        return None
    peak = peaks.peak_for(ctx.device_kind)
    least = calls * max(
        cost.accumulate_bytes(ctx.n, 1, ctx.update_dtype)
        / peak.hbm_bytes_per_s,
        cost.accumulate_flops(ctx.n, 1) / peak.flops_per_s)
    return 100.0 * least / secs
