"""Gateway: 95th percentile of the admitting ``AggregationService.submit``
call, timed exactly by the pusher, over the submissions admitted in the
window."""
from chipbench import stats


def read(ctx):
    xs = [s.admit_s for s in ctx.sent.values()
          if ctx.t0 <= s.t_admit <= ctx.t_last]
    return 1e3 * stats.quantile(xs, 0.95) if xs else None
