"""Gateway queue: the exact 95th percentile of the window's rounds'
``queue.wait`` spans, one per update: queued by ``submit_update`` to
pulled by the service's cohort feed."""
from chipbench import stats


def read(ctx):
    xs = [s.dur_s for r in ctx.rounds if r.get("trace") is not None
          for s in r["trace"].spans_of("queue.wait")]
    return stats.quantile(xs, 0.95) if xs else None
