"""Server step: per round, the service's close stamp (taken once the
published parameters are ready on the device) minus the end of the
round's ``round`` span; the mean over the window's rounds.  Both are
``perf_counter`` stamps of one process."""


def read(ctx):
    xs = []
    for r in ctx.rounds:
        tr = r.get("trace")
        spans = tr.spans_of("round") if tr is not None else []
        if spans:
            end = spans[0].t0 + spans[0].dur_s
            xs.append(r["t_close"] - end)
    return 1e3 * sum(xs) / len(xs) if xs else None
