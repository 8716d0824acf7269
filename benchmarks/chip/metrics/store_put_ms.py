"""Object store: the window's rounds' ``store.put`` spans (each update
the dispatch loop puts, and each partial a mid publishes), summed and
divided by the updates those rounds folded."""


def read(ctx):
    traces = [r["trace"] for r in ctx.rounds if r.get("trace") is not None]
    puts = [s.dur_s for t in traces for s in t.spans_of("store.put")]
    folded = sum(int(r["outcome"].accepted) for r in ctx.rounds)
    return 1e3 * sum(puts) / folded if puts and folded else None
