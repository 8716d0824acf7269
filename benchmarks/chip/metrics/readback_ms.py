"""Read-back: per round, the summed ``readback`` spans (each
device-to-host read of an accumulator: a mid's result and its publish,
the controller top fold's result); the mean over the window's
rounds."""


def read(ctx):
    xs = [sum(s.dur_s for s in spans) for spans in (
        r["trace"].spans_of("readback") for r in ctx.rounds
        if r.get("trace") is not None) if spans]
    return 1e3 * sum(xs) / len(xs) if xs else None
