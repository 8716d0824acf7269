"""Engine upload: host-to-device bytes over the time spent in the
``fold.upload`` spans (each ``jnp.asarray`` of an update, a K-way slab
or a partial in ``JaxEngine``) of the window's rounds.  Each span waits
for its copy to land on the device, so this is the copy's rate as the
host sees it, from the call to the data being there."""


def read(ctx):
    spans = [s for r in ctx.rounds if r.get("trace") is not None
             for s in r["trace"].spans_of("fold.upload")]
    secs = sum(s.dur_s for s in spans)
    return sum(s.n for s in spans) / secs / 1e9 if secs > 0 else None
