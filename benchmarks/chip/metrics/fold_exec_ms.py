"""Engine: measured fold execution per folded update.  The window's
rounds' ``fold.mid`` and ``fold.top`` spans carry the same samples the
sidecars file under ``fold/exec_s`` (each synced by ``JaxEngine.sync``),
summed and divided by the updates those rounds folded."""


def read(ctx):
    secs = sum(r["trace"].sum_kind("fold.mid") + r["trace"].sum_kind(
        "fold.top") for r in ctx.rounds if r.get("trace") is not None)
    folded = sum(int(r["outcome"].accepted) for r in ctx.rounds)
    return 1e3 * secs / folded if folded and secs > 0 else None
